"""The gateway's device-gap ledger (``aux/transitions.py``): a blocking
wait that finds the newest device work ready opens a gap, the next device
work closes it and books it to its query, as ``fetch`` (the same query
drained the device) or ``query`` (another did, or the previous query), by
the span the query's threads were in and by the fetch that drained it.

Bounds are one-sided and the sleeps at least 50 ms, so a loaded machine
can only lengthen what is asserted."""

import threading
import time

import numpy as np
import pytest

from spark_rapids_tpu.aux import tracing
from spark_rapids_tpu.aux import transitions as TR
from spark_rapids_tpu.aux.tracing import span
from spark_rapids_tpu.exec import stage_compiler as SC

from tests.asserts import tpu_session

SLEEP_S = 0.05
JOIN_AGG = ("SELECT name, SUM(qty) AS q FROM sales JOIN dim "
            "ON sales.sk = dim.sk WHERE qty > {lo} GROUP BY name "
            "ORDER BY q DESC, name LIMIT 5")
TIMEOUT_S = 300


def _session(extra=None):
    s = tpu_session({"spark.rapids.sql.test.enabled": "false",
                     **(extra or {})})
    rng = np.random.default_rng(11)
    n = 3000
    s.create_or_replace_temp_view("sales", s.create_dataframe({
        "sk": rng.integers(0, 40, n).astype(np.int64),
        "qty": rng.integers(1, 9, n).astype(np.int64)}))
    s.create_or_replace_temp_view("dim", s.create_dataframe({
        "sk": np.arange(40, dtype=np.int64),
        "name": np.array([f"item{i}" for i in range(40)], dtype=object)}))
    return s


@pytest.fixture(scope="module")
def session():
    s = _session()
    s.sql(JOIN_AGG.format(lo=0)).collect()      # builds the programs
    yield s
    s.stop()


def _program():
    """A built stage program of its own kind, called once (its compile)."""
    import jax.numpy as jnp
    prog = SC.get_or_build("test.gaps", ("add1",), lambda: lambda x: x + 1)
    x = jnp.arange(1024)
    prog(x)
    return prog, x


def _sums_to_the_gaps(gaps):
    assert sum(gaps["by_span"].values()) == pytest.approx(
        gaps["fetch_s"] + gaps["query_s"], abs=1e-5 * (1 + gaps["count"]))
    assert sum(gaps["by_site"].values()) == pytest.approx(
        gaps["fetch_s"] + gaps["query_s"], abs=1e-5 * (1 + gaps["count"]))


def test_a_fetch_then_a_sleep_in_a_span_then_a_dispatch_books_a_fetch_gap():
    prog, x = _program()
    with tracing.QueryExecution(description="fetch-gap") as qe:
        out = prog(x)
        TR.fetch(out, site="test-fetch")
        with span("test.sleep"):
            time.sleep(SLEEP_S)
        prog(x)
    gaps = qe.summary_dict["device_gaps"]
    assert gaps["fetch_s"] >= 0.9 * SLEEP_S
    assert gaps["by_span"]["test.sleep"] >= 0.9 * SLEEP_S
    assert gaps["by_site"]["test-fetch"] >= 0.9 * SLEEP_S
    start, end, kind, site = gaps["intervals"][-1]
    assert (kind, site) == ("fetch", "test-fetch")
    assert end - start >= 0.9 * SLEEP_S
    assert gaps["intervals_dropped"] == 0
    _sums_to_the_gaps(gaps)


def test_the_gap_is_open_until_device_work_closes_it():
    """Two fetches in a row keep the first's gap; work outside any query
    closes it in the process totals only."""
    prog, x = _program()
    out = prog(x)
    before = TR.totals()
    TR.fetch(out, site="first")
    time.sleep(SLEEP_S)
    TR.sync_int(out[0], site="second")
    prog(x)
    after = TR.totals()
    assert after["gap_count"] - before["gap_count"] == 1
    assert after["gap_seconds"] - before["gap_seconds"] >= 0.9 * SLEEP_S


def test_two_collects_in_a_row_book_a_query_gap_to_the_second(session):
    session.sql(JOIN_AGG.format(lo=1)).collect()
    time.sleep(SLEEP_S)
    session.sql(JOIN_AGG.format(lo=2)).collect()
    gaps = tracing.last_query_summary()["device_gaps"]
    # the first query's answer drained the device; the second query's
    # planning, and the sleep before it, ran with nothing queued
    assert gaps["query_s"] >= 0.9 * SLEEP_S
    assert gaps["by_span"]["(between queries)"] >= 0.9 * SLEEP_S
    planned = {k for k in gaps["by_span"] if k.startswith("plan.")}
    assert {"plan.parse", "plan.analyze", "plan.rewrite"} <= planned
    assert "query" in {kind for _s, _e, kind, _site in gaps["intervals"]}
    _sums_to_the_gaps(gaps)


def test_the_summary_carries_the_clock_anchor(session):
    session.sql(JOIN_AGG.format(lo=3)).collect()
    clock = tracing.last_query_summary()["clock"]
    now_mono, now_wall = time.monotonic(), time.time_ns()
    mapped = clock["wall_ns"] + 1e9 * (now_mono - clock["monotonic_s"])
    assert abs(mapped - now_wall) < 5e6
    assert clock is tracing.clock_anchor()      # one a process


def test_a_traced_span_lies_where_the_anchor_puts_it(tmp_path):
    """The profiler stamps ``srt.*`` events with ``time.time_ns()`` less
    its ``profile_start_time``: a span's start from the tree, moved by the
    anchor, is its annotation's start in the xplane."""
    import glob
    import os

    from jax.profiler import ProfileData

    from spark_rapids_tpu.aux.profiler import Profiler
    try:
        with Profiler(str(tmp_path)).scoped():
            with tracing.QueryExecution(description="anchor") as qe:
                with span("test.anchor") as sp:
                    time.sleep(0.01)
    except Exception as e:  # noqa: BLE001 - profiler availability varies
        pytest.skip(f"jax profiler unavailable here: {e}")
    found = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(found[0])
    start_time = None
    starts = []
    for plane in data.planes:
        start_time = dict(plane.stats).get("profile_start_time", start_time)
        for line in plane.lines:
            starts += [e.start_ns for e in line.events
                       if e.name == "srt.test.anchor"
                       and dict(e.stats).get("span_id") == sp.span_id]
    assert start_time is not None and len(starts) == 1
    clock = qe.summary_dict["clock"]
    mapped = (clock["wall_ns"] + 1e9 * (sp.start - clock["monotonic_s"])
              - start_time)
    assert abs(mapped - starts[0]) < 1e6


def test_four_overlapping_served_queries_add_up_to_the_process(session):
    from spark_rapids_tpu.serving.server import QueryServer
    server = QueryServer(session=session)
    try:
        tags, errors = [], []

        def client(i):
            try:
                for k in range(2):
                    tag = f"gaps.s{i}.{k}"
                    server.submit(JOIN_AGG.format(lo=i + 4 * k),
                                  tag=tag).result(TIMEOUT_S)
                    tags.append(tag)
            except BaseException as e:  # noqa: BLE001 - shown below
                errors.append(e)

        before = TR.totals()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(TIMEOUT_S)
        assert not any(t.is_alive() for t in threads)
        assert not errors, errors
        after = TR.totals()
    finally:
        server.stop()
    summaries = [s for s in tracing.recent_summaries()
                 if s["description"].startswith("serve:gaps.")]
    assert len(summaries) == len(tags) == 8
    gaps = [s["device_gaps"] for s in summaries]
    assert sum(g["count"] for g in gaps) == \
        after["gap_count"] - before["gap_count"] > 0
    assert sum(g["fetch_s"] + g["query_s"] for g in gaps) == pytest.approx(
        after["gap_seconds"] - before["gap_seconds"], abs=1e-4)
    for g in gaps:
        _sums_to_the_gaps(g)


def test_with_the_ledger_off_nothing_is_booked_and_nothing_is_asked(
        monkeypatch):
    from spark_rapids_tpu.config import TpuConf
    asked = []
    monkeypatch.setattr(TR, "_ready", lambda leaf: asked.append(leaf))
    monkeypatch.setattr(TR, "_smallest_leaf",
                        lambda out: asked.append(out))
    s = _session({"spark.rapids.sql.transitions.enabled": "false"})
    try:
        assert not TR.enabled()
        before = TR.totals()
        s.sql(JOIN_AGG.format(lo=1)).collect()
        prog, x = _program()
        prog(x)
        summary = tracing.last_query_summary()
        assert "device_gaps" not in summary and "transitions" not in summary
        assert TR.totals() == before
        assert asked == []
    finally:
        s.stop()
        TR.sync_from_conf(TpuConf({}))
    assert TR.enabled()


def test_a_dispatch_onto_a_drained_device_with_no_fetch_is_unplaced():
    prog, x = _program()
    prog(x)                         # closes any gap left open before
    with tracing.QueryExecution(description="unplaced") as qe:
        out = prog(x)
        out.block_until_ready()     # drained outside the gateway
        time.sleep(SLEEP_S)
        prog(x)
    gaps = qe.summary_dict["device_gaps"]
    assert gaps["unplaced"] >= 1
    assert gaps["count"] == 0 and gaps["fetch_s"] == gaps["query_s"] == 0


def test_take_fronts_eager_calls_close_a_gap_and_are_counted():
    from spark_rapids_tpu.columnar.batch import batch_from_pydict
    from spark_rapids_tpu.ops.batch_ops import take_front
    prog, x = _program()
    batch = batch_from_pydict({"a": np.arange(100, dtype=np.int64)}
                              ).to_device()
    with tracing.QueryExecution(description="eager") as qe:
        TR.fetch(prog(x), site="before-limit")
        time.sleep(SLEEP_S)
        front = take_front(batch, 7)
    assert front.row_count == 7
    gaps = qe.summary_dict["device_gaps"]
    assert gaps["eager_device_calls"] == 1
    assert gaps["by_site"]["before-limit"] >= 0.9 * SLEEP_S


def test_explain_analyze_renders_the_gaps(session):
    text = session.sql(JOIN_AGG.format(lo=5)).explain(analyze=True)
    assert "== Device gaps ==" in text and "fetch_s=" in text


def test_a_deleted_newest_output_is_never_asked_whether_it_is_ready():
    """jax's ``is_ready()`` of a deleted array crashes the process: the
    engine deletes through the gateway (``delete_arrays``, under the
    ledger's lock) and the ledger asks ``is_deleted()`` first."""
    prog, x = _program()
    out = prog(x)
    TR.delete_arrays([out, None])
    assert out.is_deleted()
    with tracing.QueryExecution(description="deleted") as qe:
        TR.sync_int(prog(x)[0], site="after-delete")
        prog(x)
    assert qe.summary_dict["device_gaps"]["by_site"]["after-delete"] >= 0
