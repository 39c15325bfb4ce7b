"""The served store channel (the benchmark's cell ``served_streams``: q3
and q55 on closed-loop streams through one ``QueryServer``), on the
benchmark generator's tables at a twentieth of SF1, with 1, 2 and 4 streams
in flight.

What the cell needs of the engine is pinned here at a small size: every
stream gets the exact answer to its own text (the plain numpy reference's,
and row for row the single session's); a query's summary counts what the
query did and nothing of its peers (over any round, the summaries add up to
the process's delta, and a text counts the same in a crowd as alone); what
a served query waits for before its work begins is in its span tree
(``serve.queue``, ``serve.admit``, ``serve.lookup``, ``device.permit``) and
``Submission.info["stages"]`` is read off the same intervals.
"""

import sys
import threading

import pytest

from spark_rapids_tpu.aux import tracing
from spark_rapids_tpu.aux import transitions as TR
from spark_rapids_tpu.exec import stage_compiler as SC

SEED = 2147493319
#: store_sales 144,020 rows, as ``tests/test_star_join_rollup.py``
SCALE_DOWN = 20
STREAMS = (1, 2, 4)
#: a stream's texts, by the member of the text's domain: every stream has
#: literals of its own
DRAWS = {"q3": (11, 407, 1203, 1999), "q55": (5, 333, 640, 998)}
TIMEOUT_S = 600


def _process_counts() -> dict:
    totals = TR.totals()
    return {"dispatches": SC.stats()["dispatches"],
            "sync_count": totals["sync_count"],
            "d2h_count": totals["d2h_count"]}


def _summary_counts(summary: dict) -> dict:
    return {"dispatches": summary["dispatches"],
            "sync_count": summary["transitions"]["sync_count"],
            "d2h_count": summary["transitions"]["d2h_count"]}


def _summary_of(tag: str) -> dict:
    """The summary of the served query submitted under ``tag``."""
    found = [s for s in tracing.recent_summaries()
             if s["description"] == "serve:" + tag]
    assert len(found) == 1, (tag, len(found))
    return found[0]


@pytest.fixture(scope="module")
def served():
    """The cell as the benchmark builds it, a session over its tables,
    every text answered alone by the session (once the programs are
    built), then rounds of 1, 2 and 4 closed-loop streams through one
    server with default serving conf, each round with the process's counts
    on both sides of it."""
    from benchmark import run as bench
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.serving.server import QueryServer
    from spark_rapids_tpu.session import TpuSession
    cell = bench.Cell("served_streams", SCALE_DOWN)
    gen, tables = bench.make_tables(cell, SEED)
    session = TpuSession(TpuConf(dict(cell.config["session_conf"])))
    for name, table in tables.items():
        session.create_or_replace_temp_view(
            name, session.create_dataframe(
                table, num_partitions=int(cell.config["partitions"])))
    # stream i: its own q3 and q55, begun at its own offset of the rotation
    scripts = []
    for i in range(max(STREAMS)):
        names = list(cell.traffic["texts"])
        names = names[i % 2:] + names[:i % 2]
        scripts.append([(q, cell.queries[q].nth(DRAWS[q][i]))
                        for q in names])
    # a first query of each text builds the programs; the steady calls that
    # ``dispatches`` counts begin with the second
    for q in cell.traffic["texts"]:
        session.sql(cell.queries[q].fill(cell.queries[q].nth(0))).collect()
    alone = {}
    for script in scripts:
        for q, params in script:
            text = cell.queries[q].fill(params)
            rows = session.sql(text).collect()
            alone[text] = {"rows": rows,
                           "summary": tracing.last_query_summary()}
    server = QueryServer(session=session)
    rounds = {}
    old_interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)     # the threads change places often
    try:
        for n in STREAMS:
            # the same texts again: computed again, not read back
            server.result_cache.clear()
            records, errors = [], []

            def client(i, n=n, records=records, errors=errors):
                try:
                    for k, (q, params) in enumerate(scripts[i]):
                        text = cell.queries[q].fill(params)
                        tag = f"r{n}.s{i}.{k}"
                        sub = server.submit(text, tag=tag)
                        rows = sub.result(TIMEOUT_S)
                        records.append({"q": q, "params": params,
                                        "text": text, "tag": tag,
                                        "rows": rows, "sub": sub})
                except BaseException as e:  # noqa: BLE001 - shown below
                    errors.append(e)

            before = _process_counts()
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(TIMEOUT_S)
            assert not any(t.is_alive() for t in threads)
            assert not errors, errors
            after = _process_counts()
            for r in records:
                r["summary"] = _summary_of(r["tag"])
            rounds[n] = {"records": records, "before": before,
                         "after": after}
    finally:
        sys.setswitchinterval(old_interval)
    yield {"cell": cell, "gen": gen, "session": session, "server": server,
           "alone": alone, "rounds": rounds}
    server.stop()
    session.stop()


@pytest.mark.parametrize("n", STREAMS)
def test_every_stream_gets_the_reference_answer_to_its_own_text(served, n):
    from benchmark import run as bench
    from benchmark.compare import compare
    records = served["rounds"][n]["records"]
    assert len(records) == 2 * n
    for r in records:
        answer = bench.load_by_name("reference", r["q"]).run(
            served["gen"], r["params"])
        got = compare(r["rows"], answer)
        assert got["groups"] > 0, "the draw keeps no row: nothing compared"
        assert got["rows_wrong"] == 0, (r["tag"], got)
        assert got["max_rel_err"] <= 1e-9, (r["tag"], got)


@pytest.mark.parametrize("n", STREAMS)
def test_a_served_answer_is_the_single_sessions_row_for_row(served, n):
    for r in served["rounds"][n]["records"]:
        assert r["rows"] == served["alone"][r["text"]]["rows"], r["tag"]
        assert r["sub"].info["resolved"] in ("planned", "plan_cache")


@pytest.mark.parametrize("n", STREAMS)
def test_the_summaries_add_up_to_what_the_process_did(served, n):
    """Over a round of queries that overlapped (or, with one stream, did
    not), the sum of the summaries' counts is the process's delta: nothing
    is counted twice and nothing is a peer's."""
    rnd = served["rounds"][n]
    summed = {k: sum(_summary_counts(r["summary"])[k]
                     for r in rnd["records"]) for k in rnd["before"]}
    delta = {k: rnd["after"][k] - rnd["before"][k] for k in rnd["before"]}
    assert summed == delta
    assert delta["dispatches"] > 0 and delta["d2h_count"] >= 2 * n


@pytest.mark.parametrize("n", STREAMS)
def test_a_text_counts_the_same_in_a_crowd_as_alone(served, n):
    for r in served["rounds"][n]["records"]:
        alone = served["alone"][r["text"]]["summary"]
        assert _summary_counts(r["summary"]) == _summary_counts(alone), \
            r["tag"]
        assert r["summary"]["dispatches_by_kind"] \
            == alone["dispatches_by_kind"], r["tag"]
        assert r["summary"]["sized_joins"] == alone["sized_joins"]


def test_the_serve_spans_are_in_phases_and_the_stages_are_read_off_them(
        served):
    for n in STREAMS:
        for r in served["rounds"][n]["records"]:
            phases = r["summary"]["phases"]
            stages = r["sub"].info["stages"]
            for name in ("serve.queue", "serve.admit", "serve.lookup",
                         "plan.parse", "plan.analyze"):
                assert name in phases, (r["tag"], sorted(phases))
            assert abs(stages["queue_wait_s"]
                       - phases["serve.queue"]) < 1e-5
            assert abs(stages["admit_wait_s"]
                       - phases["serve.admit"]) < 1e-5
            # the lookup's spans hold the text's parse and analysis; a
            # phase is self time, a stage the span's whole length
            assert abs(stages["lookup_s"] - phases["serve.lookup"]
                       - phases["plan.parse"]
                       - phases["plan.analyze"]) < 1e-4
            assert stages["lookup_s"] > 0
            assert abs(sum(phases.values())
                       - r["summary"]["duration_s"]) < 1e-3
            # the submission's intervals are the ones the query adopted
            names = [name for name, _, _ in r["sub"].spans]
            assert names[0] == "serve.queue"
            assert names.count("serve.lookup") == 3
            # what the client waited is what the summary calls a duration,
            # but for the worker's hand-over on either side
            assert r["summary"]["duration_s"] <= r["sub"].info["latency_s"]
            assert r["summary"]["duration_s"] \
                > 0.9 * r["sub"].info["latency_s"] - 0.05


def test_the_summary_says_how_the_query_resolved(served):
    """The first round plans every text (the structure is new, or known
    with other literals); the later rounds find the exact plan cached."""
    first = served["rounds"][1]["records"]
    assert {r["summary"]["resolved"] for r in first} == {"planned"}
    assert {r["summary"]["plan_cache"] for r in first} \
        <= {"miss", "norm_hit"}
    for r in served["rounds"][4]["records"]:
        assert r["summary"]["resolved"] == r["sub"].info["resolved"]
        if r["text"] in {f["text"] for f in first}:
            assert r["summary"]["resolved"] == "plan_cache"
            assert r["summary"]["plan_cache"] == "hit"
    totals = served["server"].stats()["plan_cache"]
    outcomes = [r["summary"]["plan_cache"] for n in STREAMS
                for r in served["rounds"][n]["records"]]
    assert outcomes.count("hit") == totals["hits"]
    assert outcomes.count("norm_hit") == totals["norm_hits"]
    assert len(outcomes) == totals["hits"] + totals["misses"] \
        + totals["busy_bypass"]


def test_an_exact_repeat_is_answered_from_the_result_cache_and_opens_no_query(
        served):
    r = served["rounds"][4]["records"][0]
    held = len(tracing.recent_summaries())
    sub = served["server"].submit(r["text"], tag="again")
    assert sub.result(TIMEOUT_S) == r["rows"]
    assert sub.info["resolved"] == "result_cache"
    assert len(tracing.recent_summaries()) == held
    # no query, so no tree: the stages still read the submission's spans
    assert [name for name, _, _ in sub.spans] \
        == ["serve.queue", "plan.parse", "plan.analyze", "serve.lookup"]
    assert sub.info["stages"]["lookup_s"] > 0
    assert sub.info["stages"]["execute_s"] == 0


def test_the_second_of_two_submissions_to_one_worker_waits_in_serve_queue(
        served):
    from spark_rapids_tpu.serving.server import QueryServer
    session, cell = served["session"], served["cell"]
    key = "spark.rapids.serving.maxConcurrentQueries"
    session.set_conf(key, "1")
    try:
        one = QueryServer(session=session)
    finally:
        session.set_conf(key, "4")
    try:
        texts = [cell.queries["q55"].fill(cell.queries["q55"].nth(i))
                 for i in (71, 72)]
        subs = [one.submit(t, tag=f"one.{k}") for k, t in enumerate(texts)]
        for sub in subs:
            sub.result(TIMEOUT_S)
    finally:
        one.stop()
    first, second = (_summary_of(f"one.{k}") for k in range(2))
    # the second sat in the queue while the one worker ran the first
    assert second["phases"]["serve.queue"] \
        > 0.5 * first["phases"]["exec.run"] > 0
    assert abs(subs[1].info["stages"]["queue_wait_s"]
               - second["phases"]["serve.queue"]) < 1e-5
    assert second["duration_s"] > second["phases"]["serve.queue"]


def test_with_one_device_permit_a_peer_waits_in_device_permit(served):
    from spark_rapids_tpu.memory.device_manager import get_runtime
    server, cell = served["server"], served["cell"]
    semaphore = get_runtime().semaphore
    old = semaphore.resize(1)
    try:
        texts = [cell.queries["q3"].fill(cell.queries["q3"].nth(i))
                 for i in (81, 82, 83)]
        subs = [server.submit(t, tag=f"permit.{k}")
                for k, t in enumerate(texts)]
        for sub in subs:
            sub.result(TIMEOUT_S)
    finally:
        semaphore.resize(old)
    summaries = [_summary_of(f"permit.{k}") for k in range(3)]
    waited = [s["phases"].get("device.permit", 0.0) for s in summaries]
    assert max(waited) > 0, waited
    for s, w in zip(summaries, waited):
        # the task metric and the span are one wait
        assert abs(s["semaphore_wait_s"] - w) < 0.05 + 0.1 * w
    # and no query of the rounds, two permits for at most four, is booked
    # a wait it did not make
    for r in served["rounds"][1]["records"]:
        assert "device.permit" not in r["summary"]["phases"]
        assert r["summary"]["semaphore_wait_s"] == 0


def test_attribution_keeps_every_update_under_many_threads():
    """More threads than cores, each inside a query of its own, noting
    dispatches and crossings as the layers do: no update is lost and none
    lands in a peer's summary."""
    threads_n, each = 16, 400
    start = _process_counts()
    queries = [tracing.QueryExecution(description=f"stress.{i}")
               for i in range(threads_n)]
    program = SC.StageProgram("stress.kind", ("stress",), lambda: None)

    def work(qe, i):
        with qe:
            for _ in range(each + i):
                program._dispatch(lambda: None, ())
                TR.record_d2h(8, 0.0)

    old_interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(qe, i))
                   for i, qe in enumerate(queries)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(old_interval)
    assert not any(t.is_alive() for t in threads)
    for i, qe in enumerate(queries):
        s = qe.summary_dict
        assert s["dispatches"] == each + i
        assert s["dispatches_by_kind"] == {"stress.kind": each + i}
        assert s["transitions"]["d2h_count"] == each + i
        assert s["transitions"]["d2h_bytes"] == 8 * (each + i)
    after = _process_counts()
    total = sum(each + i for i in range(threads_n))
    assert after["dispatches"] - start["dispatches"] == total
    assert after["d2h_count"] - start["d2h_count"] == total
