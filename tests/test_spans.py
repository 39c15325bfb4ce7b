"""The span primitive of the query path (``aux.tracing.span``): one tree per
query with the vocabulary's phases, self times that add up, the counters of
the per-query summary, and the same spans in the profiler's trace beside
programs named ``jit_run_<kind>``."""

import glob
import os

import numpy as np
import pytest

from spark_rapids_tpu.aux import events as EV
from spark_rapids_tpu.aux import tracing as TR
from spark_rapids_tpu.aux.profiler import Profiler
from spark_rapids_tpu.columnar.column import bucket_rows
from spark_rapids_tpu.exec import stage_compiler as SC
from spark_rapids_tpu.expressions.base import Alias, col, lit

from tests.asserts import tpu_session

JOIN_AGG = ("SELECT name, SUM(qty) AS q FROM sales JOIN dim "
            "ON sales.sk = dim.sk GROUP BY name ORDER BY q DESC, name "
            "LIMIT 5")


def _star_session(extra=None):
    s = tpu_session({"spark.rapids.sql.test.enabled": "false",
                     **(extra or {})})
    rng = np.random.default_rng(7)
    n = 3000
    s.create_or_replace_temp_view("sales", s.create_dataframe({
        "sk": rng.integers(0, 40, n).astype(np.int64),
        "qty": rng.integers(1, 9, n).astype(np.int64)}, num_partitions=2))
    s.create_or_replace_temp_view("dim", s.create_dataframe({
        "sk": np.arange(40, dtype=np.int64),
        "name": np.array([f"item{i}" for i in range(40)], dtype=object)}))
    return s


def _traced(fn):
    """``fn()`` under a QueryExecution of the test's own, so the live span
    tree can be walked after it finished."""
    with TR.QueryExecution(description="test") as qe:
        out = fn()
    return qe, out


def _phase_tree(qe):
    """``[(name, parent's name among phase spans or 'query')]`` in the
    order the spans opened, and the spans by name."""
    by_id = {qe.root.span_id: qe.root}
    found = []

    def walk(sp, phase_parent):
        by_id[sp.span_id] = sp
        for c in sp.children:
            if c.kind == "phase":
                found.append((c, phase_parent))
                walk(c, c)
            else:
                walk(c, phase_parent)

    walk(qe.root, qe.root)
    found.sort(key=lambda pair: pair[0].span_id)
    return found


def test_one_tree_per_query_with_the_vocabularys_phases():
    s = _star_session()
    s.sql(JOIN_AGG).collect()                   # compile first
    df = s.sql(JOIN_AGG)
    assert [p[0] for p in df._planned] == ["plan.parse", "plan.analyze"]
    qe, rows = _traced(df.collect)
    assert len(rows) == 5 and df._planned == ()
    tree = _phase_tree(qe)
    names = [sp.name for sp, _ in tree]
    for want in ("plan.parse", "plan.analyze", "plan.rewrite", "exec.run",
                 "xfer.d2h", "result.rows"):
        assert want in names, names
    assert "exec.replay" not in names and names.count("plan.rewrite") == 1
    parents = {sp.name: parent.name for sp, parent in tree}
    for top in ("plan.parse", "plan.analyze", "plan.rewrite", "exec.run",
                "result.rows"):
        assert parents[top] == "query"
    # the download happens under the run, and the plan's nodes hang there
    assert parents["xfer.d2h"] == "exec.run"
    run = next(sp for sp, _ in tree if sp.name == "exec.run")
    assert qe._plan_span in run.children
    assert all(sp.end is not None and sp.end >= sp.start for sp, _ in tree)
    # every span of the tree belongs to the one query: one index, one id
    assert all(sp.span_id in qe._span_index for sp, _ in tree)
    summary = qe.summary_dict
    assert summary["query_id"] == qe.query_id
    assert set(summary["phases"]) >= {"plan.parse", "plan.rewrite",
                                      "exec.run", "xfer.d2h",
                                      "result.rows", "(unattributed)"}


def test_sql_collect_adopts_the_texts_planning_spans_once():
    s = _star_session()
    df = s.sql(JOIN_AGG)
    df.collect()
    first = TR.last_query_summary()
    assert first["phases"]["plan.parse"] > 0
    assert first["phases"]["plan.analyze"] > 0
    df.collect()                    # the same DataFrame again: planned once
    second = TR.last_query_summary()
    assert "plan.parse" not in second["phases"]
    assert second["query_id"] != first["query_id"]


def test_phases_add_up_to_the_querys_duration():
    s = _star_session()
    for _ in range(2):
        s.sql(JOIN_AGG).collect()
        summary = TR.last_query_summary()
        assert abs(sum(summary["phases"].values())
                   - summary["duration_s"]) < 1e-3
        assert all(v >= 0 for v in summary["phases"].values())
    # warm: what no span covers is small beside the run
    assert summary["phases"]["(unattributed)"] < summary["duration_s"]
    assert summary["phases"]["exec.run"] > 0


def test_self_time_is_duration_less_what_the_children_cover():
    """The rule on hand-made intervals: nested, overlapping threads, and
    adopted spans (before the root, one inside another, a gap after them
    that is nobody's)."""
    qe = TR.QueryExecution(description="hand-made")
    qe.root.start, qe.root.end = 100.0, 110.0

    def child(parent, name, start, end):
        sp = TR.Span(name, parent.span_id, kind="phase")
        sp.start, sp.end = start, end
        parent.children.append(sp)
        return sp

    child(qe.root, "serve.lookup", 89.0, 91.0)      # adopted: before root
    child(qe.root, "plan.parse", 90.0, 90.5)        # adopted, inside it
    run = child(qe.root, "exec.run", 101.0, 109.0)
    child(run, "xfer.d2h", 102.0, 105.0)
    child(run, "xfer.sync", 104.0, 106.0)           # another thread
    child(qe.root, "result.rows", 109.0, 109.5)
    got, early_s = qe._phase_self_times(110.0)
    assert got == {"serve.lookup": 1.5, "plan.parse": 0.5, "exec.run": 4.0,
                   "xfer.d2h": 2.0, "xfer.sync": 2.0, "result.rows": 0.5,
                   "(unattributed)": 1.5}
    assert early_s == 2.0
    assert sum(got.values()) == 10.0 + early_s


def test_an_overflowing_speculation_replays_and_says_so():
    """Five build rows a key: the pair table outgrows the optimistic bucket
    and its headroom, so the action runs again in exact mode."""
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    rng = np.random.default_rng(11)
    s.create_or_replace_temp_view("t", s.create_dataframe({
        "k": rng.integers(0, 9, 2000).astype(np.int64),
        "v": rng.standard_normal(2000)}, num_partitions=2))
    s.create_or_replace_temp_view("u", s.create_dataframe({
        "bk": np.repeat(np.arange(9, dtype=np.int64), 5),
        "m": np.arange(45, dtype=np.int64)}))
    q = ("SELECT u.m, SUM(t.v) AS sv FROM t JOIN u ON t.k = u.bk "
         "GROUP BY u.m ORDER BY u.m")
    qe, rows = _traced(lambda: s.sql(q).collect())
    assert len(rows) == 45
    tree = _phase_tree(qe)
    names = [sp.name for sp, _ in tree]
    assert names.count("exec.replay") == 1
    assert names.count("plan.rewrite") == 2 and names.count("exec.run") == 2
    parents = [(sp.name, parent.name) for sp, parent in tree]
    # the first pass under the root, the second under the replay
    assert parents.count(("exec.run", "query")) == 1
    assert parents.count(("exec.run", "exec.replay")) == 1
    assert parents.count(("plan.rewrite", "exec.replay")) == 1
    summary = qe.summary_dict
    assert summary["speculation_replays"] == 1
    assert summary["phases"]["exec.replay"] >= 0
    # the tree's exec spans are those of the plan that ran last
    replay_run = next(sp for sp, parent in tree
                      if sp.name == "exec.run" and
                      parent.name == "exec.replay")
    assert qe._plan_span in replay_run.children
    # a query that did not overflow counts none
    s.sql("SELECT COUNT(*) AS c FROM t").collect()
    assert TR.last_query_summary()["speculation_replays"] == 0


def test_dispatches_and_padded_rows_of_a_two_batch_plan():
    """Two partitions of 100 rows through one projection: by hand, the
    projection's program runs once a batch, and every batch is padded to
    the bucket of 100 rows."""
    s = tpu_session({"spark.rapids.sql.test.enabled": "false"})
    df = s.create_dataframe({"a": np.arange(200, dtype=np.int64)},
                            num_partitions=2) \
        .select(Alias(col("a") * lit(2), "b"))
    df.collect()                                # builds the programs
    before = SC.stats()
    rows = df.collect()
    after = SC.stats()
    assert [r["b"] for r in rows] == list(range(0, 400, 2))
    summary = TR.last_query_summary()
    by_kind = summary["dispatches_by_kind"]
    assert by_kind["expr.project"] == 2
    assert summary["dispatches"] == sum(by_kind.values())
    assert summary["dispatches"] == after["dispatches"] - before["dispatches"]
    assert 0 < summary["dispatch_s"] < summary["duration_s"]
    assert after["dispatches_by_kind"]["expr.project"] \
        - before["dispatches_by_kind"]["expr.project"] == 2
    # nothing was built in the second run, so nothing but dispatches ran
    assert after["compiles"] == before["compiles"]
    bucket = bucket_rows(100)
    project = next(n for n in summary["nodes"]
                   if n["node"] == "TpuProjectExec")
    assert project["device"] is True
    assert [p["padded_rows"] for p in project["partitions"]] \
        == [bucket, bucket]
    assert [p["batches"] for p in project["partitions"]] == [1, 1]
    scan = next(n for n in summary["nodes"]
                if n["node"] == "TpuInMemoryScanExec")
    assert sum(p["rows"] for p in scan["partitions"]) == 200
    assert sum(p["padded_rows"] for p in scan["partitions"]) == 2 * bucket
    assert summary["pair_rows_padded"] == 0     # no join
    assert summary["probe_gather_rounds"] == 0


def test_the_hash_join_notes_its_padded_pair_table():
    s = _star_session()
    s.sql(JOIN_AGG).collect()
    summary = TR.last_query_summary()
    # speculative sizing: probe bucket x SPECULATIVE_PAIR_HEADROOM a batch
    from spark_rapids_tpu.exec.joins import SPECULATIVE_PAIR_HEADROOM
    assert summary["pair_rows_padded"] > 0
    assert summary["pair_rows_padded"] % SPECULATIVE_PAIR_HEADROOM == 0
    assert summary["pair_rows_padded"] >= 3000


def test_the_hash_join_notes_the_gathers_of_its_probes():
    """``probe_gather_rounds``: the per-probe-row gathers the query's
    ``join.probe`` programs were built with, two a probed batch (the
    bucket-start table's ``starts[b]`` and ``starts[b + 1]``) where a
    binary search made two a round."""
    from spark_rapids_tpu.ops.join_ops import PROBE_GATHER_ROUNDS
    s = _star_session()
    s.sql(JOIN_AGG).collect()
    summary = TR.last_query_summary()
    probes = summary["dispatches_by_kind"]["join.probe"]
    assert probes >= 1 and PROBE_GATHER_ROUNDS == 2
    assert summary["probe_gather_rounds"] == PROBE_GATHER_ROUNDS * probes


def test_spans_outside_a_query_only_annotate():
    assert EV.active_query() is None
    with TR.span("plan.rewrite") as sp:
        assert sp is None
    TR.add_count("speculation_replays")         # nothing to count on
    s = _star_session({"spark.rapids.tpu.tracing.enabled": "false"})
    before = len(TR.recent_summaries())
    assert len(s.sql(JOIN_AGG).collect()) == 5
    assert len(TR.recent_summaries()) == before


def test_the_event_log_and_the_tools_carry_the_phase_spans(tmp_path):
    from spark_rapids_tpu.tools.reader import (profiles_from_events,
                                               read_events)
    from spark_rapids_tpu.tools.trace import build_trace
    log = tmp_path / "events.jsonl"
    s = _star_session({"spark.rapids.sql.eventLog.path": str(log)})
    s.sql(JOIN_AGG).collect()
    qid = TR.last_query_summary()["query_id"]
    events, diag = read_events(str(log))
    profiles, _ = profiles_from_events(events, diag)
    qp = next(p for p in profiles if p.query_id == qid)
    names = [sp.name for sp in qp.phases]
    assert names[:2] == ["plan.parse", "plan.analyze"]
    assert {"plan.rewrite", "exec.run", "xfer.d2h", "result.rows"} \
        <= set(names)
    d2h = next(sp for sp in qp.phases if sp.name == "xfer.d2h")
    assert d2h.metrics == {"site": "download"}
    # the plan's nodes stay a tree of their own for the profile's ranking
    assert all(sp.kind == "exec" for sp in qp.exec_spans())
    assert qp.summary["phases"]["exec.run"] > 0
    slices = [e for e in build_trace([qp])["traceEvents"]
              if e.get("cat") == "phase"]
    assert {e["name"] for e in slices} == set(names)
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in slices)


def test_explain_analyze_renders_the_plan_and_the_phases():
    s = _star_session()
    text = s.sql(JOIN_AGG).explain(analyze=True)
    plan_part = text.split("== Phases (self time) ==")[0]
    assert "Join" in plan_part and "exec.run" not in plan_part
    assert "exec.run=" in text and "dispatches=" in text
    assert "pair_rows_padded=" in text and "probe_gather_rounds=" in text
    assert "sized_joins=" in text


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    """One profiled run of the join query on the CPU backend: the rows
    with and without a trace running, the traced query's summary and the
    events of the xplane."""
    from jax.profiler import ProfileData
    s = _star_session()
    plain = s.sql(JOIN_AGG).collect()           # also builds the programs
    path = str(tmp_path_factory.mktemp("xplane"))
    try:
        with Profiler(path).scoped():
            traced = s.sql(JOIN_AGG).collect()
    except Exception as e:  # noqa: BLE001 - profiler availability varies
        pytest.skip(f"jax profiler unavailable here: {e}")
    summary = TR.last_query_summary()
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    assert found, "the profiler wrote no xplane"
    events = []
    for plane in ProfileData.from_file(found[0]).planes:
        for line in plane.lines:
            for e in line.events:
                events.append((e.name, dict(e.stats)))
    return plain, traced, summary, events


def test_rows_are_identical_with_and_without_a_trace_running(xplane):
    plain, traced, _summary, _events = xplane
    assert plain == traced and len(traced) == 5


def test_the_xplane_holds_the_querys_spans(xplane):
    _plain, _traced, summary, events = xplane
    qid = summary["query_id"]
    mine = {}
    for name, stats in events:
        if name.startswith("srt.") and stats.get("query_id") == qid:
            mine.setdefault(name, []).append(stats)
    for want in ("srt.plan.rewrite", "srt.exec.run", "srt.xfer.d2h",
                 "srt.result.rows", "srt.dispatch"):
        assert want in mine, sorted(mine)
    # the slice in the xplane and the span in the summary are one object:
    # they share the span's id
    assert all(st["span_id"] > 0 for st in mine["srt.exec.run"])
    assert mine["srt.xfer.d2h"][0]["site"] == "download"
    # one annotation a steady dispatch, carrying the program's kind
    assert len(mine["srt.dispatch"]) == summary["dispatches"]
    kinds = {}
    for st in mine["srt.dispatch"]:
        kinds[st["kind"]] = kinds.get(st["kind"], 0) + 1
    assert kinds == summary["dispatches_by_kind"]
    # the per-batch pulls carry the operator's name
    assert any(n.startswith("srt.exec.Tpu") and n.endswith("Exec")
               for n in mine)
    # parse and analyse ran before the query had an id
    outside = [st for name, st in events if name == "srt.plan.parse"]
    assert outside and all(st["query_id"] == EV.NO_QUERY for st in outside)


def test_the_xplane_names_programs_by_kind(xplane):
    """``jit_run_<kind>``: the name ``benchmark/trace/reduce.py`` shortens
    to the kind (``stage_compiler._counting``)."""
    _plain, _traced, summary, events = xplane
    modules = {stats["hlo_module"] for _name, stats in events
               if "hlo_module" in stats}
    ours = {m for m in modules if m.startswith("jit_run_")}
    assert ours, sorted(modules)[:20]
    kinds = {m[len("jit_run_"):] for m in ours}
    assert {"join.probe", "join.pair", "fused.agg_update"} <= kinds
    assert kinds <= set(summary["dispatches_by_kind"])


SCOPES = {"join.probe": ("hash", "lookup", "offsets"),
          "join.pair": ("expand", "verify"),
          "fused.agg_update": ("keys", "update")}


@pytest.fixture(scope="module")
def lowered():
    """``{kind: [lowered text with locations]}`` of every call the join
    query makes of a program of the three kinds."""
    texts: dict = {}
    real = SC.StageProgram.__call__

    def recording(self, *args):
        if self.kind in SCOPES:
            texts.setdefault(self.kind, []).append(
                self._fn.lower(*args).as_text(debug_info=True))
        return real(self, *args)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(SC.StageProgram, "__call__", recording)
        _star_session().sql(JOIN_AGG).collect()
    return texts


@pytest.mark.parametrize("kind", sorted(SCOPES))
def test_named_scopes_name_the_phases_inside_a_program(kind, lowered):
    """Each XLA op's ``op_name`` carries the engine's name for what it
    does (``jit(run[<kind>])/.../<scope>/<primitive>``), read from the
    lowered program as the device trace shows it."""
    import re
    assert lowered.get(kind), sorted(lowered)
    for text in lowered[kind]:
        seen = set(re.findall(
            r'"jit\(run\[' + re.escape(kind) + r'\]\)/(?:jit\(main\)/)?'
            r'([a-z]+)/', text))
        assert set(SCOPES[kind]) <= seen, (kind, sorted(seen))
