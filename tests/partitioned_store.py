"""What ``tests/test_partitioned_store.py`` and its two star files
(``tests/test_partitioned_store_q7.py``, ``..._q27.py``) share: the
benchmark generator's tables at a twentieth of SF1 registered in 1, 4 or 10
partitions, and every text of a file answered once in each layout.  Three
files, so that three xdist workers share the compiles and none is over
300 s (a 10-partition plan builds ten tasks' programs and a broadcast
build's ten-input concat, which the CPU compiler takes its time over)."""

import gc
import json
import os
import sys

from spark_rapids_tpu.aux import tracing

SEED = 2147493319
#: store_sales 144,020 rows, as ``tests/test_served_streams.py``
SCALE_DOWN = 20
ALL_TEXTS = ("q3", "q55", "q7_qual", "q27_qual")
LAYOUTS = (1, 4, 10)
THRESHOLD = "spark.sql.autoBroadcastJoinThreshold"
HERE = os.path.dirname(os.path.abspath(__file__))


def sections(explained: str) -> dict:
    """``explain()``'s text by section heading."""
    out, name = {}, None
    for line in explained.splitlines():
        if line.startswith("== ") and line.endswith(" =="):
            name = line[3:-3]
            out[name] = []
        elif name is not None:
            out[name].append(line)
    return out


def subtree(lines, at):
    """The lines under ``lines[at]`` (deeper indentation)."""
    depth = len(lines[at]) - len(lines[at].lstrip(" *!"))
    under = []
    for line in lines[at + 1:]:
        if len(line) - len(line.lstrip(" *!")) <= depth:
            break
        under.append(line)
    return under


def parents_plans() -> dict:
    """``explain()`` of the four texts at one partition at the parent of
    the PR that added the size rule (6056c83), literals ``nth(0)``."""
    with open(os.path.join(HERE, "store_plans_one_partition.json")) as f:
        return json.load(f)


def answered(texts, plans):
    """``{"runs": {(partitions, mode, text): explain, rows, summary}, ...}``
    for every ``(partitions, mode)`` of ``plans`` (mode ``rule``: default
    conf; ``shuffled``: the threshold at -1, no join broadcast), in that
    order; the last plan's session stays open (``sessions``) with its
    programs built.  The CPU compiler's programs are dropped between
    layouts (``tests/conftest.py`` says why)."""
    import jax
    from benchmark import run as bench
    from benchmark.literals import Query
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.session import TpuSession
    cell = bench.Cell("store_star_join", SCALE_DOWN)
    cell.queries = {q: Query(q) for q in ALL_TEXTS}
    gen, tables = bench.make_tables(cell, SEED)
    params = {q: cell.queries[q].nth(0) for q in texts}
    filled = {q: cell.queries[q].fill(params[q]) for q in texts}
    sessions, runs = {}, {}
    old_interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)     # sibling tasks change places often
    try:
        for n, mode in plans:
            if sessions:
                jax.clear_caches()
                gc.collect()
            conf = {"spark.rapids.sql.enabled": "true"}
            if mode == "shuffled":
                conf[THRESHOLD] = "-1"
            s = sessions[n, mode] = TpuSession(TpuConf(conf))
            for name, table in tables.items():
                s.create_or_replace_temp_view(
                    name, s.create_dataframe(table, num_partitions=n))
            for q in texts:
                df = s.sql(filled[q])
                runs[n, mode, q] = {
                    "explain": df.explain(), "rows": df.collect(),
                    "summary": tracing.last_query_summary()}
    finally:
        sys.setswitchinterval(old_interval)
    return {"gen": gen, "params": params, "texts": filled, "runs": runs,
            "sessions": sessions}


def same_rows(got, want) -> bool:
    """Row for row: every exact column equal, in the same order; a double
    (a sum or an average over partial aggregates, which another layout
    adds up in another order) within 1e-9 of the other's."""
    import math
    if len(got) != len(want):
        return False
    for a, b in zip(got, want):
        if a.keys() != b.keys():
            return False
        for k in a:
            x, y = a[k], b[k]
            if isinstance(x, float) and isinstance(y, float):
                if not math.isclose(x, y, rel_tol=1e-9, abs_tol=1e-12):
                    return False
            elif x != y:
                return False
    return True


def assert_reference_answer(store, n, q):
    from benchmark import run as bench
    from benchmark.compare import compare
    answer = bench.load_by_name("reference", q).run(store["gen"],
                                                    store["params"][q])
    rows = store["runs"][n, "rule", q]["rows"]
    got = compare(rows, answer)
    assert got["groups"] > 0, "the draw keeps no row: nothing compared"
    assert got["rows_wrong"] == 0 and got["max_rel_err"] <= 1e-9, got
    assert same_rows(rows, store["runs"][1, "rule", q]["rows"])


def assert_shuffled_same_rows(store, n, q):
    run = store["runs"][n, "shuffled", q]
    plan = sections(run["explain"])["TPU Plan"]
    assert not any("BroadcastHashJoin" in line for line in plan)
    assert any("HashPartitioning(" in line for line in plan)
    assert run["summary"]["broadcast_builds"] == 0
    assert same_rows(run["rows"], store["runs"][1, "rule", q]["rows"])


#: the layouts a star file answers its text in: by the rule in 1, 4 and 10
#: partitions, every join shuffled in 4
STAR_PLANS = [(1, "rule"), (4, "rule"), (4, "shuffled"), (10, "rule")]


def assert_every_dimension_broadcast_and_built_once(store, n, q):
    """At a twentieth of SF1 all four dimensions are under the threshold
    (at SF10 ``customer_demographics`` is not: ``PERF.md`` section 7): four
    broadcast joins, four builds whatever the number of probe tasks, and
    the fact table crosses no exchange."""
    run = store["runs"][n, "rule", q]
    plan = sections(run["explain"])["TPU Plan"]
    assert sum("BroadcastHashJoin" in line for line in plan) == 4
    assert not any("ShuffledHashJoin" in line or "SubPartitionHashJoin" in
                   line for line in plan)
    assert run["summary"]["broadcast_builds"] == 4
