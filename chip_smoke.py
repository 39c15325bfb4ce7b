"""chip_smoke.py — the quickest proof that the engine still starts on the chip.

Drives the engine's main path once on one TPU v5e through the entry points
a user calls (``TpuSession``, ``create_dataframe``, ``session.sql(...)
.collect()``, ``QueryServer.submit``) and checks every answer against a
reference.  One process, no ``JAX_PLATFORMS`` set here, no phase wrapped in
a ``try`` that lets the run end with 0: any failed check raises.

    python chip_smoke.py             # one chip: all phases
    python chip_smoke.py --chips 4   # four chips: ONLY the mesh phase

Each phase prints one JSON line as it finishes; the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Every number printed here is ONE smoke reading, not a benchmark.

The phase functions take their sizes as arguments so that
``tests/test_chip_smoke.py`` drives them in-process at a tiny size on the
CPU and ``scripts/tpu_rehearsal.py`` replays them at the smoke's size to
ask the chip's compiler about every program they build.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))

#: the smoke's sizes (one v5e chip, 16 GB HBM)
RESIDENT_ROWS = 128_000_000      # x (int64, float64, int32) = 2.56 GB in HBM
RESIDENT_PARTS = 4
RESIDENT_REF_ROWS = 8_000_000    # CPU-engine reference slice
TPCDS_SF = 96                    # repo sf=96: store_sales 2,880,000 rows
TPCDS_QUERIES = ("q3", "q7", "q1", "q12")
SERVING_Q3_MOYS = (12, 10, 9)    # literal variants of q3's d_moy = 11

#: operators that must never be placed on the host in the smoke's queries
_DEVICE_ONLY = ("Join", "Aggregate", "Sort", "Window", "Exchange",
                "TakeOrdered")


class SmokeFailure(AssertionError):
    """A smoke check that did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


# ---------------------------------------------------------------------------
# start-up: compile cache, device, native library
# ---------------------------------------------------------------------------

class CacheCounters:
    """Persistent-compilation-cache hits and misses as JAX reports them."""

    def __init__(self):
        self.hits = 0
        self.misses = 0

    def listen(self) -> "CacheCounters":
        import jax.monitoring

        def on_event(name: str, **_kw) -> None:
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_listener(on_event)
        return self


def place_compile_cache() -> str:
    """Where the compile cache lives: ``JAX_COMPILATION_CACHE_DIR`` when the
    environment places it (JAX reads the variable itself; nothing is
    assigned here), otherwise ``<checkout>/.jax_cache``."""
    import jax
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        placed = os.path.join(REPO, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", placed)
    # every stage program persists, not only the slow ones
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return placed


def phase_device(want_platform: str = "tpu", want_count: int = 1) -> dict:
    """Platform must be ``want_platform``, else fail before any work."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != want_platform:
        raise SmokeFailure(
            f"need platform {want_platform!r}, JAX found {d.platform!r} "
            f"({d.device_kind}); refusing to run on a stand-in device")
    check(len(devs) >= want_count,
          f"need {want_count} device(s), JAX found {len(devs)}")
    stats = d.memory_stats() or {}
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "bytes_limit": stats.get("bytes_limit")}
    emit("device", **info)
    return info


def build_native() -> dict:
    """``*.so`` is git-ignored: rebuild libtpucol from the committed source
    and load THAT, instead of trusting a library left in the working tree."""
    native_dir = os.path.join(REPO, "native")
    t0 = time.perf_counter()
    subprocess.run(["make", "-B", "-C", native_dir], check=True,
                   capture_output=True, timeout=300)
    from spark_rapids_tpu import native
    check(native.have_native(), "libtpucol built but did not load")
    return {"built": True, "loaded": True,
            "build_s": time.perf_counter() - t0}


def make_sessions(extra_conf: dict | None = None):
    from spark_rapids_tpu.config import TpuConf
    from spark_rapids_tpu.session import TpuSession
    conf = {"spark.rapids.sql.enabled": "true"}
    conf.update(extra_conf or {})
    tpu = TpuSession(TpuConf(conf))
    cpu = TpuSession(TpuConf({"spark.rapids.sql.enabled": "false"}),
                     init_device=False)
    return tpu, cpu


def _compile_stats() -> dict:
    from spark_rapids_tpu.exec import stage_compiler as SC
    return SC.stats()


def _timed_collect(df):
    t0 = time.perf_counter()
    rows = df.collect()
    return rows, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# phase: resident table
# ---------------------------------------------------------------------------

def build_resident_data(n_rows: int, seed: int = 7) -> dict:
    import numpy as np
    rng = np.random.default_rng(seed)
    return {
        "k": rng.integers(0, 1 << 20, n_rows).astype(np.int64),
        "v": rng.standard_normal(n_rows),
        "w": rng.integers(-1000, 1000, n_rows).astype(np.int32),
    }


def resident_query(df, threshold=0):
    """filter ``w > threshold``, project ``k+1``, ``v*2.0``,
    ``murmur3(k, w)``, global sums — every projected column is forced to
    materialize through the aggregation.  ``threshold`` rides a promoted
    literal slot: every threshold variant shares one compiled program."""
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.expressions import arithmetic as A
    from spark_rapids_tpu.expressions import hashing as H
    from spark_rapids_tpu.expressions import predicates as P
    from spark_rapids_tpu.expressions.base import Alias, col, lit
    return (df
            .filter(P.GreaterThan(col("w"), lit(threshold)))
            .select(Alias(A.Add(col("k"), lit(1)), "k1"),
                    Alias(A.Multiply(col("v"), lit(2.0)), "v2"),
                    Alias(H.Murmur3Hash(col("k"), col("w")), "h"))
            .agg(F.sum("k1").alias("sk"),
                 F.sum("v2").alias("sv"),
                 F.sum("h").alias("sh")))


def phase_resident(tpu, cpu, n_rows: int = RESIDENT_ROWS,
                   parts: int = RESIDENT_PARTS,
                   ref_rows: int = RESIDENT_REF_ROWS, seed: int = 7) -> dict:
    t0 = time.perf_counter()
    data = build_resident_data(n_rows, seed)
    datagen_s = time.perf_counter() - t0
    # plain numpy reference at full size
    keep = data["w"] > 0
    ref_sk = int((data["k"][keep] + 1).sum())
    ref_sv = float((data["v"][keep] * 2.0).sum())

    table = tpu.create_dataframe(data, num_partitions=parts)
    before = _compile_stats()
    rows, cold_s = _timed_collect(resident_query(table))
    cold = _compile_stats()
    warm_s = []
    for _ in range(2):
        again, s = _timed_collect(resident_query(table))
        warm_s.append(s)
        check(again == rows, f"resident: warm answer drifted: {again} vs "
                             f"{rows}")
    warm = _compile_stats()
    check(len(rows) == 1, f"resident: expected one row, got {rows}")
    got = rows[0]
    check(got["sk"] == ref_sk,
          f"resident: sk {got['sk']} != numpy {ref_sk}")
    check(abs(got["sv"] - ref_sv) <= 1e-6 * abs(ref_sv),
          f"resident: sv {got['sv']} vs numpy {ref_sv}")
    check(warm["traces"] == cold["traces"],
          f"resident: warm collects traced "
          f"{warm['traces'] - cold['traces']} new program(s)")

    # the repo's CPU engine on the first ref_rows rows, all three sums
    ref_rows = min(ref_rows, n_rows)
    head = {k: v[:ref_rows] for k, v in data.items()}
    del table, data, keep
    small = resident_query(
        tpu.create_dataframe(head, num_partitions=parts)).collect()[0]
    oracle = resident_query(
        cpu.create_dataframe(head, num_partitions=parts)).collect()[0]
    check(small["sk"] == oracle["sk"] and small["sh"] == oracle["sh"],
          f"resident: first {ref_rows} rows: {small} vs CPU engine {oracle}")
    check(abs(small["sv"] - oracle["sv"]) <= 1e-6 * abs(oracle["sv"]),
          f"resident: sv {small['sv']} vs CPU engine {oracle['sv']}")
    out = {"rows": n_rows, "bytes": n_rows * 20, "partitions": parts,
           "datagen_s": datagen_s, "cold_s": cold_s, "warm_s": warm_s,
           "programs_compiled": cold["compiles"] - before["compiles"],
           "compile_s": cold["compile_s"] - before["compile_s"],
           "warm_traces": warm["traces"] - cold["traces"],
           "sk": got["sk"], "sv": got["sv"], "sh": got["sh"],
           "ref_rows": ref_rows}
    emit("resident_table", **out)
    return out


# ---------------------------------------------------------------------------
# phase: TPC-DS
# ---------------------------------------------------------------------------

def host_placed(df) -> list:
    """The operators ``explain()`` places on the host, with reasons."""
    text = df.explain()
    placement = text.split("== Placement ==", 1)[1]
    placement = placement.split("\n== ", 1)[0]
    return [ln.strip() for ln in placement.splitlines()
            if ln.strip().startswith("!")]


def check_device_placement(qname: str, df) -> list:
    on_host = host_placed(df)
    bad = [ln for ln in on_host
           if any(op in ln.split(" ", 1)[0] for op in _DEVICE_ONLY)]
    check(not bad, f"{qname}: operator(s) placed on the host: {bad}")
    return on_host


def register_tpcds(sessions, sf: float, data_dir: str, seed: int = 20,
                   num_partitions: int = 1, storage: str = "parquet"):
    from spark_rapids_tpu.testing.tpcds import register_tables
    for s in sessions:
        register_tables(s, sf=sf, num_partitions=num_partitions, seed=seed,
                        storage=storage, data_dir=data_dir)


def phase_tpcds(tpu, cpu, queries=TPCDS_QUERIES,
                oracles: dict | None = None) -> dict:
    """Each query cold then warm on the device engine, compared with the
    CPU engine on the same tables; an empty answer fails.  The CPU
    engine's answers are left in ``oracles`` (by SQL text) for a later
    phase that asks the same question."""
    from spark_rapids_tpu.testing.rowcompare import rows_equal
    from spark_rapids_tpu.testing.tpcds_queries import QUERIES
    out = {}
    for q in queries:
        df = tpu.sql(QUERIES[q])
        on_host = check_device_placement(q, df)
        before = _compile_stats()
        rows, cold_s = _timed_collect(df)
        cold = _compile_stats()
        warm_rows, warm_s = _timed_collect(tpu.sql(QUERIES[q]))
        warm = _compile_stats()
        oracle, cpu_s = _timed_collect(cpu.sql(QUERIES[q]))
        if oracles is not None:
            oracles[QUERIES[q]] = oracle
        check(len(oracle) > 0, f"{q}: the reference answer is empty")
        diff = rows_equal(oracle, rows)
        check(diff is None, f"{q}: cold answer differs from the CPU "
                            f"engine: {diff}")
        diff = rows_equal(oracle, warm_rows)
        check(diff is None, f"{q}: warm answer differs from the CPU "
                            f"engine: {diff}")
        check(warm["traces"] == cold["traces"],
              f"{q}: warm collect traced "
              f"{warm['traces'] - cold['traces']} new program(s)")
        out[q] = {"cold_s": cold_s, "warm_s": warm_s, "rows": len(rows),
                  "programs_compiled": cold["compiles"] - before["compiles"],
                  "compile_s": cold["compile_s"] - before["compile_s"],
                  "warm_traces": warm["traces"] - cold["traces"],
                  "cpu_engine_s": cpu_s, "host_placed": on_host}
        emit("tpcds", query=q, **out[q])
    return out


# ---------------------------------------------------------------------------
# phase: serving
# ---------------------------------------------------------------------------

def phase_serving(tpu, cpu, moys=SERVING_Q3_MOYS,
                  oracles: dict | None = None) -> dict:
    """One QueryServer on the same session: q3 with three ``d_moy``
    literals and q7 once, submitted concurrently."""
    from spark_rapids_tpu.serving.server import QueryServer
    from spark_rapids_tpu.testing.rowcompare import rows_equal
    from spark_rapids_tpu.testing.tpcds_queries import QUERIES
    q3 = QUERIES["q3"]
    check("d_moy = 11" in q3, "q3 no longer carries d_moy = 11")
    texts = {f"q3[d_moy={m}]": q3.replace("d_moy = 11", f"d_moy = {m}")
             for m in moys}
    texts["q7"] = QUERIES["q7"]
    before = _compile_stats()
    server = QueryServer(session=tpu)
    try:
        t0 = time.perf_counter()
        subs = {tag: server.submit(sql, tag=tag)
                for tag, sql in texts.items()}
        answers = {tag: sub.result(timeout=900) for tag, sub in subs.items()}
        wall_s = time.perf_counter() - t0
        stats = server.stats()
    finally:
        server.stop()
    after = _compile_stats()
    for tag, sql in texts.items():
        oracle = (oracles or {}).get(sql) or cpu.sql(sql).collect()
        check(len(oracle) > 0, f"serving {tag}: reference answer is empty")
        diff = rows_equal(oracle, answers[tag])
        check(diff is None, f"serving {tag}: differs from the CPU engine: "
                            f"{diff}")
    new_programs = after["compiles"] - before["compiles"]
    check(new_programs == 0,
          f"serving: literal variants compiled {new_programs} new "
          f"program(s) ({after['traces'] - before['traces']} trace(s))")
    pc = stats["plan_cache"]
    out = {"queries": len(texts), "wall_s": wall_s,
           "plan_cache_hits": pc.get("hits", 0),
           "plan_cache_norm_hits": pc.get("norm_hits", 0),
           "plan_cache_misses": pc.get("misses", 0),
           "new_programs": new_programs,
           "rows": {tag: len(a) for tag, a in answers.items()}}
    emit("serving", **out)
    return out


# ---------------------------------------------------------------------------
# phase: counters (hard checks over what the phases left behind)
# ---------------------------------------------------------------------------

def hiding_counters() -> dict:
    """The process-wide counts of paths that hide the device.  They are
    never reset, so a phase reads them when it starts and checks what it
    added itself: what ran in the process before it is not its fault."""
    from spark_rapids_tpu.aux import faults
    st = _compile_stats()
    return {"async_failures": st["async_failures"],
            "ledger_errors": st["ledger_errors"],
            "recoveries": faults.recovery_stats()}


def phase_counters(native_info: dict, cache: CacheCounters,
                   cache_dir: str, baseline: dict) -> dict:
    """``baseline`` is ``hiding_counters()`` from before the smoke's first
    phase."""
    import jax
    from spark_rapids_tpu.aux import transitions
    st = _compile_stats()
    now = hiding_counters()
    rec = {k: v - baseline["recoveries"].get(k, 0)
           for k, v in now["recoveries"].items()
           if v != baseline["recoveries"].get(k, 0)}
    async_failures = now["async_failures"] - baseline["async_failures"]
    ledger_errors = now["ledger_errors"] - baseline["ledger_errors"]
    check(async_failures == 0,
          f"background compiles failed: {st['async_error']}")
    check(ledger_errors == 0,
          f"{ledger_errors} audit-ledger recording(s) raised")
    check(rec.get("collective_fallbacks", 0) == 0,
          f"collective exchange fell back to the host: {rec}")
    mem = jax.devices()[0].memory_stats() or {}
    out = {"programs": st["programs"], "compiles": st["compiles"],
           "compile_s": st["compile_s"], "traces": st["traces"],
           "async_failures": async_failures,
           "ledger_errors": ledger_errors,
           "collective_fallbacks": rec.get("collective_fallbacks", 0),
           "recoveries": rec, "transitions": transitions.totals(),
           "peak_device_bytes": mem.get("peak_bytes_in_use"),
           "native": native_info, "compile_cache_dir": cache_dir,
           "compile_cache_hits": cache.hits,
           "compile_cache_misses": cache.misses}
    emit("counters", **out)
    return out


# ---------------------------------------------------------------------------
# phase: mesh (--chips 4 only)
# ---------------------------------------------------------------------------

MESH_GROUPBY = ("select ss_store_sk, count(*) cnt, sum(ss_quantity) qty, "
                "sum(ss_ext_sales_price) sales from store_sales "
                "group by ss_store_sk")


def count_logged_events(event_log: str, kind: str) -> int:
    """Events of ``kind`` in the session's event log (in-query events reach
    the query's own sinks, of which the log file is the durable one)."""
    from spark_rapids_tpu.tools.reader import log_file_set, read_events
    if not log_file_set(event_log):
        return 0                # nothing logged yet
    events, _diag = read_events(event_log)
    return sum(1 for ev in events if ev.kind == kind)


def shard_devices(n_devices: int, rows: int = 1 << 16) -> list:
    """Shards one batch over the active mesh the way the exchange does and
    returns the device ids its ``addressable_shards`` sit on."""
    import numpy as np
    from spark_rapids_tpu.columnar.batch import batch_from_pydict
    from spark_rapids_tpu.parallel import shard_batch
    from spark_rapids_tpu.parallel.mesh import active_mesh
    ctx = active_mesh()
    check(ctx is not None and ctx.num_devices == n_devices,
          f"mesh of {n_devices} devices is not active: {ctx}")
    per = rows // n_devices
    host = [batch_from_pydict({"k": np.arange(i * per, (i + 1) * per,
                                              dtype=np.int64)})
            for i in range(n_devices)]
    cols, _counts = shard_batch(ctx, host)
    return sorted({s.device.id for s in cols[0][0].addressable_shards})


def phase_mesh(tpu, cpu, n_devices: int = 4, queries=("q3",)) -> dict:
    """The collective exchange over an ``n_devices`` mesh activated through
    ``spark.rapids.mesh.*``: a ``store_sales`` group-by and q3, compared
    with the CPU engine; the exchange must take the in-mesh path.  ``tpu``
    is a ``mesh_conf`` session (its event log counts the exchanges)."""
    from spark_rapids_tpu.aux import faults
    from spark_rapids_tpu.testing.rowcompare import rows_equal
    from spark_rapids_tpu.testing.tpcds_queries import QUERIES
    fallbacks_before = faults.recovery_stats().get("collective_fallbacks", 0)
    devices = shard_devices(n_devices)
    check(len(devices) == n_devices,
          f"sharded columns sit on devices {devices}, not on {n_devices} "
          f"distinct ones")
    texts = {"store_sales_groupby": MESH_GROUPBY}
    texts.update({q: QUERIES[q] for q in queries})
    event_log = tpu.conf.get("spark.rapids.sql.eventLog.path")
    check(event_log, "the mesh session has no event log to count from")
    out = {"shard_devices": devices, "queries": {}}
    seen = count_logged_events(event_log, "iciExchange")
    for tag, sql in texts.items():
        rows, cold_s = _timed_collect(tpu.sql(sql))
        warm_rows, warm_s = _timed_collect(tpu.sql(sql))
        oracle = cpu.sql(sql).collect()
        check(len(oracle) > 0, f"mesh {tag}: reference answer is empty")
        for got in (rows, warm_rows):
            diff = rows_equal(oracle, got)
            check(diff is None,
                  f"mesh {tag}: differs from the CPU engine: {diff}")
        now = count_logged_events(event_log, "iciExchange")
        out["queries"][tag] = {"cold_s": cold_s, "warm_s": warm_s,
                               "rows": len(rows),
                               "ici_exchanges": now - seen}
        seen = now
    rec = faults.recovery_stats()
    out["ici_exchanges"] = seen
    out["collective_fallbacks"] = \
        rec.get("collective_fallbacks", 0) - fallbacks_before
    check(out["collective_fallbacks"] == 0,
          f"collective exchange fell back to the host: {rec}")
    check(out["queries"]["store_sales_groupby"]["ici_exchanges"] > 0,
          "the group-by's exchange did not take the in-mesh path")
    check(out["ici_exchanges"] > 0, "no exchange took the in-mesh path")
    emit("mesh", **out)
    return out


def mesh_conf(n_devices: int, event_log: str) -> dict:
    return {"spark.rapids.mesh.enabled": "true",
            "spark.rapids.mesh.shape": str(n_devices),
            "spark.rapids.sql.eventLog.path": event_log}


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs ONLY the mesh phase and its reference")
    ap.add_argument("--seed", type=int, default=7,
                    help="seed of the generated tables")
    args = ap.parse_args(argv)

    # outside its checkout this script has nothing to drive: fail here,
    # before any work and before anything is printed
    import spark_rapids_tpu  # noqa: F401

    device = phase_device("tpu", args.chips)
    cache = CacheCounters().listen()
    cache_dir = place_compile_cache()
    native_info = build_native()
    data_dir = tempfile.mkdtemp(prefix="chip_smoke_tpcds_")
    baseline = hiding_counters()
    try:
        if args.chips == 4:
            # in-memory tables of four partitions: every hash exchange is
            # as wide as the mesh, which makes it eligible for the in-mesh
            # path (the parquet reader coalesces its files into one)
            tpu, cpu = make_sessions(mesh_conf(
                4, os.path.join(data_dir, "mesh_events.jsonl")))
            register_tpcds((tpu, cpu), TPCDS_SF, data_dir, seed=args.seed,
                           num_partitions=4, storage="memory")
            phase_mesh(tpu, cpu, 4)
        else:
            tpu, cpu = make_sessions()
            phase_resident(tpu, cpu, seed=args.seed)
            t0 = time.perf_counter()
            register_tpcds((tpu, cpu), TPCDS_SF, data_dir, seed=args.seed)
            emit("tpcds_datagen", sf=TPCDS_SF, seconds=time.perf_counter() - t0)
            oracles: dict = {}
            phase_tpcds(tpu, cpu, oracles=oracles)
            phase_serving(tpu, cpu, oracles=oracles)
        phase_counters(native_info, cache, cache_dir, baseline)
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
