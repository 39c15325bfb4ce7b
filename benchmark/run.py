"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process every time: finds the chip (no chip, no result), places the
compile cache inside the checkout, makes the cell's tables from ``--seed``,
warms the cell's own texts, measures for ``--seconds``, then compares what
the timed path returned with the plain reference and prints one JSON line.

Nothing here names a cell, a query text, a configuration or a metric: the
cell comes from ``BENCHMARK.json`` and ``workloads/<cell>.json``, its
configuration from ``configs/<config>.json``, its data from the generator
module that file names (``datagen/<module>.py``), its texts from
``queries/``, its reference answers from ``reference/<q>.py``, its driver
from ``drivers/<driver>.py``, each end-to-end metric from
``end_to_end/<metric>.py``, each per-layer metric from
``layer_metrics/<metric>.py`` and each limit of ``correct`` from
``limits.json``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: operators that must never be placed on the host (chip_smoke._DEVICE_ONLY)
DEVICE_ONLY = ("Join", "Aggregate", "Sort", "Window", "Exchange",
               "TakeOrdered")
#: process-wide counts of paths that hide the device; a rise in the window
#: counts as failed queries
HIDING_COUNTERS = ("async_failures", "ledger_errors", "collective_fallbacks")


#: where the reader of a metric of each group lives: ``<dir>/<name>.py``
READERS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_json(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


def load_by_name(directory: str, name: str):
    """The module ``<directory>/<name>.py`` (names may hold dots)."""
    path = os.path.join(HERE, directory, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{directory}.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# ---------------------------------------------------------------------------
# start-up: device, compile cache, native library
# ---------------------------------------------------------------------------

def find_device(chips: int, rehearse: bool) -> dict:
    import jax
    devices = jax.devices()
    d = devices[0]
    if not rehearse and (d.platform != "tpu" or len(devices) < chips):
        raise NoChip(f"the cell needs {chips} tpu chip(s); JAX found "
                     f"{len(devices)} x {d.platform} ({d.device_kind})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def place_compile_cache() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` where the environment places it (JAX
    reads it itself), else ``<checkout>/.jax_cache``: a fixed path, because
    the path is part of the cache's key."""
    import jax
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        placed = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", placed)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return placed


def build_native() -> None:
    """``*.so`` is not committed: build libtpucol from the checkout's
    source where it is missing or older than its source."""
    subprocess.run(["make", "-C", os.path.join(ROOT, "native")], check=True,
                   capture_output=True, timeout=300)
    from spark_rapids_tpu import native
    if not native.have_native():
        raise RuntimeError("libtpucol built but did not load")


def host_placed(explained: str) -> list:
    """The operators ``explain()`` places on the host."""
    placement = explained.split("== Placement ==", 1)[1].split("\n== ", 1)[0]
    on_host = [ln.strip() for ln in placement.splitlines()
               if ln.strip().startswith("!")]
    return [ln for ln in on_host
            if any(op in ln.split(" ", 1)[0] for op in DEVICE_ONLY)]


def program_counters(driver) -> dict:
    from spark_rapids_tpu.aux import faults, transitions
    from spark_rapids_tpu.exec import stage_compiler
    st = stage_compiler.stats()
    hiding = {k: st.get(k, 0) for k in HIDING_COUNTERS[:2]}
    hiding["collective_fallbacks"] = faults.recovery_stats().get(
        "collective_fallbacks", 0)
    return {"stage_compiler": {k: st[k] for k in ("programs", "compiles",
                                                  "compile_s", "traces")},
            "traces_by_kind": dict(st.get("traces_by_kind") or {}),
            "transitions": transitions.totals(), "hiding": hiding,
            "server": driver.counters()}


# ---------------------------------------------------------------------------
# the cell: data, texts, schedule
# ---------------------------------------------------------------------------

class Cell:
    """What one run needs of a cell, read from the data files."""

    def __init__(self, name: str, scale_down: int = 1):
        from benchmark.literals import Query
        bench = load_json(ROOT, "BENCHMARK.json")
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.bench, self.entry, self.name = bench, entry, name
        config = next(c for c in bench["configs"]
                      if c["name"] == entry["config"])
        self.config = load_json(ROOT, config["file"])
        self.traffic = load_json(HERE, "workloads", entry["traffic"] + ".json")
        self.queries = {q: Query(q) for q in self.traffic["texts"]}
        self.datagen = importlib.import_module(
            "benchmark.datagen." + self.config["datagen"])
        fixed = self.config["fixed_tables"]   # what --scale-down leaves whole
        self.rows = {t: n if t in fixed else max(n // scale_down, 8)
                     for t, n in self.config["rows"].items()}

    def metrics(self, group: str) -> list:
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]


def make_tables(cell: Cell, seed: int):
    """Generator plus the arrow tables of the columns the texts name."""
    from benchmark.work import query_columns
    gen = cell.datagen.make(cell.rows, seed, **cell.config["datagen_args"])
    wanted: dict = {}
    for q in cell.queries.values():
        for table, cols in query_columns(q, cell.datagen).items():
            wanted.setdefault(table, set()).update(cols)
    return gen, cell.datagen.arrow_tables(gen, wanted,
                                          cell.config["integer_type"])


class Stream:
    """One client's endless sequence of (text name, literals), from the
    seed, in rounds that each hold every text of the cell once: a rotation
    (the texts in the file's order, begun at the stream's own offset), or a
    fresh permutation every round.  Either way every seed sends the same
    texts equally often, in another order and with other literals."""

    def __init__(self, cell: Cell, seed: int, index: int, pool):
        import numpy as np
        self.cell, self.pool = cell, pool
        self.rng = np.random.default_rng([seed, 104729, index])
        self.order = cell.traffic["order"]
        names = list(cell.traffic["texts"])
        shift = index % len(names)     # rotations of several streams differ
        self.names = names[shift:] + names[:shift]
        self.round: list = []

    def next(self):
        if not self.round:
            self.round = list(self.names) if self.order == "rotation" else \
                [self.names[i] for i in self.rng.permutation(len(self.names))]
        name = self.round.pop(0)
        values = self.pool.draw(name)
        return name, values, self.cell.queries[name].fill(values)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

class Window:
    """Drives the cell's streams for ``seconds`` and keeps every query's
    record.  A stream submits no query after the deadline; the one it has
    in flight then is waited for and counted, with all of its time.  The
    window closes when the last stream has its answer; rates are taken over
    all of that time."""

    def __init__(self, driver, cell: Cell, seed: int, seconds: float,
                 bad_texts: set, pool):
        self.driver, self.cell, self.seed = driver, cell, seed
        self.pool = pool
        self.seconds = seconds
        self.bad_texts = bad_texts
        self.records: list = []
        self.lock = threading.Lock()
        self.t0 = 0.0
        self.trace_until = None       # set while a trace is being taken
        self.trace_span = None

    def _client(self, index: int) -> None:
        import jax
        stream = Stream(self.cell, self.seed, index, self.pool)
        deadline = self.t0 + self.seconds
        while time.perf_counter() < deadline:
            with jax.profiler.TraceAnnotation("bench.draw_literals"):
                name, values, text = stream.next()
            rec = {"q": name, "params": values, "stream": index,
                   "start_s": time.perf_counter() - self.t0}
            try:
                rec.update(self.driver.run_one(text))
                if name in self.bad_texts:
                    rec["error"] = "an operator of this text is on the host"
            except Exception:  # noqa: BLE001 — a failed query is counted,
                # with its traceback, and the stream goes on
                rec["error"] = traceback.format_exc(limit=8)[-2000:]
            rec["end_s"] = time.perf_counter() - self.t0
            with self.lock:
                self.records.append(rec)
            if index == 0 and self.trace_until is not None \
                    and self.driver.streams == 1 \
                    and rec["end_s"] >= self.trace_until:
                self.stop_trace()

    def start_trace(self, directory: str, seconds: float) -> None:
        import jax
        shutil.rmtree(directory, ignore_errors=True)
        jax.profiler.start_trace(directory)
        self.trace_dir = directory
        self.trace_until = seconds
        self.trace_span = [time.perf_counter(), None]

    def stop_trace(self) -> None:
        import jax
        if self.trace_until is None:
            return
        self.trace_until = None
        self.trace_span[1] = time.perf_counter()
        jax.profiler.stop_trace()

    def run(self, trace_dir=None, trace_seconds: float = 0.0) -> None:
        clients = [threading.Thread(target=self._client, args=(i,),
                                    name=f"bench-stream-{i}")
                   for i in range(self.driver.streams)]
        self.t0 = time.perf_counter()
        if trace_dir:
            self.start_trace(trace_dir, trace_seconds)
        for c in clients:
            c.start()
        if trace_dir and self.driver.streams > 1:
            time.sleep(max(0.0, self.t0 + trace_seconds
                           - time.perf_counter()))
            self.stop_trace()
        for c in clients:
            c.join()
        self.stop_trace()
        self.closed_s = time.perf_counter() - self.t0
        if self.trace_span:
            lo, hi = (t - self.t0 for t in self.trace_span)
            for r in self.records:
                r["traced"] = r["start_s"] >= lo and r["end_s"] <= hi


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def check_answers(cell: Cell, gen, records: list) -> dict:
    """Compares the rows every answered query of the window returned with
    the reference's answer to the same text."""
    from benchmark.compare import compare
    answered = [r for r in records if "rows" in r]
    references = {q: load_by_name("reference", q) for q in cell.queries}
    memo: dict = {}
    out = {"rows_wrong": 0, "max_rel_err": 0.0, "checked": 0,
           "empty_answers": 0,
           "unanswered": sum("rows" not in r for r in records)}
    for r in answered:
        key = (r["q"], json.dumps(r["params"], sort_keys=True))
        if key not in memo:
            memo[key] = references[r["q"]].run(gen, r["params"])
        got = compare(r["rows"], memo[key])
        out["rows_wrong"] += got["rows_wrong"]
        out["max_rel_err"] = max(out["max_rel_err"], got["max_rel_err"])
        out["checked"] += 1
        out["empty_answers"] += got["groups"] == 0
        if got["rows_wrong"]:
            log(f"WRONG {r['q']} {r['params']}: {got}; first rows "
                f"{r['rows'][:2]}")
    return out


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="sandbox only: run on whatever device JAX finds and "
                         "print the numbers under rehearsal_metrics, never "
                         "under metrics")
    ap.add_argument("--scale-down", type=int, default=1,
                    help="with --rehearse: divide the row counts")
    args = ap.parse_args(argv)
    if args.scale_down != 1 and not args.rehearse:
        ap.error("--scale-down is for --rehearse only")
    return args


class Prepared:
    """A cell set up for one seed: data made, tables registered, texts
    warm.  ``close()`` stops what it started."""

    def __init__(self, cell: Cell, seed: int, cache_dir: str):
        from spark_rapids_tpu.config import TpuConf
        from spark_rapids_tpu.session import TpuSession
        from benchmark.literals import LiteralPool
        self.cell, self.seed = cell, seed
        self.pool = LiteralPool(cell.queries, seed)
        t = time.perf_counter()
        self.gen, tables = make_tables(cell, seed)
        self.datagen_s = time.perf_counter() - t
        conf = dict(cell.config["session_conf"])
        conf.setdefault("spark.rapids.sql.compile.cacheDir", cache_dir)
        self.session = TpuSession(TpuConf(conf))
        for name, table in tables.items():
            self.session.create_or_replace_temp_view(
                name, self.session.create_dataframe(
                    table, num_partitions=int(cell.config["partitions"])))
        del tables
        self.driver = load_by_name("drivers", cell.traffic["driver"]).Driver(
            self.session, cell.traffic)
        self.bad_texts: set = set()
        t = time.perf_counter()
        self._warm()
        self.warm_s = time.perf_counter() - t

    def _warm(self) -> None:
        """Every text of the cell once, with literals of its own: the
        tables go up to the device and each program is compiled or loaded
        from the compile cache.  What a new literal compiles after that, it
        compiles in the window, where ``window_compiles`` counts it."""
        for name, query in self.cell.queries.items():
            text = query.fill(self.pool.draw(name))
            on_host = host_placed(self.driver.explain(text))
            if on_host:
                self.bad_texts.add(name)
                log(f"benchmark: {name}: on the host: {on_host}")
            t = time.perf_counter()
            self.driver.warm(text)
            log(f"warm {name} {time.perf_counter() - t:.3f}s")

    def close(self) -> None:
        self.driver.close()
        self.session.stop()


def measure(prepared: Prepared, seconds: float, trace: bool):
    """The window, with the program's counters read on both sides of it."""
    cell = prepared.cell
    window = Window(prepared.driver, cell, prepared.seed, seconds,
                    prepared.bad_texts, prepared.pool)
    before = program_counters(prepared.driver)
    trace_dir = os.path.join(HERE, ".cache", "trace") if trace else None
    window.run(trace_dir, min(float(cell.traffic.get("trace_seconds", 10)),
                              seconds))
    after = program_counters(prepared.driver)
    return window, before, after


def judge(cell: Cell, gen, records: list):
    """Each number compared beside its limit, and whether all hold."""
    got = check_answers(cell, gen, records)
    checks = {name: {"value": got[name], "limit": limit} for name, limit
              in load_json(HERE, "limits.json")["limits"].items()}
    correct = got["checked"] > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    return checks, correct, got["checked"]


def main(argv=None) -> int:
    args = parse_args(argv)
    # outside a checkout of the program there is nothing to measure: fail
    # before any work and before anything is printed
    import spark_rapids_tpu  # noqa: F401
    cell = Cell(args.workload, args.scale_down)
    try:
        device = find_device(int(cell.entry["chips"]), args.rehearse)
    except NoChip as e:
        log(f"benchmark: {e}")
        return 3
    import jax
    cache_dir = place_compile_cache()
    build_native()
    peaks = load_json(HERE, "peaks.json").get(device["kind"])
    if peaks is None and not args.rehearse:
        raise SystemExit(f"no peaks for device kind {device['kind']!r} in "
                         f"benchmark/peaks.json")

    prepared = Prepared(cell, args.seed, cache_dir)
    setup_s = time.perf_counter() - T_PROCESS
    window, before, after = measure(prepared, args.seconds, bool(args.trace))
    device["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()[:int(cell.entry["chips"])])
    prepared.close()

    records = sorted(window.records, key=lambda r: r["end_s"])
    completed = [r for r in records if "error" not in r]
    hidden = sum(after["hiding"][k] - before["hiding"][k]
                 for k in HIDING_COUNTERS)
    failed = min(len(records), len(records) - len(completed) + hidden)
    for r in records:
        if "error" in r:
            log(f"FAILED {r['q']} {r['params']}: {r['error']}")

    # ---- what the window measured ---------------------------------------
    trace = None
    if args.trace:
        from benchmark.trace.reduce import reduce_directory
        t = time.perf_counter()
        trace = reduce_directory(window.trace_dir)
        log(f"trace reduced in {time.perf_counter() - t:.1f}s: "
            f"busy {trace['busy_s']:.3f}s of {trace['window_s']:.3f}s")
        shutil.rmtree(window.trace_dir, ignore_errors=True)
        device["busy_s"], device["window_s"] = (trace["busy_s"],
                                                trace["window_s"])
    # what the metrics' readers see of a run
    run = types.SimpleNamespace(
        completed=completed, records=records, trace=trace,
        counters={"before": before, "after": after}, device=device,
        peaks=peaks, cell=cell, gen=prepared.gen, datagen=cell.datagen,
        seconds=window.closed_s,
        setup_s=setup_s)
    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in cell.metrics(group):
        v = load_by_name(READERS[group], m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # ---- correct? --------------------------------------------------------
    t = time.perf_counter()
    checks, correct, checked = judge(cell, prepared.gen, records)
    check_s = time.perf_counter() - t

    texts_seen = [(r["q"], json.dumps(r["params"], sort_keys=True))
                  for r in records]
    log(json.dumps({
        "cell": cell.name, "seed": args.seed, "queries": len(records),
        "window_s": window.closed_s,
        "last_submitted_s": max((r["start_s"] for r in records), default=0.0),
        "exact_repeats_share":
            (len(texts_seen) - len(set(texts_seen))) / max(1, len(records)),
        "server": {k: {c: after["server"][k][c] - before["server"][k].get(c, 0)
                       for c in after["server"][k]}
                   for k in after["server"]},
        "window_compiles": {k: after["stage_compiler"][k]
                            - before["stage_compiler"][k]
                            for k in ("compiles", "traces", "compile_s")},
        "window_traces_by_kind": {
            k: n - before["traces_by_kind"].get(k, 0)
            for k, n in after["traces_by_kind"].items()
            if n != before["traces_by_kind"].get(k, 0)},
        "datagen_s": prepared.datagen_s, "warm_s": prepared.warm_s,
        "check_s": check_s, "checked": checked, "compile_cache": cache_dir,
        "latency_s_in_order": [[r["q"], round(r["latency_s"], 4)]
                               for r in completed[:64]],
        "mean_ms_by_text": {
            q: round(1e3 * sum(r["latency_s"] for r in completed
                               if r["q"] == q)
                     / max(1, sum(r["q"] == q for r in completed)), 3)
            for q in cell.queries}}))
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")

    result = {"correct": correct, "attempted": len(records), "failed": failed,
              "metrics": metrics, "device": device}
    if args.rehearse:
        result["rehearsal_metrics"] = result["metrics"]
        result["metrics"] = {}
        result["device"] = dict(device, rehearsal=True)
    if trace is not None:
        result["breakdown"] = {"device_ops": trace["device_ops"][:10],
                               "idle_gaps": trace["idle_gaps"][:10]}
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
