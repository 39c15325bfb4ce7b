"""What a run of a benchmark cell holds of the program's own spans, and
how the gateway's device-gap ledger compares with the device.

    chiprun -- python scripts/span_probe.py --workload store_scan_agg --seed 7
    chiprun -- python scripts/span_probe.py --workload store_scan_agg --seed 7 --trace 0

Runs ``benchmark/run.py`` in this process and writes
``chiprun_out/span_probe_<cell>_<seed>_t<trace>.json``.

With ``--trace 1`` (the default) it keeps the xplane that the harness would
delete and writes:

- the per-query summaries of the window (``phases``, counters,
  ``device_gaps``, without the nodes) beside the client's latencies;
- the ``srt.*`` host events inside each ``bench.collect`` span, counted by
  name, with the query ids they carry;
- per host thread, the self time inside ``bench.collect`` by event name;
- the device's time by program kind and by the scope each operation's
  ``op_name`` begins with (``jax.named_scope``), and the thirty
  ``op_name``s that took most of it;
- **the ledger against the device** (``ledger_check``): every idle gap of
  the first device (as ``benchmark/trace/reduce.py`` defines it) booked to
  the ``srt.*`` span open on the host; the summaries' gap ``intervals`` laid
  on the trace by their clock anchor; ``recall`` (the share of the idle
  seconds that booked gaps cover), ``precision`` (the share of booked
  seconds in which the device ran no operation), the idle the ledger left
  unbooked by ``srt.*`` span, the programs that ran inside booked gaps, and
  the ``clock_error`` (the median |difference| between a tree span's start,
  moved by the anchor, and its ``srt.*`` annotation's); the events these
  are computed from go to ``ledger_check_events`` so they can be read again.

With ``--trace 0`` no profiler runs: it prints the window's means of the
summaries' ``device_gaps`` (``gap_means``: all queries and by text) and of
the planning phases, with ``qps`` as the harness reckons it.

Not part of the benchmark: it measures nothing the driver compares."""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: ``jit(run[join.pair])/jit(main)/expand/gather:`` -> ``join.pair``,
#: ``expand``
OP_NAME = re.compile(r"^jit\(run\[([^\]]+)\]\)/(?:jit\([^)]*\)/)*([^/:]+)")
#: the phases of a text's planning, whose traced and untraced means differ
#: by what the profiler costs the host
PLAN_PHASES = ("plan.parse", "plan.analyze", "plan.rewrite")


def self_times(events, within=None):
    """``{name: self ns}`` of one thread's events ``(name, start, dur)``:
    every instant goes to the event that opened last among those open
    then.  (Not a stack: a ``with`` of a generator-based context manager
    shows as two short events, and the annotation it enters outlives the
    first.)  ``within`` (sorted, disjoint ``(start, end)``) counts only
    those instants, and an instant with no event open goes to
    ``(no srt span)``."""
    import heapq

    from spark_rapids_tpu.aux.tracing import _coverage
    measure = (lambda a, b: b - a) if within is None else _coverage(within)
    edges = []
    for i, (_name, s, d) in enumerate(events):
        edges.append((s, 1, i))
        edges.append((s + d, 0, i))
    edges.sort()
    out, open_heap, closed = {}, [], set()
    at = min([e[0] for e in edges[:1]] + [w[0] for w in (within or [])[:1]],
             default=0.0)
    for t, opens, i in edges:
        while open_heap and open_heap[0][1] in closed:
            heapq.heappop(open_heap)
        name = events[open_heap[0][1]][0] if open_heap else \
            (None if within is None else "(no srt span)")
        if name is not None:
            out[name] = out.get(name, 0.0) + measure(at, t)
        at = t
        if opens:
            heapq.heappush(open_heap, (-events[i][1], i))
        else:
            closed.add(i)
    if within:
        out["(no srt span)"] = out.get("(no srt span)", 0.0) + \
            measure(at, max(at, within[-1][1]))
    return {k: v for k, v in out.items() if v > 0}


def device_ops_by_scope(path: str):
    """Device time by ``(program kind, head of the op_name)``, and of the
    thirty longest ``op_name``s.  The
    op_name (``tf_op``) is a stat of the event's metadata, which
    ``ProfileData`` does not hand out: read from the xplane protobuf with
    the message classes that the installed tensorflow ships (loaded alone,
    tensorflow itself is not imported)."""
    import importlib.util
    found = importlib.util.find_spec("tensorflow")
    if found is None:
        return "no xplane_pb2 here (tensorflow is not installed)", []
    pb2 = os.path.join(list(found.submodule_search_locations)[0], "tsl",
                       "profiler", "protobuf", "xplane_pb2.py")
    spec = importlib.util.spec_from_file_location("xplane_pb2", pb2)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    space = module.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out: dict = {}
    by_op: dict = {}

    def scope_of(op_name):
        m = OP_NAME.match(op_name)
        return m.groups() if m else ("(other)", "")

    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        op_names = {}
        for mid, meta in plane.event_metadata.items():
            for st in meta.stats:
                if stat_names.get(st.metadata_id) == "tf_op":
                    op_names[mid] = st.str_value or \
                        stat_names.get(st.ref_value, "")
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            events = []
            for e in line.events:
                op_name = op_names.get(e.metadata_id, "")
                got = out.setdefault(scope_of(op_name),
                                     {"ops": 0, "seconds": 0.0,
                                      "example": op_name[:120]})
                got["ops"] += 1
                events.append((op_name, e.offset_ps, e.duration_ps))
            # self time: a loop's body is counted, the loop only for the rest
            for op_name, ps in self_times(events).items():
                out[scope_of(op_name)]["seconds"] += ps / 1e12
                by_op[op_name] = by_op.get(op_name, 0.0) + ps / 1e12
    scopes = [{"kind": k[0], "scope": k[1], **v} for k, v in
              sorted(out.items(), key=lambda kv: -kv[1]["seconds"])]
    ops = [{"op_name": k, "seconds": v} for k, v in
           sorted(by_op.items(), key=lambda kv: -kv[1])[:30]]
    return scopes, ops


def _overlap(a, b) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    from spark_rapids_tpu.aux.tracing import _coverage
    if not a or not b:
        return 0.0
    measure = _coverage(a)
    return sum(measure(s, e) for s, e in b)


def ledger_check(data, summaries, tree_starts) -> dict:
    """The gap ledger against the device (module docstring)."""
    import bisect

    from benchmark.trace.reduce import (DEVICE_PREFIX, MODULES_LINE,
                                        OPS_LINE, SPAN_PREFIX, short_program,
                                        union)
    from spark_rapids_tpu.aux.tracing import _coverage
    start_time = None
    bench, srt, ops, modules = [], [], None, []
    for plane in data.planes:
        start_time = dict(plane.stats).get("profile_start_time", start_time)
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            if ops is None and OPS_LINE in lines:
                ops = [(float(e.start_ns), float(e.start_ns + e.duration_ns))
                       for e in lines[OPS_LINE].events]
                if MODULES_LINE in lines:
                    modules = [(short_program(e.name), float(e.start_ns),
                                float(e.start_ns + e.duration_ns))
                               for e in lines[MODULES_LINE].events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        bench.append((float(e.start_ns),
                                      float(e.start_ns + e.duration_ns)))
                    elif e.name.startswith("srt."):
                        st = dict(e.stats)
                        srt.append((e.name, float(e.start_ns),
                                    float(e.duration_ns),
                                    st.get("query_id"), st.get("span_id")))
    if start_time is None or not ops or not bench:
        return {"error": "no profile_start_time, device operations or "
                         "bench.* spans in the xplane"}
    lo, hi = min(s for s, _ in bench), max(e for _, e in bench)
    busy = union((max(s, lo), min(e, hi)) for s, e in ops if e > lo and s < hi)
    idle, edge = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > edge:
            idle.append((edge, s))
        edge = max(edge, e)

    anchors = {json.dumps(s["clock"], sort_keys=True) for s in summaries
               if "clock" in s}
    if len(anchors) != 1:
        return {"error": f"{len(anchors)} clock anchors in the summaries"}
    clock = json.loads(anchors.pop())

    def on_trace(t: float) -> float:
        return clock["wall_ns"] + 1e9 * (t - clock["monotonic_s"]) \
            - start_time

    booked = {"fetch": [], "query": []}
    resume_lag, dropped = [], 0
    busy_starts = [s for s, _ in busy]
    for s in summaries:
        g = s.get("device_gaps") or {}
        dropped += g.get("intervals_dropped", 0)
        for start, end, kind, _site in g.get("intervals", []):
            a, b = max(on_trace(start), lo), min(on_trace(end), hi)
            if b > a:
                booked[kind].append((a, b))
                i = bisect.bisect_left(busy_starts, b)
                if i < len(busy_starts):
                    resume_lag.append((busy_starts[i] - b) / 1e6)
    every = sorted(booked["fetch"] + booked["query"])
    idle_s = sum(e - s for s, e in idle) / 1e9
    booked_s = sum(e - s for s, e in every) / 1e9
    covered_s = _overlap(idle, every) / 1e9

    srt_events = [(n, s, d) for n, s, d, _q, _sp in srt
                  if s + d > lo and s < hi]
    idle_by_span = self_times(srt_events, within=idle)
    unbooked, j = [], 0     # idle less booked: one pass over both
    for s, e in idle:
        at = s
        while j < len(every) and every[j][1] <= at:
            j += 1
        k = j
        while k < len(every) and every[k][0] < e:
            if every[k][0] > at:
                unbooked.append((at, every[k][0]))
            at = max(at, every[k][1])
            k += 1
        if at < e:
            unbooked.append((at, e))
    unbooked_by_span = self_times(srt_events, within=unbooked)
    inside: dict = {}
    if every:
        measure = _coverage(every)
        for name, s, e in modules:
            got = measure(s, e)
            if got > 0:
                inside[name] = inside.get(name, 0.0) + got / 1e9

    deltas = []
    for name, s, _d, q, sp in srt:
        t = tree_starts.get((q, sp))
        if t is not None and name != "srt.serve.queue":
            deltas.append((s - on_trace(t)) / 1e6)
    top = lambda d, n=12: [[k, v / 1e9] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:n]]
    return {
        "window_s": (hi - lo) / 1e9, "idle_s": idle_s,
        "idle_pct": 100.0 * idle_s / ((hi - lo) / 1e9),
        "booked_s": booked_s,
        "booked_s_by_kind": {k: sum(e - s for s, e in v) / 1e9
                             for k, v in booked.items()},
        "recall": covered_s / idle_s if idle_s else None,
        "precision": _overlap(idle, every) / 1e9 / booked_s
        if booked_s else None,
        "precision_by_kind": {k: _overlap(idle, sorted(v)) / 1e9
                              / (sum(e - s for s, e in v) / 1e9)
                              for k, v in booked.items() if v},
        "intervals_dropped": dropped,
        "idle_by_srt_span": top(idle_by_span),
        "unbooked_idle_by_srt_span": top(unbooked_by_span),
        "programs_inside_booked_gaps_s": sorted(
            inside.items(), key=lambda kv: -kv[1])[:12],
        "resume_lag_ms_median": statistics.median(resume_lag)
        if resume_lag else None,
        "clock_error_ms_median": statistics.median(abs(x) for x in deltas)
        if deltas else None,
        "clock_error_ms_signed_median": statistics.median(deltas)
        if deltas else None,
        "clock_matches": len(deltas),
        "events": {"lo_ns": lo, "hi_ns": hi,
                   "profile_start_time": start_time, "clock": clock,
                   "busy": busy, "booked": booked,
                   "srt": [e for e in srt if e[1] + e[2] > lo and e[1] < hi],
                   "modules": [m for m in modules if m[2] > lo and m[1] < hi]},
    }


def inspect(path: str, summaries, tree_starts) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    collects, host_lines = [], []
    for plane in data.planes:
        for line in plane.lines:
            events = [(e.name, float(e.start_ns), float(e.duration_ns),
                       dict(e.stats)) for e in line.events]
            if plane.name.startswith("/host:"):
                host_lines.append((plane.name, line.name, events))
                collects += [(s, s + d) for n, s, d, _ in events
                             if n == "bench.collect"]
    inside = lambda s: any(lo <= s < hi for lo, hi in collects)
    srt: dict = {}
    threads = []
    top = lambda d, n: [[k, v / 1e9] for k, v in
                        sorted(d.items(), key=lambda kv: -kv[1])[:n]]
    for plane, line, events in host_lines:
        mine = [(n.split("#", 1)[0][:80], s, d) for n, s, d, _ in events
                if inside(s)]
        if mine:
            total: dict = {}
            for n, _s, d in mine:
                total[n] = total.get(n, 0.0) + d
            threads.append({"thread": f"{plane}:{line}",
                            "events": len(mine),
                            "self_seconds": top(self_times(mine), 30),
                            "inclusive_seconds": top(total, 30)})
        for n, s, d, st in events:
            if n.startswith("srt.") and inside(s):
                got = srt.setdefault(n, {"count": 0, "seconds": 0.0,
                                         "query_ids": set()})
                got["count"] += 1
                got["seconds"] += d / 1e9
                got["query_ids"].add(st.get("query_id"))
    for got in srt.values():
        got["query_ids"] = sorted(got["query_ids"], key=str)
    threads.sort(key=lambda t: -t["events"])
    scopes, ops = device_ops_by_scope(path)
    check = ledger_check(data, summaries, tree_starts)
    events = check.pop("events", None)
    return {"collect_spans": len(collects),
            "srt_events_inside_bench_collect": srt,
            "host_threads_inside_bench_collect": threads[:8],
            "device_ops_by_scope": scopes,
            "device_ops_by_op_name": ops,
            "ledger_check": check,
            "ledger_check_events": events}


def gap_means(summaries, records) -> dict:
    """Means a query of the summaries' ``device_gaps`` and planning phases,
    over the window and by text."""
    def means(pairs):
        n = len(pairs)
        if not n:
            return {}
        out = {"queries": n}
        for key in ("fetch_s", "query_s", "count", "late", "unplaced",
                    "eager_device_calls"):
            out[key] = sum(g.get(key, 0) for g, _s in pairs) / n
        out["fetch_gap_ms"] = 1e3 * out.pop("fetch_s")
        out["query_gap_ms"] = 1e3 * out.pop("query_s")
        for part in ("by_span", "by_site"):
            tot: dict = {}
            for g, _s in pairs:
                for k, v in g.get(part, {}).items():
                    tot[k] = tot.get(k, 0.0) + v
            out[part + "_ms"] = {k: 1e3 * v / n for k, v in sorted(
                tot.items(), key=lambda kv: -kv[1])}
        out["plan_phases_ms"] = {p: 1e3 * sum(
            s["phases"].get(p, 0.0) for _g, s in pairs) / n
            for p in PLAN_PHASES}
        out["duration_ms"] = 1e3 * sum(s["duration_s"] for _g, s in pairs) / n
        return out

    pairs = [(s.get("device_gaps"), s, r) for s, r in zip(summaries, records)
             if s.get("device_gaps") is not None]
    by_text: dict = {}
    for g, s, r in pairs:
        by_text.setdefault(r.get("q"), []).append((g, s))
    plan = [r["plan_s"] for r in records if r.get("plan_s") is not None]
    return {"all": means([(g, s) for g, s, _r in pairs]),
            "by_text": {q: means(v) for q, v in by_text.items()},
            "plan_ms": 1e3 * sum(plan) / len(plan) if plan else None}


class _PhaseStarts:
    """An event sink for the console tap: each finished query's phase
    spans ``(query_id, span_id) -> start``, for the clock error."""

    def __init__(self):
        self.starts = {}

    def emit(self, ev):
        p = ev.payload
        if ev.kind == "spanMetrics" and p.get("kind") == "phase":
            self.starts[(ev.query_id, p["span_id"])] = p["start_s"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="store_scan_agg")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=40)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--session-conf", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="added to the cell's session conf (a builder's "
                         "comparison, e.g. the span tree off)")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--scale-down", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    out_path = args.out or os.path.join(
        ROOT, "chiprun_out",
        f"span_probe_{args.workload}_{args.seed}_t{args.trace}.json")
    from benchmark import run as R
    from spark_rapids_tpu.aux import events as EV
    kept, seen = {}, {}
    real_rmtree, real_measure = R.shutil.rmtree, R.measure
    phase_starts = _PhaseStarts()

    if args.session_conf:
        extra = dict(kv.split("=", 1) for kv in args.session_conf)
        real_init = R.Cell.__init__

        def with_conf(self, *a, **kw):
            real_init(self, *a, **kw)
            self.config["session_conf"] = {
                **self.config.get("session_conf", {}), **extra}

        R.Cell.__init__ = with_conf

    def measure(prepared, seconds, trace):
        got = real_measure(prepared, seconds, trace)
        seen["window"], seen["before"], seen["after"] = got
        return got

    def keep_the_trace(path, *a, **kw):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if found and "window" in seen:
            from spark_rapids_tpu.aux import tracing
            n = len(seen["window"].records)
            kept["xplane"] = inspect(sorted(found, key=os.path.getmtime)[-1],
                                     tracing.recent_summaries()[-n:],
                                     phase_starts.starts)
        return real_rmtree(path, *a, **kw)

    R.measure = measure
    R.shutil.rmtree = keep_the_trace
    if args.trace:
        EV.set_console_tap(phase_starts)
    cmd = ["--workload", args.workload, "--seed", str(args.seed),
           "--seconds", str(args.seconds), "--trace", str(args.trace)]
    if args.rehearse:
        cmd += ["--rehearse", "--scale-down", str(args.scale_down)]
    code = R.main(cmd)
    EV.set_console_tap(None)
    from spark_rapids_tpu.aux import tracing
    window = seen.get("window")
    records = sorted(window.records, key=lambda r: r["end_s"]) \
        if window else []
    held = tracing.recent_summaries()
    summaries = held[-len(records):] if records and len(held) >= \
        len(records) else []
    completed = [r for r in records if "error" not in r]
    report = {"exit": code, "workload": args.workload, "seed": args.seed,
              "trace": args.trace, "session_conf": args.session_conf,
              "qps": len(completed) / window.closed_s if completed else None,
              "queries": len(records),
              "gap_totals": {k: seen["after"]["transitions"][k]
                             - seen["before"]["transitions"].get(k, 0)
                             for k in ("gap_count", "gap_seconds")
                             if k in seen["after"]["transitions"]}
              if window else None,
              "gap_means": gap_means(summaries, records)}
    if report["gap_totals"] and window:
        report["gap_totals"]["share_of_window_pct"] = 100.0 * \
            report["gap_totals"].get("gap_seconds", 0.0) / window.closed_s
    if "xplane" in kept:
        traced = [(s, r) for s, r in zip(summaries, records)
                  if r.get("traced")]
        report["gap_means_traced_queries"] = gap_means(
            [s for s, _r in traced], [r for _s, r in traced])
    print("span_probe: " + json.dumps({k: v for k, v in report.items()
                                       if k != "gap_means"}
                                      | {"gap_means_all":
                                         report["gap_means"]["all"]},
                                      default=str), file=sys.stderr)
    if "xplane" in kept:
        print("span_probe ledger_check: " + json.dumps(
            kept["xplane"]["ledger_check"], default=str), file=sys.stderr)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump({**report, **kept,
                   "summaries": [{k: v for k, v in s.items() if k != "nodes"}
                                 for s in summaries]}, f, default=str)
    print(f"span_probe: wrote {out_path}", file=sys.stderr)
    return code


if __name__ == "__main__":
    sys.exit(main())
