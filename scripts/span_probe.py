"""What a traced run of a benchmark cell holds of the program's own spans.

    chiprun -- python scripts/span_probe.py --workload store_scan_agg --seed 7

Runs ``benchmark/run.py --trace 1`` in this process, keeps the xplane that
the harness would delete, and writes ``chiprun_out/span_probe.json``:

- the per-query summaries of the window (``phases``, counters, without the
  nodes) beside the client's latencies;
- the ``srt.*`` host events inside each ``bench.collect`` span, counted by
  name, with the query ids they carry;
- per host thread, the self time inside ``bench.collect`` by event name
  (an event's duration less what the events inside it on its thread
  cover) and the inclusive time;
- the device's time by program kind and by the scope each operation's
  ``op_name`` begins with (``jax.named_scope``), and the thirty
  ``op_name``s that took most of it.

Not part of the benchmark: it measures nothing the driver compares."""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: ``jit(run[join.pair])/jit(main)/expand/gather:`` -> ``join.pair``,
#: ``expand``
OP_NAME = re.compile(r"^jit\(run\[([^\]]+)\]\)/(?:jit\([^)]*\)/)*([^/:]+)")


def self_times(events):
    """``{name: self ns}`` of one thread's events ``(name, start, dur)``:
    every instant goes to the event that opened last among those open
    then.  (Not a stack: a ``with`` of a generator-based context manager
    shows as two short events, and the annotation it enters outlives the
    first.)"""
    import heapq
    edges = []
    for i, (_name, s, d) in enumerate(events):
        edges.append((s, 1, i))
        edges.append((s + d, 0, i))
    edges.sort()
    out, open_heap, closed, at = {}, [], set(), None
    for t, opens, i in edges:
        while open_heap and open_heap[0][1] in closed:
            heapq.heappop(open_heap)
        if open_heap:
            name = events[open_heap[0][1]][0]
            out[name] = out.get(name, 0.0) + (t - at)
        at = t
        if opens:
            heapq.heappush(open_heap, (-events[i][1], i))
        else:
            closed.add(i)
    return out


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


def inspect(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    collects, host_lines, device = [], [], {}
    for plane in data.planes:
        for line in plane.lines:
            events = [(e.name, float(e.start_ns), float(e.duration_ns),
                       dict(e.stats)) for e in line.events]
            if plane.name.startswith("/device:"):
                device[(plane.name, line.name)] = events
            else:
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
    return {"collect_spans": len(collects),
            "srt_events_inside_bench_collect": srt,
            "host_threads_inside_bench_collect": threads[:8],
            "device_ops_by_scope": scopes,
            "device_ops_by_op_name": ops}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="store_scan_agg")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=40)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--scale-down", type=int, default=1)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "span_probe.json"))
    args = ap.parse_args(argv)
    from benchmark import run as R
    kept = {}
    real_rmtree = R.shutil.rmtree

    def keep_the_trace(path, *a, **kw):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if found:
            kept["xplane"] = inspect(sorted(found,
                                            key=os.path.getmtime)[-1])
        return real_rmtree(path, *a, **kw)

    R.shutil.rmtree = keep_the_trace
    cmd = ["--workload", args.workload, "--seed", str(args.seed),
           "--seconds", str(args.seconds), "--trace", "1"]
    if args.rehearse:
        cmd += ["--rehearse", "--scale-down", str(args.scale_down)]
    code = R.main(cmd)
    from spark_rapids_tpu.aux import tracing
    summaries = [{k: v for k, v in s.items() if k != "nodes"}
                 for s in tracing.recent_summaries()]
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"exit": code, "summaries": summaries, **kept}, f,
                  indent=1, default=str)
    print(f"span_probe: wrote {args.out}", file=sys.stderr)
    return code


if __name__ == "__main__":
    sys.exit(main())
