"""From a profiler trace (``*.xplane.pb``) to the numbers the benchmark
reports: how long an operation ran on the device, the traced window, the
operations that took most time (by self time: a loop's body is listed, the
loop only for what it spends outside it), and the idle gaps by what the host
was doing.

Read with nothing but ``jax.profiler.ProfileData``.  The reduction itself
(:func:`reduce_events`) works on plain tuples, so it is tested on a small
recorded trace kept beside it (``fixture_trace.json``) and on hand-made
intervals.

- A device plane is one whose name starts with ``/device:TPU:``; the
  operations are the events of its ``XLA Ops`` line, the programs those of
  ``XLA Modules``.  Busy time is the union of the operations' intervals
  (the programs' where a plane has no operations line), averaged over the
  device planes that have any.
- The window is the span from the first to the last event of the harness's
  own spans (``bench.*`` annotations on the host's planes), or of the device
  events where there are none.
- An idle gap is an interval of the window in which no operation ran on the
  first device.  It goes to the harness span that covers most of it, or to
  ``(no span)``.
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


def read_xplane(path: str) -> dict:
    """``{"devices": {plane: {"ops": [...], "modules": [...]}}, "spans":
    [...]}``; every event a tuple ``(name, start_ns, duration_ns)``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [(e.name, float(e.start_ns),
                                         float(e.duration_ns))
                                        for e in line.events]
            devices[plane.name] = {"ops": lines.get(OPS_LINE, []),
                                   "modules": lines.get(MODULES_LINE, [])}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):],
                                      float(e.start_ns),
                                      float(e.duration_ns)))
    return {"devices": devices, "spans": spans}


def union(intervals):
    """Merged, sorted ``[start, end]`` intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def short_program(name: str) -> str:
    """``jit_run_join.pair(1613...)`` -> ``join.pair``: the program's kind
    without jit's prefix and the key's hash."""
    name = name.split("(", 1)[0]
    return name[len("jit_run_"):] if name.startswith("jit_run_") else name


def short_op(name: str) -> str:
    """``%while.7 = (u32[]...) while(...)`` -> ``%while.7``."""
    return name.split(" = ", 1)[0][:60]


def name_ops(ops, modules):
    """Each operation under ``<program>:<op>``, the program being the one
    that was running when the operation started."""
    import bisect
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    named = []
    for name, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        inside = i >= 0 and s < modules[i][1] + modules[i][2]
        program = short_program(modules[i][0]) if inside else "(no program)"
        named.append((f"{program}:{short_op(name)}", s, d))
    return named


def self_times(ops):
    """``(name, self_ns)`` per operation: its duration less that of the
    operations nested directly inside it (a ``while`` holds its body's
    fusions on the same line), so that the self times add up to the busy
    time and no nanosecond is listed twice."""
    out, stack = [], []          # stack of [end, index into out]
    for name, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and s >= stack[-1][0]:
            stack.pop()
        if stack:
            parent = out[stack[-1][1]]
            parent[1] -= min(d, stack[-1][0] - s)
        out.append([name, d])
        stack.append([s + d, len(out) - 1])
    return out


def _covering_span(gap, spans) -> str:
    best, best_cover = "(no span)", 0.0
    for name, start, dur in spans:
        cover = min(gap[1], start + dur) - max(gap[0], start)
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def reduce_events(events: dict) -> dict:
    spans = events["spans"]
    planes = [p for p in events["devices"].values()
              if p["ops"] or p["modules"]]
    timed = [(s, s + d) for _, s, d in spans] or \
        [(s, s + d) for p in planes for _, s, d in (p["ops"] or p["modules"])]
    if not timed:
        return {"busy_s": 0.0, "window_s": 0.0, "device_ops": [],
                "idle_gaps": [], "programs": []}
    lo, hi = min(s for s, _ in timed), max(e for _, e in timed)
    busy = []
    by_op: dict = {}
    by_program: dict = {}
    first_busy = None
    for plane in planes:
        ops = plane["ops"] or plane["modules"]
        merged = union((max(s, lo), min(s + d, hi)) for _, s, d in ops
                       if s + d > lo and s < hi)
        busy.append(sum(e - s for s, e in merged))
        if first_busy is None:
            first_busy = merged
        for name, d in self_times(name_ops(ops, plane["modules"])):
            by_op[name] = by_op.get(name, 0.0) + d
        for name, s, d in plane["modules"]:
            name = short_program(name)
            by_program[name] = by_program.get(name, 0.0) + d
    gaps: dict = {}
    edge = lo
    for s, e in (first_busy or []) + [[hi, hi]]:
        if s > edge:
            name = _covering_span((edge, s), spans)
            gaps[name] = gaps.get(name, 0.0) + (s - edge)
        edge = max(edge, e)
    n = max(1, len(planes))
    top = lambda d: [[k, v / 1e9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])]
    return {"busy_s": sum(busy) / n / 1e9, "window_s": (hi - lo) / 1e9,
            "device_ops": top({k: v / n for k, v in by_op.items()}),
            "programs": top({k: v / n for k, v in by_program.items()}),
            "idle_gaps": top(gaps)}


def reduce_directory(directory: str) -> dict:
    """Reduces the newest trace under ``directory``."""
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {directory}")
    return reduce_events(read_xplane(found[-1]))
