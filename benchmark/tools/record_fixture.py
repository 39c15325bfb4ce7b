"""Cuts a recorded ``*.xplane.pb`` down to the small fixture the reduction
is tested on (``benchmark/trace/fixture_trace.json``).

    python benchmark/tools/record_fixture.py <trace.xplane.pb> <span> <min_op_ms>

Keeps the named harness span, the device programs inside it and the device
operations inside it that ran for at least ``min_op_ms``; works the expected
values out with a sweep of its own, not with the code under test.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def busy_by_sweep(intervals, lo, hi) -> float:
    """Covered length of [lo, hi] by counting open intervals at each edge."""
    edges = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            edges += [(s, 1), (e, -1)]
    edges.sort()
    open_now, since, covered = 0, None, 0.0
    for t, step in edges:
        if open_now == 0 and step == 1:
            since = t
        open_now += step
        if open_now == 0:
            covered += t - since
    return covered


def main(argv) -> int:
    from benchmark.trace.reduce import read_xplane, short_program
    path, span_name, min_ms = argv[0], argv[1], float(argv[2])
    events = read_xplane(path)
    name, start, dur = next(s for s in events["spans"] if s[0] == span_name)
    lo, hi = start, start + dur
    inside = lambda evs: [[n[:120], s - lo, d] for n, s, d in evs
                          if s >= lo and s + d <= hi]
    devices = {}
    for plane, lines in events["devices"].items():
        devices[plane] = {
            "ops": [e for e in inside(lines["ops"]) if e[2] >= min_ms * 1e6],
            "modules": inside(lines["modules"])}
    spans = [[name, 0.0, dur]]
    first = next(iter(devices.values()))
    ops = first["ops"]
    by_program: dict = {}
    for n, _s, d in first["modules"]:
        by_program[short_program(n)] = by_program.get(short_program(n), 0.0) + d
    busy = busy_by_sweep([(s, s + d) for _n, s, d in ops], 0.0, dur)
    out = {"recorded_from": os.path.basename(path), "span": span_name,
           "min_op_ms": min_ms, "devices": devices, "spans": spans,
           "expected": {"busy_s": busy / 1e9, "window_s": dur / 1e9,
                        "top_program": max(by_program, key=by_program.get),
                        "gap_spans": [name]}}
    target = os.path.join(ROOT, "benchmark", "trace", "fixture_trace.json")
    with open(target, "w") as f:
        json.dump(out, f)
    print(target, os.path.getsize(target), "bytes;", len(ops), "ops;",
          out["expected"])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
