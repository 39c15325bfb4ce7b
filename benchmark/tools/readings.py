"""The control's readings, the upper ones the limits of ``limits.json`` are
set below.

    python benchmark/tools/readings.py --workload <cell> --seeds 1,2,3 --queries 4

For every seed: makes the cell's data at the cell's own size, draws the texts
a window of that seed sends, and puts the control in the program's place:
the reference's own answers to those texts with money held and accumulated
in float32, the nearest precision below the float64 the configurations
state, ordered and cut as the text says and judged by the run's own code.
The control has to come out as not correct.  The program's readings, the
lower ones, are what every run prints as its ``checks``.  One JSON line per
seed; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as R  # noqa: E402
from benchmark.compare import ordered  # noqa: E402
from benchmark.literals import LiteralPool  # noqa: E402


def control_rows(cell, gen, name: str, params: dict, dtype=np.float32):
    """The control's answer to one text as a client would receive it."""
    answer = R.load_by_name("reference", name).run(gen, params, dtype=dtype)
    rows = ordered(answer)
    if answer["limit"] is not None:
        rows = rows[:answer["limit"]]
    return [{k: (float(v) if isinstance(v, np.floating) else v)
             for k, v in row.items()} for row in rows]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--queries", type=int, default=4,
                    help="texts of the window compared per seed")
    ap.add_argument("--scale-down", type=int, default=1)
    args = ap.parse_args(argv)
    cell = R.Cell(args.workload, args.scale_down)
    for seed in (int(s) for s in args.seeds.split(",")):
        gen = cell.datagen.make(cell.rows, seed, **cell.config["datagen_args"])
        pool = LiteralPool(cell.queries, seed)
        for name in cell.queries:       # the draws the warm-up takes
            pool.draw(name)
        stream = R.Stream(cell, seed, 0, pool)
        records = []
        for _ in range(args.queries):
            name, values, _text = stream.next()
            records.append({"q": name, "params": values, "latency_s": 0.0,
                            "rows": control_rows(cell, gen, name, values)})
        checks, correct, checked = R.judge(cell, gen, records)
        print(json.dumps({
            "cell": cell.name, "seed": seed, "checked": checked,
            "rows": cell.rows,
            "control_float32": {k: v["value"] for k, v in checks.items()},
            "control_correct": correct}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
