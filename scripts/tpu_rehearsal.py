"""Asks the chip's compiler about every program the smoke builds — here,
without the chip (``on-chip-measurement`` guide, section 2.3).

    JAX_PLATFORMS=cpu python scripts/tpu_rehearsal.py [--mesh] [--json OUT]

1. Runs ``chip_smoke.py``'s queries once on the CPU at the smoke's size,
   with ``f64bits._BITCAST64 = False`` so that the TPU branch of every
   64-bit-float kernel is the one traced (under ``JAX_PLATFORMS=cpu``
   ``f64_bitcast_ok()`` otherwise answers for the CPU and traces a 64-bit
   bitcast the TPU lacks).  Answers are not checked here: the dd split is
   lossy on a real binary64, and correctness is the smoke's business.
2. Takes every ``StageProgram``'s jitted function and the argument shapes
   of each distinct call from the executable cache.
3. Lowers each for a described (not attached) ``v5e:2x2`` chip and prints
   kind, key, compile seconds and ``memory_analysis()`` per program,
   sorted by seconds, with the total.  A refusal is printed with its error
   and makes the exit code 1.

``--mesh`` adds the four-chip path: the smoke's mesh phase on four virtual
CPU devices, then the shard_map all-to-all (``parallel/collective.py``) on
a four-device ``Mesh`` built from the described topology.

One process only: libtpu's lock admits one at a time.  Sizes shrink with
``--rows`` / ``--sf`` for a quick pass over the script itself.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from spark_rapids_tpu.testing.tpu_compile import (  # noqa: E402
    ProgramRecorder, compile_for_chip, describe_v5e)

#: the rule of ISSUE 25: per program and for the whole set
MAX_PROGRAM_S = 60.0
MAX_TOTAL_S = 15 * 60.0


def run_smoke_queries(rec: ProgramRecorder, rows: int, ref_rows: int, sf: float,
                      data_dir: str) -> None:
    """chip_smoke's one-chip program set, unchecked."""
    import chip_smoke as S
    from spark_rapids_tpu.testing.tpcds_queries import QUERIES
    tpu, _cpu = S.make_sessions()
    rec.phase = "resident"
    data = S.build_resident_data(rows)
    S.resident_query(tpu.create_dataframe(
        data, num_partitions=S.RESIDENT_PARTS)).collect()
    head = {k: v[:min(ref_rows, rows)] for k, v in data.items()}
    del data
    S.resident_query(tpu.create_dataframe(
        head, num_partitions=S.RESIDENT_PARTS)).collect()
    S.register_tpcds((tpu,), sf, data_dir)
    for q in S.TPCDS_QUERIES:
        rec.phase = q
        tpu.sql(QUERIES[q]).collect()
    rec.phase = "serving"
    for m in S.SERVING_Q3_MOYS:
        tpu.sql(QUERIES["q3"].replace("d_moy = 11",
                                      f"d_moy = {m}")).collect()


def run_mesh_queries(rec: ProgramRecorder, sf: float, data_dir: str) -> None:
    """chip_smoke's four-chip program set on four virtual CPU devices."""
    import chip_smoke as S
    from spark_rapids_tpu.parallel.mesh import set_active_mesh
    from spark_rapids_tpu.testing.tpcds_queries import QUERIES
    tpu, _cpu = S.make_sessions(
        S.mesh_conf(4, os.path.join(data_dir, "events.jsonl")))
    try:
        S.register_tpcds((tpu,), sf, data_dir, num_partitions=4,
                         storage="memory")
        rec.phase = "mesh"
        tpu.sql(S.MESH_GROUPBY).collect()
        tpu.sql(QUERIES["q3"]).collect()
    finally:
        set_active_mesh(None)


def recapture_collectives(rec: ProgramRecorder, topo) -> None:
    """The all-to-all closes over its mesh, so the programs recorded on the
    CPU mesh cannot be lowered for the chip: build each again over a
    four-device ``Mesh`` of the described topology (capture-only)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from spark_rapids_tpu.parallel import collective as C
    from spark_rapids_tpu.parallel.mesh import MeshContext
    ctx = MeshContext(Mesh(np.asarray(topo.devices[:4]), ("data",)),
                      data_axis="data")
    sharding = ctx.data_sharding()

    def on_mesh(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)

    old = [(k, v) for k, v in rec.calls.items()
           if k[0] == "parallel.collective_shuffle"]
    # the cache key names the mesh by device ids, which the described chips
    # share with the CPU devices: drop the CPU-mesh programs (the recorder
    # keeps its own references) so that each is built anew
    from spark_rapids_tpu.exec import stage_compiler
    stage_compiler.clear()
    for ident, (_prog, specs, phase) in old:
        del rec.calls[ident]
        arrs, counts, pids = jax.tree.map(on_mesh, specs)
        rec.phase = phase
        rec.capture(C.collective_hash_shuffle, ctx,
                    [tuple(c) for c in arrs], counts, pids)


def compile_all(rec: ProgramRecorder, topo) -> list:
    import jax
    rows = []
    n = len(rec.calls)
    for i, ((kind, key_hash, _sig), (prog, specs, phase)) in \
            enumerate(rec.calls.items()):
        row = {"kind": kind, "key": key_hash, "phase": phase,
               "args": sum(1 for _ in jax.tree.leaves(specs))}
        t0 = time.perf_counter()
        try:
            compiled = compile_for_chip(prog, specs, topo)
            row["seconds"] = time.perf_counter() - t0
            mem = compiled.memory_analysis()
            row["temp_bytes"] = mem.temp_size_in_bytes
            row["argument_bytes"] = mem.argument_size_in_bytes
            row["output_bytes"] = mem.output_size_in_bytes
            row["code_bytes"] = mem.generated_code_size_in_bytes
        except Exception as e:  # noqa: BLE001 — a refusal is the finding
            row["seconds"] = time.perf_counter() - t0
            row["refused"] = f"{type(e).__name__}: {e}"[:2000]
        rows.append(row)
        print(f"[{i + 1}/{n}] {kind} {key_hash} ({phase}) "
              f"{row['seconds']:.1f}s"
              + (f" REFUSED {row['refused'][:300]}" if "refused" in row
                 else ""), file=sys.stderr, flush=True)
    return rows


def print_table(rows: list) -> int:
    rows = sorted(rows, key=lambda r: -r["seconds"])
    print(f"{'seconds':>8}  {'kind':<28} {'key':<12} {'phase':<9} "
          f"{'temp MiB':>9} {'args MiB':>9} {'out MiB':>8}")
    for r in rows:
        if "refused" in r:
            print(f"{r['seconds']:8.1f}  {r['kind']:<28} {r['key']:<12} "
                  f"{r['phase']:<9} REFUSED: {r['refused']}")
            continue
        print(f"{r['seconds']:8.1f}  {r['kind']:<28} {r['key']:<12} "
              f"{r['phase']:<9} {r['temp_bytes'] / 2**20:9.1f} "
              f"{r['argument_bytes'] / 2**20:9.1f} "
              f"{r['output_bytes'] / 2**20:8.1f}")
    total = sum(r["seconds"] for r in rows)
    refused = [r for r in rows if "refused" in r]
    slow = [r for r in rows if r["seconds"] > MAX_PROGRAM_S]
    print(f"programs={len(rows)} total_compile_s={total:.1f} "
          f"refused={len(refused)} over_{MAX_PROGRAM_S:.0f}s={len(slow)} "
          f"total_under_{MAX_TOTAL_S:.0f}s={total < MAX_TOTAL_S}")
    return 1 if refused else 0


def main(argv=None) -> int:
    import chip_smoke as S
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=S.RESIDENT_ROWS)
    ap.add_argument("--ref-rows", type=int, default=S.RESIDENT_REF_ROWS)
    ap.add_argument("--sf", type=float, default=S.TPCDS_SF)
    ap.add_argument("--mesh", action="store_true",
                    help="add the four-chip phase and its all-to-all")
    ap.add_argument("--only-mesh", action="store_true",
                    help="the four-chip phase alone")
    ap.add_argument("--json", default="",
                    help="also write the rows to this file")
    args = ap.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS", "").lower() != "cpu":
        raise SystemExit("run with JAX_PLATFORMS=cpu: this script must "
                         "never reach for a chip")

    import jax
    from spark_rapids_tpu.ops import f64bits
    f64bits._BITCAST64 = False      # trace the TPU branch
    # compiles for a described chip are written to the persistent cache but
    # cannot be read back without one: keep it out of the way
    jax.config.update("jax_enable_compilation_cache", False)

    rec = ProgramRecorder()
    t0 = time.perf_counter()
    with rec.installed(), \
            tempfile.TemporaryDirectory(prefix="tpu_rehearsal_") as tmp:
        if not args.only_mesh:
            run_smoke_queries(rec, args.rows, args.ref_rows, args.sf,
                              os.path.join(tmp, "one"))
        if args.mesh or args.only_mesh:
            run_mesh_queries(rec, args.sf, os.path.join(tmp, "mesh"))
    print(f"recorded {len(rec.calls)} program call shapes in "
          f"{time.perf_counter() - t0:.0f}s on the CPU",
          file=sys.stderr, flush=True)

    topo = describe_v5e()
    if args.mesh or args.only_mesh:
        recapture_collectives(rec, topo)
    rows = compile_all(rec, topo)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
    return print_table(rows)


if __name__ == "__main__":
    sys.exit(main())
