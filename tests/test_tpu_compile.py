"""The smoke's main programs, compiled for a v5e that is described and not
attached (``on-chip-measurement`` guide, section 2.3): each must be
accepted by the chip's compiler at the smoke's shapes.

A compile that passes is not a chip run.  What these guard is that no
later PR hands the TPU compiler a program it refuses, or a sort that
carries whole rows (``ops/sort_ops.py``: build time grows steeply with a
variadic sort's operand count) — asserted as an operand COUNT, not a
clock, so busy test workers cannot fail it.

One file, one process: the topology is described inside a module-scoped
fixture (never at import), which loads libtpu and keeps its lock.
"""

import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.testing import tpu_compile as TC

SMALL = 32_768
LARGE = 1 << 20


@pytest.fixture(scope="module")
def topo():
    try:
        return TC.describe_v5e()
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without a chip: keep the cache out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def tpu_branch(monkeypatch, topo, no_compile_cache):
    """Trace the TPU branch of the 64-bit-float kernels: under
    ``JAX_PLATFORMS=cpu`` ``f64_bitcast_ok()`` otherwise answers for the
    CPU and traces a bitcast the TPU lacks."""
    from spark_rapids_tpu.ops import f64bits
    monkeypatch.setattr(f64bits, "_BITCAST64", False)
    return topo


def _column(n, dt):
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.column import DeviceColumn
    valid = jnp.ones(n, dtype=bool)
    if dt is T.STRING:
        return DeviceColumn(jnp.zeros((n, 16), dtype=np.uint8), valid, n,
                            dt, jnp.zeros(n, dtype=np.int32))
    return DeviceColumn(jnp.zeros(n, dtype=dt.np_dtype), valid, n, dt)


def _batch(n, *dtypes):
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    return ColumnarBatch([_column(n, dt) for dt in dtypes], n,
                         [f"c{i}" for i in range(len(dtypes))])


def _compiles(topo, fn, *args, **kwargs):
    prog, specs = TC.ProgramRecorder().capture(fn, *args, **kwargs)
    compiled = TC.compile_for_chip(prog, specs, topo)
    assert compiled.memory_analysis() is not None
    return prog, specs


def test_flagship_fused_stage(tpu_branch):
    import jax
    from jax.sharding import SingleDeviceSharding
    from __graft_entry__ import _fused_pipeline_fn
    one_chip = SingleDeviceSharding(tpu_branch.devices[0])
    args = [jax.ShapeDtypeStruct((LARGE,), dt, sharding=one_chip)
            for dt in (np.int64, np.float64, np.int32, np.bool_)]
    compiled = jax.jit(_fused_pipeline_fn(LARGE)).lower(*args).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("rows", [SMALL, LARGE])
def test_compact_batch_sorts_only_what_orders(tpu_branch, rows):
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.batch_ops import compact_batch
    batch = _batch(rows, T.LONG, T.DOUBLE, T.INT, T.STRING)
    prog, specs = _compiles(tpu_branch, compact_batch, batch,
                            jnp.ones(rows, dtype=bool))
    counts = TC.sort_operand_counts(prog, specs)
    assert counts and max(counts) <= 2, counts


@pytest.mark.parametrize("rows", [SMALL, 1 << 22])
def test_exchange_sorts_a_map_batch_by_partition_id(tpu_branch, rows):
    """``exchange.sort`` at the partial aggregate's bucket and at a fact
    partition's: one one-operand sort of the packed (id, position), one
    gather a plane."""
    import jax.numpy as jnp
    from spark_rapids_tpu.exec.exchange import sort_by_partition
    batch = _batch(rows, T.LONG, T.DOUBLE, T.INT, T.STRING)
    prog, specs = _compiles(tpu_branch, sort_by_partition, batch,
                            jnp.zeros(rows, dtype=np.int32), 10)
    assert prog.kind == "exchange.sort"
    counts = TC.sort_operand_counts(prog, specs)
    assert counts and max(counts) == 1, counts


def test_exchange_reads_ten_pieces_at_the_bucket_of_their_rows(tpu_branch):
    """``exchange.read`` as ``store_sf10_tasks`` runs it: ten pieces at the
    32,768-row floor, some four thousand rows read."""
    from spark_rapids_tpu.exec import exchange as X
    pieces = []
    for _ in range(10):
        piece = X._SortedPiece(_batch(SMALL, T.INT, T.STRING, T.DOUBLE,
                                      T.LONG))
        piece.settle([410] * 10, 10)
        pieces.append(piece)
    prog, specs = _compiles(tpu_branch, X._read_rows, pieces, 0, 10, 10)
    assert prog.kind == "exchange.read"
    assert not TC.sort_operand_counts(prog, specs)


def test_sized_stage_and_its_compact_terminal(tpu_branch):
    """The star cell's demographic stage at SF1's shapes (2,097,152 rows,
    a key and three strings, filter only): the stage program that hands
    on its planes uncompacted with the permutation and the count, and
    ``fused.compact`` at the floor's bucket."""
    import jax.numpy as jnp
    from spark_rapids_tpu.exec.fused import TpuFusedStageExec
    from spark_rapids_tpu.columnar.column import SIZED_MIN_BUCKET
    from spark_rapids_tpu.expressions.base import BoundReference
    from spark_rapids_tpu.expressions.predicates import EqualTo
    from spark_rapids_tpu.plan.base import LeafExec
    from spark_rapids_tpu.plan.stages import PromotedLiteral
    rows = 1 << 21
    assert rows > SIZED_MIN_BUCKET
    batch = _batch(rows, T.LONG, T.STRING, T.STRING, T.STRING)
    values = ("M", "S", "College")
    stage = TpuFusedStageExec(
        [("filter", EqualTo(BoundReference(i + 1, T.STRING, True, f"c{i}"),
                            PromotedLiteral(v, T.STRING, i)))
         for i, v in enumerate(values)], LeafExec(),
        promoted=[PromotedLiteral(v, T.STRING, i)
                  for i, v in enumerate(values)])
    prog, specs = _compiles(tpu_branch,
                            lambda: stage._finish(*stage._program(batch)))
    assert prog.kind == "fused.stage"
    counts = TC.sort_operand_counts(prog, specs)
    assert counts and max(counts) <= 2, counts
    planes = [(c.data, c.validity, c.lengths, c.elem_valid)
              for c in batch.columns]
    prog, _ = _compiles(tpu_branch, stage._compact_sized, planes,
                        jnp.zeros(rows, dtype=np.int32), np.int32(27_440))
    assert prog.kind == "fused.compact"


def test_double_key_sort(tpu_branch):
    from spark_rapids_tpu.ops.sort_ops import SortOrder, sort_gather_batch
    batch = _batch(LARGE, T.DOUBLE, T.LONG, T.STRING)
    prog, specs = _compiles(tpu_branch, sort_gather_batch, batch,
                            [SortOrder.desc(0), SortOrder.asc(1)])
    assert max(TC.sort_operand_counts(prog, specs)) <= 2


def test_two_key_group_by(tpu_branch):
    from spark_rapids_tpu.ops.agg_ops import segmented_aggregate
    batch = _batch(LARGE, T.INT, T.STRING, T.DOUBLE, T.LONG)
    prog, specs = _compiles(
        tpu_branch, segmented_aggregate, batch, 2,
        [(2, "sum", False, T.DOUBLE), (3, "count", True, T.LONG)])
    assert max(TC.sort_operand_counts(prog, specs)) <= 2


def test_join_build_probe_and_pairs(tpu_branch):
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import join_ops as J
    build = _batch(LARGE, T.LONG, T.DOUBLE)
    probe = _batch(1 << 19, T.LONG, T.INT)
    prog, specs = _compiles(tpu_branch, J.build_side, build, (0,),
                            [probe.columns[0]])
    assert max(TC.sort_operand_counts(prog, specs)) <= 2
    # a dimension at the floor's bucket under the fact table's: the
    # probe's bucket widens the table to 2^23 slots
    fact = _batch(1 << 22, T.LONG)
    _compiles(tpu_branch, J.build_side, _batch(SMALL, T.LONG, T.DOUBLE),
              (0,), [fact.columns[0]])
    built = J.BuiltSide(
        build, (0,),
        jnp.zeros((1 << J._table_bits(LARGE, 1 << 19)) + 1,
                  dtype=np.int32),
        jnp.zeros(LARGE, dtype=np.int32), [1])
    _compiles(tpu_branch, J._probe_ranges, [probe.columns[0]], built)
    # the pair table under speculative sizing: twice the probe bucket
    ranges = jnp.zeros(1 << 19, dtype=np.int64)
    _compiles(tpu_branch, J._expand_verify, probe, (0,), built, (False,),
              ranges, ranges, jnp.int64(0), LARGE)


def test_window_over_partition(tpu_branch):
    from spark_rapids_tpu.ops.window_ops import compute_windows
    # payload (string, double) ++ partition key (string) ++ value (double)
    batch = _batch(LARGE, T.STRING, T.DOUBLE, T.STRING, T.DOUBLE)
    prog, specs = _compiles(
        tpu_branch, compute_windows, batch, 2, 1, [],
        [("agg", "sum", 3, "range", None, None, False), ("row_number",)])
    assert max(TC.sort_operand_counts(prog, specs)) <= 2


def test_result_pack(tpu_branch):
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.transfer import _pack_planes
    batch = _batch(LARGE, T.LONG, T.DOUBLE, T.INT, T.STRING)
    planes = []
    for c in batch.columns:
        planes += [p for p in (c.data, c.validity, c.lengths)
                   if p is not None]
    _compiles(tpu_branch, _pack_planes, planes, 4096, jnp.int64(100))
