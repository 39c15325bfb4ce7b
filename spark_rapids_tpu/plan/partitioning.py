"""Output partitioning strategies.

Reference: GpuPartitioning.scala:37 (device slice), GpuHashPartitioningBase
(cudf hash partition; Spark-murmur3 pmod numPartitions), GpuRangePartitioner
(sample + sort bounds), GpuRoundRobinPartitioning, GpuSinglePartitioning —
registered in the PartRule map (GpuOverrides.scala:3875).

TPU-first: a partitioning only computes a per-row partition-id column; the
exchange then sorts by pid (fused lax.sort, stable) and slices — one device
pass regardless of fan-out, instead of cuDF's table split.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, HostColumnarBatch
from spark_rapids_tpu.expressions.base import (EvalContext, Expression,
                                               expr_key)


class Partitioning:
    num_partitions: int = 1

    #: expressions the planner must type-check (keys)
    @property
    def exprs(self) -> List[Expression]:
        return []

    def partition_ids_tpu(self, batch: ColumnarBatch):
        """int32[bucket] pid per row (padding rows get num_partitions)."""
        raise NotImplementedError

    # -- the same ids as ONE traced program (exec/exchange.py) --------------
    def program_key(self) -> tuple:
        """What the ids depend on beside the shapes of ``pid_inputs``."""
        return (type(self).__name__, self.num_partitions,
                tuple(expr_key(e) for e in self.exprs))

    def pid_inputs(self, batch: ColumnarBatch, kind: str = "expr.project"):
        """``(batch, further device batches)`` that :meth:`pids_from`
        computes the ids from; a program dispatched to make them goes
        under ``kind``."""
        return batch, ()

    def pids_from(self, batch: ColumnarBatch, *more):
        """:meth:`partition_ids_tpu` over :meth:`pid_inputs`; traceable."""
        return self.partition_ids_tpu(batch)

    def partition_ids_cpu(self, batch: HostColumnarBatch) -> np.ndarray:
        raise NotImplementedError

    def desc(self) -> str:
        return type(self).__name__


@dataclasses.dataclass
class SinglePartitioning(Partitioning):
    num_partitions: int = 1

    def partition_ids_tpu(self, batch):
        from spark_rapids_tpu.columnar.column import _jnp
        jnp = _jnp()
        pos = jnp.arange(batch.bucket, dtype=np.int32)
        return jnp.where(pos < batch.row_count, 0, 1).astype(np.int32)

    def partition_ids_cpu(self, batch):
        return np.zeros(batch.row_count, dtype=np.int32)

    def desc(self):
        return "SinglePartition"


class HashPartitioning(Partitioning):
    """pid = pmod(murmur3(keys, seed=42), n) — bit-exact Spark placement
    (reference: GpuHashPartitioningBase + HashFunctions murmur3)."""

    def __init__(self, key_exprs: Sequence[Expression], n: int):
        self.key_exprs = list(key_exprs)
        self.num_partitions = n

    @property
    def exprs(self):
        return self.key_exprs

    def _hash_expr(self):
        from spark_rapids_tpu.expressions.hashing import Murmur3Hash
        return Murmur3Hash(*self.key_exprs)

    def partition_ids_tpu(self, batch):
        from spark_rapids_tpu.columnar.column import _jnp
        from spark_rapids_tpu.expressions.evaluator import device_batch_tcols
        jnp = _jnp()
        ctx = EvalContext(device_batch_tcols(batch), "tpu", batch.bucket)
        h = self._hash_expr().eval_tpu(ctx)
        n = np.int32(self.num_partitions)
        pid = ((h.data % n) + n) % n
        pos = jnp.arange(batch.bucket, dtype=np.int32)
        return jnp.where(pos < batch.row_count, pid,
                         self.num_partitions).astype(np.int32)

    def partition_ids_cpu(self, batch):
        from spark_rapids_tpu.expressions.evaluator import (host_batch_tcols,
                                                            tcol_to_host_column)
        ctx = EvalContext(host_batch_tcols(batch), "cpu", batch.row_count)
        h = self._hash_expr().eval_cpu(ctx)
        hv = np.asarray(tcol_to_host_column(h, batch.row_count).arrow)
        n = np.int32(self.num_partitions)
        return (((hv.astype(np.int32) % n) + n) % n).astype(np.int32)

    def desc(self):
        ks = ", ".join(e.sql() for e in self.key_exprs)
        return f"HashPartitioning({ks}, {self.num_partitions})"


class RoundRobinPartitioning(Partitioning):
    def __init__(self, n: int, start: int = 0):
        self.num_partitions = n
        self.start = start

    def program_key(self):
        return super().program_key() + (self.start,)

    def partition_ids_tpu(self, batch):
        from spark_rapids_tpu.columnar.column import _jnp
        jnp = _jnp()
        pos = jnp.arange(batch.bucket, dtype=np.int32)
        pid = (pos + np.int32(self.start)) % np.int32(self.num_partitions)
        return jnp.where(pos < batch.row_count, pid,
                         self.num_partitions).astype(np.int32)

    def partition_ids_cpu(self, batch):
        pos = np.arange(batch.row_count, dtype=np.int32)
        return ((pos + self.start) % self.num_partitions).astype(np.int32)

    def desc(self):
        return f"RoundRobinPartitioning({self.num_partitions})"


class RangePartitioning(Partitioning):
    """Range partitioning over sort keys; ``bounds`` (a host batch of key
    columns, n-1 rows) is produced by the exchange from a sample
    (reference: GpuRangePartitioner.sketch/createRangeBounds)."""

    def __init__(self, specs, n: int,
                 bounds: Optional[HostColumnarBatch] = None):
        from spark_rapids_tpu.exec.sort import SortSpec  # noqa: F401
        self.specs = list(specs)
        self.num_partitions = n
        self.bounds = bounds

    @property
    def exprs(self):
        return [s.expr for s in self.specs]

    # -- key normalization (shared with the device sort) --------------------
    def _key_batch_tpu(self, batch: ColumnarBatch,
                       kind: str = "expr.project") -> ColumnarBatch:
        from spark_rapids_tpu.expressions.base import Alias
        from spark_rapids_tpu.expressions.evaluator import eval_exprs_tpu
        return eval_exprs_tpu(
            [Alias(s.expr, f"k{i}") for i, s in enumerate(self.specs)], batch,
            kind=kind)

    def _key_batch_cpu(self, batch: HostColumnarBatch) -> HostColumnarBatch:
        from spark_rapids_tpu.expressions.evaluator import (eval_exprs_cpu,)
        from spark_rapids_tpu.expressions.base import Alias
        return eval_exprs_cpu(
            [Alias(s.expr, f"k{i}") for i, s in enumerate(self.specs)], batch)

    def _norm_words(self, key_batch: ColumnarBatch, jnp):
        """Per-row list of order words (same normalization as sort_ops, so
        bound comparison == sort order)."""
        from spark_rapids_tpu.ops.sort_ops import SortOrder, _order_words
        words = []
        for i, s in enumerate(self.specs):
            o = SortOrder(i, s.ascending, s.effective_nulls_first)
            words.extend(_order_words(key_batch.columns[i], o, jnp))
        return words

    @staticmethod
    def _align_widths(a: ColumnarBatch, b: ColumnarBatch, jnp):
        """Pads string key columns to a common width so both sides produce
        the same number of sortable words."""
        from spark_rapids_tpu.columnar.column import DeviceColumn

        def pad(col, w):
            if col.lengths is None or col.data.shape[1] >= w:
                return col
            d = jnp.pad(col.data, ((0, 0), (0, w - col.data.shape[1])))
            return DeviceColumn(d, col.validity, col.row_count,
                                col.data_type, col.lengths)

        ac, bc = [], []
        for ca, cb in zip(a.columns, b.columns):
            if ca.lengths is not None:
                w = max(ca.data.shape[1], cb.data.shape[1])
                ca, cb = pad(ca, w), pad(cb, w)
            ac.append(ca)
            bc.append(cb)
        return (ColumnarBatch(ac, a.row_count, a.names),
                ColumnarBatch(bc, b.row_count, b.names))

    def program_key(self):
        return super().program_key() + tuple(
            (s.ascending, s.effective_nulls_first) for s in self.specs)

    def pid_inputs(self, batch, kind: str = "expr.project"):
        assert self.bounds is not None, "bounds not computed"
        keys = self._key_batch_tpu(batch, kind)
        if self.bounds.row_count == 0:
            return keys, ()
        return keys, (self.bounds.to_device(),)

    def partition_ids_tpu(self, batch):
        return self.pids_from(*self.pid_inputs(batch))

    def pids_from(self, keys, bnd=None):
        """The ids from the evaluated key columns and the bounds' (n-1
        rows, a count the host knows; none: every row to partition 0)."""
        from spark_rapids_tpu.columnar.column import _jnp
        jnp = _jnp()
        bucket = keys.bucket
        live = jnp.arange(bucket, dtype=np.int32) < keys.row_count
        if bnd is None:
            return jnp.where(live, 0, self.num_partitions).astype(np.int32)
        keys, bnd = self._align_widths(keys, bnd, jnp)
        row_words = self._norm_words(keys, jnp)
        bound_words = self._norm_words(bnd, jnp)
        pid = jnp.zeros(bucket, dtype=np.int32)
        for j in range(int(bnd.row_count)):
            # lexicographic row > bound_j
            gt = jnp.zeros(bucket, dtype=bool)
            eq = jnp.ones(bucket, dtype=bool)
            for rw, bw in zip(row_words, bound_words):
                bj = bw[j]
                gt = gt | (eq & (rw > bj))
                eq = eq & (rw == bj)
            pid = pid + gt.astype(np.int32)
        return jnp.where(live, pid, self.num_partitions).astype(np.int32)

    def partition_ids_cpu(self, batch):
        # genuinely host-side: numpy twin of the device word normalization
        # (the CPU oracle must never touch the accelerator)
        from spark_rapids_tpu.ops.sort_ops import host_order_words
        assert self.bounds is not None, "bounds not computed"
        n = batch.row_count
        if self.bounds.row_count == 0:
            return np.zeros(n, dtype=np.int32)
        keys = self._key_batch_cpu(batch)
        # agree on string rectangle widths across rows and bounds; keep the
        # probed rectangles so the scatter isn't done twice per column
        widths, kpairs, bpairs = [], [], []
        for kc, bc in zip(keys.columns, self.bounds.columns):
            if isinstance(kc.data_type, (T.StringType, T.BinaryType)):
                kp, bp = kc.string_np(), bc.string_np()
                widths.append(max(kp[0].shape[1], bp[0].shape[1], 1))
                kpairs.append(kp)
                bpairs.append(bp)
            else:
                widths.append(None)
                kpairs.append(None)
                bpairs.append(None)
        row_words: List[np.ndarray] = []
        bound_words: List[np.ndarray] = []
        for i, s in enumerate(self.specs):
            from spark_rapids_tpu.ops.sort_ops import SortOrder
            o = SortOrder(i, s.ascending, s.effective_nulls_first)
            row_words.extend(host_order_words(keys.columns[i], o, widths[i],
                                              kpairs[i]))
            bound_words.extend(
                host_order_words(self.bounds.columns[i], o, widths[i],
                                 bpairs[i]))
        pid = np.zeros(n, dtype=np.int32)
        for j in range(self.bounds.row_count):
            gt = np.zeros(n, dtype=bool)
            eq = np.ones(n, dtype=bool)
            for rw, bw in zip(row_words, bound_words):
                bj = bw[j]
                gt = gt | (eq & (rw > bj))
                eq = eq & (rw == bj)
            pid += gt.astype(np.int32)
        return pid

    def desc(self):
        ks = ", ".join(s.expr.sql() for s in self.specs)
        return f"RangePartitioning({ks}, {self.num_partitions})"
