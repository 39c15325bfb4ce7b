"""Batch-level device kernels.

Key TPU-first decisions:
- ``compact_batch`` implements filtering as a stable sort of the row
  positions on the keep mask + gather — dynamic-shape-free, so the same
  compiled program serves every batch; the resulting row COUNT stays on
  the device.
  (cuDF's apply_boolean_mask materializes a shorter column; XLA wants the
  static shape kept and the logical length tracked separately.)
- ``concat_batches`` re-packs several padded batches into one bigger padded
  bucket with a single jit'ed copy per (shapes, bucket) signature.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import (DeferredCount, DeviceColumn,
                                              bucket_rows, rc_traceable,
                                              sum_counts)


def _jx():
    from spark_rapids_tpu.columnar.column import _jnp
    return _jnp()


#: deferred-concat padding guard (ADVICE r5): above this summed padded
#: input footprint, ``concat_batches`` forces the counts (one batched
#: sync) and sizes the output from live rows instead of next-pow2 of the
#: summed padded buckets
CONCAT_FORCE_SYNC_BYTES = 64 << 20




def _col_sig(c: DeviceColumn) -> Tuple:
    return (str(c.data.dtype), tuple(c.data.shape), c.lengths is not None,
            c.elem_valid is not None)


def gather_batch(batch: ColumnarBatch, idx, row_count: int,
                 idx_valid=None) -> ColumnarBatch:
    """Gathers rows by index (device gather-map application; reference:
    cuDF Table.gather via JoinGatherer).  ``idx`` may exceed row bounds for
    padding positions; callers pass ``idx_valid`` to invalidate those rows.
    Dictionary columns gather their code planes (encoding survives);
    RLE columns are run-shaped and materialize first."""
    from spark_rapids_tpu.columnar.encoding import (materialize_rle_batch,
                                                    rewrap_like)
    batch = materialize_rle_batch(batch)
    jnp = _jx()
    out = []
    n = idx.shape[0]
    safe = jnp.clip(idx, 0, batch.bucket - 1)
    for c in batch.columns:
        data = jnp.take(c.data, safe, axis=0)
        valid = jnp.take(c.validity, safe, axis=0)
        if idx_valid is not None:
            valid = valid & idx_valid
        lengths = None if c.lengths is None else jnp.take(c.lengths, safe, axis=0)
        ev = None if c.elem_valid is None else jnp.take(c.elem_valid, safe,
                                                        axis=0)
        out.append(rewrap_like(c, data, valid, row_count, lengths, ev))
    return ColumnarBatch(out, row_count, batch.names)


#: rows of one block of ``prefix_sum``
_PREFIX_BLOCK = 1024


def prefix_sum(x, jnp):
    """Inclusive prefix sum along axis 0, as ``jnp.cumsum`` gives it (a
    bool counts as an integer).  A long 1-D array is summed in blocks:
    within rows of ``_PREFIX_BLOCK``, then over the row totals.

    The form follows what XLA:TPU builds quickly, measured for v5e with
    ``scripts/tpu_rehearsal.py``'s method: one long 64-bit reduce-window
    is slow (``jnp.cumsum`` of int64[2^19] 75 s, of float64[2^20] 233 s,
    of float64[1024] 125 s), the blocked integer ``cumsum`` is under 2 s
    from 2^12 to 2^22 rows, and for floats only the ``associative_scan``
    is quick (2-3 s; on integers it takes 100 s)."""
    import jax
    if x.dtype == bool:
        x = x.astype(np.int64)
    if x.ndim != 1:
        return jnp.cumsum(x, axis=0)

    def within(a, axis):
        if jnp.issubdtype(a.dtype, jnp.inexact):
            return jax.lax.associative_scan(jnp.add, a, axis=axis)
        return jnp.cumsum(a, axis=axis)

    n = x.shape[0]
    if n <= _PREFIX_BLOCK or n % _PREFIX_BLOCK:
        return within(x, 0)
    rows = within(x.reshape(n // _PREFIX_BLOCK, _PREFIX_BLOCK), 1)
    totals = rows[:, -1]
    before = prefix_sum(totals, jnp) - totals
    return (rows + before[:, None]).reshape(n)


def expand_positions(offsets, out_bucket: int, jnp):
    """For every ``r`` in ``[0, out_bucket)`` the count of ``offsets[i] <=
    r``, less one, as int32: the source row of output position ``r`` when
    row ``i`` expands to the positions from ``offsets[i]`` up to
    ``offsets[i + 1]``.  ``offsets`` is non-decreasing and starts at 0, so
    this is ``searchsorted(offsets, arange(out_bucket), "right") - 1``
    without the search: the queries are an iota, and a histogram of the
    offsets, summed, counts the same thing with no loop and no gather (a
    per-row binary search was 48% of a TPC-DS q3's device time, PERF.md
    section 6, PR 29).  Offsets at or past ``out_bucket`` count for no
    position; they may exceed 32 bits and are sent out of range before
    they are narrowed."""
    idx = jnp.minimum(offsets, out_bucket).astype(np.int32)
    hist = jnp.zeros(out_bucket, dtype=np.int32).at[idx].add(
        1, mode="drop", indices_are_sorted=True)
    return prefix_sum(hist, jnp) - 1


def compaction_perm(keep, jnp):
    """int32 permutation that moves kept rows to the front, stable."""
    from spark_rapids_tpu.ops.sort_ops import lex_sort_perm
    return lex_sort_perm([~keep], keep.shape[0], jnp)


def take_front_planes(arrs, perm, cnt, out_bucket: int, jnp):
    """Traceable gather of ``[(data, valid, lengths, elem_valid)]`` by the
    first ``out_bucket`` positions of ``perm`` (a ``compaction_perm``: the
    ``cnt`` kept rows first), validity cleared past ``cnt``.  A gathered
    row is what costs, so ``out_bucket`` is the bucket of what was kept
    wherever the caller knows it (``exec/fused.py``)."""
    front = perm[:out_bucket]
    live = jnp.arange(out_bucket) < cnt

    def move(plane):
        return None if plane is None else jnp.take(plane, front, axis=0)

    return [(move(d), move(v) & live, move(ln), move(ev))
            for d, v, ln, ev in arrs]


def compact_planes(arrs, keep, jnp):
    """Traceable compaction of ``[(data, valid, lengths, elem_valid)]`` by
    the ``keep`` mask: kept rows first (stable), validity cleared past the
    kept count.  Returns (planes, count)."""
    cnt = jnp.sum(keep)
    return take_front_planes(arrs, compaction_perm(keep, jnp), cnt,
                             keep.shape[0], jnp), cnt


def compact_batch(batch: ColumnarBatch, keep,
                  kind: str = "batch.compact") -> ColumnarBatch:
    """Moves kept rows to the front (stable), returns batch with new count.
    ``kind`` names the program for the stage compiler's counters and the
    device trace.
    Dictionary code planes compact like any int plane (the encoding
    survives — late materialization); RLE materializes first.

    No host sync: the count stays deferred on device.  Only the drop flag
    and the row position are sorted, packed into one 32-bit key
    (``compaction_perm``); every plane then moves by the permutation.  A
    sort that carries the planes as operands takes the TPU compiler
    minutes to build (``ops/sort_ops.py``); whether the gather form costs
    run time on the chip is not measured.
    """
    from spark_rapids_tpu.columnar.encoding import materialize_rle_batch
    batch = materialize_rle_batch(batch)
    jnp = _jx()
    key = ("compact", tuple(_col_sig(c) for c in batch.columns))
    def build():
        def run(arrs, keep):
            return compact_planes(arrs, keep, jnp)

        return run
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    fn = get_or_build(kind, key, build)
    arrs = [(c.data, c.validity, c.lengths, c.elem_valid)
            for c in batch.columns]
    outs, cnt = fn(arrs, keep)
    # count stays on device: chained kernels consume it sync-free
    row_count = DeferredCount(cnt)
    from spark_rapids_tpu.columnar.encoding import rewrap_like
    cols = [rewrap_like(c, d, v, row_count, ln, ne)
            for (d, v, ln, ne), c in zip(outs, batch.columns)]
    return ColumnarBatch(cols, row_count, batch.names)


def shrink_batch(batch: ColumnarBatch) -> ColumnarBatch:
    """Re-buckets a batch whose logical rows are far fewer than its bucket
    (e.g. aggregate output, post-filter shuffle input) by slicing every
    plane to the next power of two >= row_count.  Forces the deferred count
    (one sync) — call only at materialization boundaries (shuffle write,
    spill) where the count is needed anyway."""
    n = int(batch.row_count)
    target = bucket_rows(max(n, 1))
    if not batch.columns or target >= batch.bucket:
        return batch
    from spark_rapids_tpu.columnar.encoding import (materialize_rle_batch,
                                                    rewrap_like)
    batch = materialize_rle_batch(batch)
    cols = []
    for c in batch.columns:
        cols.append(rewrap_like(
            c, c.data[:target], c.validity[:target], n,
            None if c.lengths is None else c.lengths[:target],
            None if c.elem_valid is None else c.elem_valid[:target]))
    return ColumnarBatch(cols, n, batch.names)


def slice_batch(batch: ColumnarBatch, start: int, length: int) -> ColumnarBatch:
    """Logical slice via gather (static shapes preserved)."""
    jnp = _jx()
    idx = jnp.arange(batch.bucket) + start
    valid_rows = jnp.arange(batch.bucket) < length
    return gather_batch(batch, idx, length, idx_valid=valid_rows)


def take_front(batch: ColumnarBatch, n) -> ColumnarBatch:
    """First n rows (limit); no data movement, just count + validity mask.
    ``n`` may itself be deferred/a device scalar (limit budget carried on
    device across batches — no per-batch sync)."""
    jnp = _jx()
    from spark_rapids_tpu.columnar.encoding import (materialize_rle_batch,
                                                    rewrap_like)
    batch = materialize_rle_batch(batch)
    rc = batch.row_count
    n_deferred = isinstance(n, DeferredCount) or not isinstance(n, int)
    if n_deferred or (isinstance(rc, DeferredCount) and not rc.is_forced):
        from spark_rapids_tpu.columnar.column import rc_traceable
        n_t = jnp.minimum(jnp.asarray(rc_traceable(n)),
                          jnp.asarray(rc_traceable(rc)))
        n = DeferredCount(n_t)
    else:
        n = min(int(n), int(rc))
        n_t = n
    keep = jnp.arange(batch.bucket) < n_t
    cols = [rewrap_like(c, c.data, c.validity & keep, n, c.lengths,
                        c.elem_valid)
            for c in batch.columns]
    return ColumnarBatch(cols, n, batch.names)


def _committed_device(b: ColumnarBatch):
    """The single device a batch's planes are committed to, or None for
    uncommitted/empty batches."""
    for c in b.columns:
        devices = getattr(c.data, "devices", None)
        if callable(devices):
            try:
                ds = list(devices())
            except Exception:  # noqa: BLE001 - best-effort placement probe
                return None
            if len(ds) == 1:
                return ds[0]
    return None


def _align_batch_devices(batches: Sequence[ColumnarBatch]
                         ) -> Sequence[ColumnarBatch]:
    """Moves batches committed to DIFFERENT devices onto one device
    before they meet in a single program (jax refuses cross-device
    inputs).  Mesh execution makes this real: a shard-local pipeline
    keeps each partition's batches on its own device, but partition
    merges (coalesced AQE reads above a host-staged exchange fed by
    mesh shards, out-of-core agg merges) legitimately combine shards —
    that transfer rides ICI on real hardware."""
    devs = {id(d): d for d in (_committed_device(b) for b in batches)
            if d is not None}
    if len(devs) <= 1:
        return batches
    import jax
    from spark_rapids_tpu.columnar.encoding import materialize_batch
    target = next(iter(devs.values()))

    moved_counts: dict = {}

    def move_count(rc):
        # unforced deferred counts are 0-d arrays committed to the
        # batch's device — they meet in the concat's size math too.
        # Memoized by identity: a batch and its columns SHARE one count
        # object (ColumnarBatch invariant) and must keep sharing it.
        if isinstance(rc, DeferredCount) and not rc.is_forced:
            if id(rc) not in moved_counts:
                moved_counts[id(rc)] = DeferredCount(
                    jax.device_put(rc.traceable(), target))
            return moved_counts[id(rc)]
        return rc

    def put(x):
        return None if x is None else jax.device_put(x, target)

    out = []
    for b in batches:
        dev = _committed_device(b)
        if dev is None or dev is target:
            out.append(b)
            continue
        # decode encoded columns BEFORE moving: an RLE column's planes
        # are run-space (rebuilding them as row planes corrupts rows),
        # and a dictionary column's value planes are shared + committed
        # to the SOURCE device — moving only the codes would hand the
        # next program cross-device inputs, the exact failure this
        # helper exists to prevent
        b = materialize_batch(b, site="device-align")
        cols = []
        for c in b.columns:
            cols.append(DeviceColumn(
                put(c.data), put(c.validity),
                move_count(c.row_count), c.data_type,
                put(c.lengths), put(getattr(c, "elem_valid", None))))
        out.append(ColumnarBatch(cols, move_count(b.row_count), b.names))
    return out


def concat_batches(batches: Sequence[ColumnarBatch]) -> ColumnarBatch:
    """Concatenates device batches into one padded batch (coalesce).

    reference: GpuCoalesceBatches/ConcatAndConsumeAll use cudf concat; here
    one jitted scatter per (input shapes) signature.
    """
    batches = list(batches)
    if len(batches) > 1:
        # drop known-empty batches without forcing deferred counts
        kept = [b for b in batches
                if isinstance(b.row_count, DeferredCount) or b.row_count > 0]
        batches = kept or batches[:1]
    if len(batches) == 1:
        return batches[0]
    batches = _align_batch_devices(batches)
    # dictionary code planes concat like int planes when every input
    # shares the fingerprint; mismatched positions decode first
    from spark_rapids_tpu.columnar.encoding import (align_batches,
                                                    rewrap_like)
    batches = align_batches(batches, site="concat")
    jnp = _jx()
    deferred_in = any(
        isinstance(b.row_count, DeferredCount) and not b.row_count.is_forced
        for b in batches)
    if deferred_in and \
            sum(b.nbytes() for b in batches) > CONCAT_FORCE_SYNC_BYTES:
        # padding guard: sizing by next-pow2 of SUMMED padded buckets can
        # allocate far past the live rows (every input carries its own
        # pow2 padding; mostly-filtered batches are nearly all padding).
        # Past this footprint one batched count sync is cheaper than the
        # OOM risk — force the counts, size from the REAL total below,
        # and drop each oversized input's padding first
        from spark_rapids_tpu.columnar.column import force_counts
        force_counts([b.row_count for b in batches])
        batches = [shrink_batch(b) for b in batches]
        deferred_in = False
    if deferred_in:
        # deferred inputs: size by the (static) bucket sum — a host sync
        # per concat costs a host round trip; the scatter kernel masks by
        # traced counts either way, so a roomier bucket only pads
        from spark_rapids_tpu.columnar.column import rc_traceable as _rt
        out_bucket = bucket_rows(sum(b.bucket for b in batches))
        tot = jnp.asarray(_rt(batches[0].row_count), dtype=np.int64)
        for b in batches[1:]:
            tot = tot + jnp.asarray(_rt(b.row_count), dtype=np.int64)
        total = DeferredCount(tot)
    else:
        total = sum_counts([b.row_count for b in batches])
        out_bucket = bucket_rows(total)
    ncols = batches[0].num_columns
    # per-column max string/array width across inputs
    widths = []
    for ci in range(ncols):
        w = 0
        for b in batches:
            c = b.columns[ci]
            if c.lengths is not None:
                w = max(w, c.data.shape[1])
        widths.append(w)
    key = ("concat", out_bucket,
           tuple(tuple(_col_sig(c) for c in b.columns) for b in batches))
    def build():
        def run(all_arrs, counts_arr):
            offsets = jnp.cumsum(counts_arr) - counts_arr
            outs = []
            for ci in range(ncols):
                tgt_rows = out_bucket
                acc_d = None
                for bi in range(len(all_arrs)):
                    d, v, ln, ev = all_arrs[bi][ci]
                    w = widths[ci]
                    if ln is not None and d.shape[1] < w:
                        d = jnp.pad(d, ((0, 0), (0, w - d.shape[1])))
                        if ev is not None:
                            ev = jnp.pad(ev,
                                         ((0, 0), (0, w - ev.shape[1])))
                    rowpos = jnp.arange(d.shape[0])
                    valid_rows = rowpos < counts_arr[bi]
                    # padding rows scatter out of range -> dropped
                    dest = jnp.where(valid_rows, rowpos + offsets[bi], tgt_rows)
                    if acc_d is None:
                        shape = (tgt_rows,) + d.shape[1:] if ln is None else \
                            (tgt_rows, w)
                        acc_d = jnp.zeros(shape, dtype=d.dtype)
                        acc_v = jnp.zeros(tgt_rows, dtype=bool)
                        acc_l = None if ln is None else \
                            jnp.zeros(tgt_rows, dtype=np.int32)
                        acc_e = None if ev is None else \
                            jnp.zeros((tgt_rows, w), dtype=bool)
                    acc_d = acc_d.at[dest].set(d, mode="drop")
                    acc_v = acc_v.at[dest].set(v & valid_rows, mode="drop")
                    if acc_l is not None:
                        acc_l = acc_l.at[dest].set(ln, mode="drop")
                    if acc_e is not None:
                        acc_e = acc_e.at[dest].set(ev, mode="drop")
                outs.append((acc_d, acc_v, acc_l, acc_e))
            return outs

        return run
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    fn = get_or_build("batch.concat", key, build)
    counts_arr = jnp.stack([jnp.asarray(rc_traceable(b.row_count),
                                        dtype=np.int64) for b in batches])
    all_arrs = [[(c.data, c.validity, c.lengths, c.elem_valid)
                 for c in b.columns] for b in batches]
    outs = fn(all_arrs, counts_arr)
    cols = []
    for (d, v, ln, ev), proto in zip(outs, batches[0].columns):
        cols.append(rewrap_like(proto, d, v, total, ln, ev))
    return ColumnarBatch(cols, total, batches[0].names)
