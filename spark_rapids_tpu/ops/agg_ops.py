"""Segmented groupby kernels.

Reference: GpuAggregateExec.scala AggHelper (:175) pipelines cuDF hash
groupby.  TPU-first redesign: XLA has no hash tables but excels at sort +
segmented reductions — groupby = stable sort by keys (ops/sort_ops), detect
segment boundaries, ``jax.ops.segment_*`` with ``num_segments = bucket``
(static shape; group count is the only host sync).  The whole
sort+boundaries+N-reductions pipeline is one jitted program per
(shapes, spec) signature.

Reduction kinds (update & merge lower to the same set):
  sum, count, min, max, first, last, first_valid, last_valid, mean, m2,
  m2_cnt/m2_mean/m2_m2 (joint Chan-merge of variance partials)
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.ops.batch_ops import prefix_sum


def _jx():
    from spark_rapids_tpu.columnar.column import _jnp
    return _jnp()




def _col_sig(c: DeviceColumn) -> Tuple:
    return (str(c.data.dtype), tuple(c.data.shape), c.lengths is not None)


def _masked_group_words(col: DeviceColumn, jnp) -> List:
    """Words where equal-group rows compare equal: nulls grouped together
    (rank word) with data masked to 0 so null garbage doesn't split groups."""
    from spark_rapids_tpu.ops.sort_ops import sortable_words
    words = [col.validity.astype(np.int8)]
    for w in sortable_words(col, jnp):
        if w.ndim == 1:
            words.append(jnp.where(col.validity, w, jnp.zeros_like(w)))
        else:
            words.append(jnp.where(col.validity[:, None], w,
                                   jnp.zeros_like(w)))
    return words


def _segment_reduce(kind: str, x, valid, seg, inrow, bucket, jnp,
                    count_valid_only=True):
    """One reduction -> (data[bucket], valid[bucket]) per segment id."""
    import jax
    present = valid & inrow
    any_valid = jax.ops.segment_max(present.astype(np.int32), seg,
                                    num_segments=bucket) > 0
    if kind == "count":
        src = present if count_valid_only else inrow
        cnt = jax.ops.segment_sum(src.astype(np.int64), seg,
                                  num_segments=bucket)
        return cnt, jnp.ones(bucket, dtype=bool)
    if kind == "sum":
        if getattr(x, "ndim", 1) == 2:
            # decimal128 (hi, lo) limbs: mod-2^128 two's-complement sum.
            # 4x 32-bit limbs segment-summed in int64 lanes (limb < 2^32,
            # rows < 2^31 -> no lane overflow), then ONE carry
            # normalization; wrapped negatives add correctly mod 2^128.
            from spark_rapids_tpu.expressions.decimal_math import (
                _normalize, join128, split128)
            limbs = split128(x[:, 0], x[:, 1], jnp)
            limbs = [jnp.where(present, l, jnp.zeros_like(l))
                     for l in limbs]
            sums = [jax.ops.segment_sum(l, seg, num_segments=bucket)
                    for l in limbs]
            norm, _carry = _normalize(sums, jnp)
            hi_s, lo_s = join128(norm, jnp)
            return jnp.stack([hi_s, lo_s], axis=1), any_valid
        z = jnp.where(present, x, jnp.zeros_like(x))
        return jax.ops.segment_sum(z, seg, num_segments=bucket), any_valid
    if kind in ("min", "max"):
        if jnp.issubdtype(x.dtype, jnp.inexact):
            # Spark: NaN > every double.  min skips NaN (unless the group
            # is all-NaN); max yields NaN when any present.  Explicit, not
            # left to backend NaN propagation (XLA CPU and TPU differ).
            ident = jnp.asarray(np.inf if kind == "min" else -np.inf, x.dtype)
            nanrow = present & jnp.isnan(x)
            z = jnp.where(present & ~jnp.isnan(x), x, ident)
            f = jax.ops.segment_min if kind == "min" else jax.ops.segment_max
            red = f(z, seg, num_segments=bucket)
            has_nan = jax.ops.segment_max(nanrow.astype(np.int32), seg,
                                          num_segments=bucket) > 0
            if kind == "max":
                red = jnp.where(has_nan, jnp.asarray(np.nan, x.dtype), red)
            else:
                has_num = jax.ops.segment_max(
                    (present & ~jnp.isnan(x)).astype(np.int32), seg,
                    num_segments=bucket) > 0
                red = jnp.where(has_nan & ~has_num,
                                jnp.asarray(np.nan, x.dtype), red)
            return red, any_valid
        info = jnp.iinfo(x.dtype)
        ident = jnp.asarray(info.max if kind == "min" else info.min,
                            x.dtype)
        z = jnp.where(present, x, ident)
        f = jax.ops.segment_min if kind == "min" else jax.ops.segment_max
        return f(z, seg, num_segments=bucket), any_valid
    if kind in ("first", "last", "first_valid", "last_valid"):
        want_valid = kind.endswith("_valid")
        cond = present if want_valid else inrow
        pos = jnp.arange(x.shape[0], dtype=np.int64)
        if kind.startswith("first"):
            p = jnp.where(cond, pos, x.shape[0])
            idx = jax.ops.segment_min(p, seg, num_segments=bucket)
            found = idx < x.shape[0]
        else:
            p = jnp.where(cond, pos, -1)
            idx = jax.ops.segment_max(p, seg, num_segments=bucket)
            found = idx >= 0
        safe = jnp.clip(idx, 0, x.shape[0] - 1)
        data = jnp.take(x, safe, axis=0)
        v = found & jnp.take(valid, safe, axis=0)
        return data, v
    if kind == "mean":
        z = jnp.where(present, x, jnp.zeros_like(x))
        s = jax.ops.segment_sum(z, seg, num_segments=bucket)
        n = jax.ops.segment_sum(present.astype(x.dtype), seg,
                                num_segments=bucket)
        return jnp.where(n > 0, s / jnp.where(n > 0, n, 1), 0.0), any_valid
    raise ValueError(f"unknown reduction kind {kind!r}")


def _lengths_reduce(kind, col, valid, seg, inrow, bucket, jnp):
    """first/last variants for string columns carry data+lengths."""
    import jax
    want_valid = kind.endswith("_valid")
    present = col.validity & inrow
    cond = present if want_valid else inrow
    pos = jnp.arange(col.data.shape[0], dtype=np.int64)
    if kind.startswith("first"):
        p = jnp.where(cond, pos, col.data.shape[0])
        idx = jax.ops.segment_min(p, seg, num_segments=bucket)
        found = idx < col.data.shape[0]
    else:
        p = jnp.where(cond, pos, -1)
        idx = jax.ops.segment_max(p, seg, num_segments=bucket)
        found = idx >= 0
    safe = jnp.clip(idx, 0, col.data.shape[0] - 1)
    data = jnp.take(col.data, safe, axis=0)
    lens = jnp.take(col.lengths, safe, axis=0)
    v = found & jnp.take(col.validity, safe, axis=0)
    return data, v, lens


_GLOBAL_OUT_BUCKET = 8


def _global_reduce(kind: str, x, valid, inrow, jnp, count_valid_only=True):
    """Whole-array reduction -> (scalar, scalar_valid).  The global-agg
    analog of _segment_reduce: plain jnp reductions instead of segment ops
    (a segment_* with num_segments=bucket is a scatter over the whole
    bucket; jnp.sum is one reduction)."""
    present = valid & inrow
    any_valid = jnp.any(present)
    if kind == "count":
        src = present if count_valid_only else inrow
        return jnp.sum(src.astype(np.int64)), jnp.asarray(True)
    if kind == "sum":
        if getattr(x, "ndim", 1) == 2:
            # decimal128 limbs: see _segment_reduce's 4x32-bit scheme
            from spark_rapids_tpu.expressions.decimal_math import (
                _normalize, join128, split128)
            limbs = split128(x[:, 0], x[:, 1], jnp)
            sums = [jnp.sum(jnp.where(present, l, jnp.zeros_like(l)))
                    for l in limbs]
            norm, _carry = _normalize(sums, jnp)
            hi_s, lo_s = join128(norm, jnp)
            return jnp.stack([hi_s, lo_s]), any_valid
        return jnp.sum(jnp.where(present, x, jnp.zeros_like(x))), any_valid
    if kind in ("min", "max"):
        if jnp.issubdtype(x.dtype, jnp.inexact):
            # Spark NaN-greatest semantics, explicit (see _segment_reduce)
            ident = jnp.asarray(np.inf if kind == "min" else -np.inf, x.dtype)
            nanrow = present & jnp.isnan(x)
            z = jnp.where(present & ~jnp.isnan(x), x, ident)
            red = jnp.min(z) if kind == "min" else jnp.max(z)
            has_nan = jnp.any(nanrow)
            if kind == "max":
                red = jnp.where(has_nan, jnp.asarray(np.nan, x.dtype), red)
            else:
                has_num = jnp.any(present & ~jnp.isnan(x))
                red = jnp.where(has_nan & ~has_num,
                                jnp.asarray(np.nan, x.dtype), red)
            return red, any_valid
        info = jnp.iinfo(x.dtype)
        ident = jnp.asarray(info.max if kind == "min" else info.min,
                            x.dtype)
        z = jnp.where(present, x, ident)
        return (jnp.min(z) if kind == "min" else jnp.max(z)), any_valid
    if kind in ("first", "last", "first_valid", "last_valid"):
        want_valid = kind.endswith("_valid")
        cond = present if want_valid else inrow
        n = x.shape[0]
        pos = jnp.arange(n, dtype=np.int64)
        if kind.startswith("first"):
            idx = jnp.min(jnp.where(cond, pos, n))
            found = idx < n
        else:
            idx = jnp.max(jnp.where(cond, pos, -1))
            found = idx >= 0
        safe = jnp.clip(idx, 0, n - 1)
        return x[safe], found & valid[safe]
    if kind == "mean":
        z = jnp.where(present, x, jnp.zeros_like(x))
        s = jnp.sum(z)
        cnt = jnp.sum(present.astype(x.dtype))
        return jnp.where(cnt > 0, s / jnp.where(cnt > 0, cnt, 1), 0.0), \
            any_valid
    raise ValueError(f"unknown reduction kind {kind!r}")


def _global_aggregate(batch: ColumnarBatch,
                      specs: Sequence[Tuple[int, str, bool, T.DataType]],
                      ) -> ColumnarBatch:
    """num_keys == 0: no sort, no segments; output planes are tiny
    (bucket 8) so downstream merge/final passes and the result download
    never touch input-sized buffers."""
    jnp = _jx()
    bucket = batch.bucket
    spec_key = tuple((o, k, cv, str(dt)) for o, k, cv, dt in specs)
    key = ("globalagg", tuple(_col_sig(c) for c in batch.columns), spec_key)
    def build():
        dtypes = [c.data_type for c in batch.columns]

        def run(arrs, row_count):
            cols = [DeviceColumn(d, v, bucket, dtypes[i], ln)
                    for i, (d, v, ln) in enumerate(arrs)]
            sel = jnp.arange(bucket, dtype=np.int32) < row_count
            return global_agg_trace(cols, sel, specs, jnp)

        return run
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    fn = get_or_build("agg.global", key, build)
    from spark_rapids_tpu.columnar.column import rc_traceable
    arrs = [(c.data, c.validity, c.lengths) for c in batch.columns]
    outs = fn(arrs, rc_traceable(batch.row_count))
    names = [f"a{j}" for j in range(len(specs))]
    cols = []
    for j, (d, v, ln) in enumerate(outs):
        dt = specs[j][3]
        if ln is None and dt.np_dtype is not None and \
                d.dtype != np.dtype(dt.np_dtype):
            d = d.astype(dt.np_dtype)
        cols.append(DeviceColumn(d, v, 1, dt, ln))
    return ColumnarBatch(cols, 1, names)


def global_agg_trace(cols, sel, specs, jnp):
    """Traceable global-agg update/merge pass over (cols, selection mask):
    returns [(data, valid, lengths)] 8-row planes, value in row 0.  Called
    by _global_aggregate and by the whole-stage fuser (exec/fused.py)."""
    inrow = sel

    def slot(val, ok, width=None):
        """scalar -> 8-row plane with the value at row 0."""
        if width is None:
            d = jnp.zeros(_GLOBAL_OUT_BUCKET, dtype=val.dtype).at[0].set(val)
        else:
            d = jnp.zeros((_GLOBAL_OUT_BUCKET, width),
                          dtype=val.dtype).at[0].set(val)
        v = jnp.zeros(_GLOBAL_OUT_BUCKET, dtype=bool).at[0].set(ok)
        return d, v

    outs = []
    i = 0
    while i < len(specs):
        o, kind, cvo, _dt = specs[i]
        c = cols[o]
        if kind == "m2_cnt":
            oc, om, o2 = specs[i][0], specs[i + 1][0], specs[i + 2][0]
            cnt_c, mean_c, m2_c = cols[oc], cols[om], cols[o2]
            pres = cnt_c.validity & inrow
            n_i = jnp.where(pres, cnt_c.data, 0.0)
            mu_i = jnp.where(pres, mean_c.data, 0.0)
            m2_i = jnp.where(pres, m2_c.data, 0.0)
            tot = jnp.sum(n_i)
            wsum = jnp.sum(n_i * mu_i)
            mu = jnp.where(tot > 0, wsum / jnp.where(tot > 0, tot, 1), 0.0)
            dev = mu_i - mu
            m2 = jnp.sum(m2_i + n_i * dev * dev)
            ok = jnp.asarray(True)
            for val in (tot, mu, m2):
                d, v = slot(val, ok)
                outs.append((d, v, None))
            i += 3
            continue
        if kind == "m2":
            x = c.data
            pres = c.validity & inrow
            z = jnp.where(pres, x, 0.0)
            cnt = jnp.sum(pres.astype(x.dtype))
            s = jnp.sum(z)
            mu = jnp.where(cnt > 0, s / jnp.where(cnt > 0, cnt, 1), 0.0)
            dctr = jnp.where(pres, x - mu, 0.0)
            d, v = slot(jnp.sum(dctr * dctr), jnp.asarray(True))
            outs.append((d, v, None))
            i += 1
            continue
        if c.lengths is not None and kind != "count":
            # first/last over strings: pick the row, carry lengths
            want_valid = kind.endswith("_valid")
            pres = c.validity & inrow
            cond = pres if want_valid else inrow
            nn = c.data.shape[0]
            pos = jnp.arange(nn, dtype=np.int64)
            if kind.startswith("first"):
                idx = jnp.min(jnp.where(cond, pos, nn))
                found = idx < nn
            else:
                idx = jnp.max(jnp.where(cond, pos, -1))
                found = idx >= 0
            safe = jnp.clip(idx, 0, nn - 1)
            d, v = slot(c.data[safe], found & c.validity[safe],
                        width=c.data.shape[1])
            ln = jnp.zeros(_GLOBAL_OUT_BUCKET,
                           dtype=c.lengths.dtype).at[0].set(c.lengths[safe])
            outs.append((d, v, ln))
        else:
            val, ok = _global_reduce(kind, c.data, c.validity, inrow, jnp,
                                     count_valid_only=cvo)
            # decimal128 sums return a (hi, lo) pair -> 2-wide plane
            width = val.shape[0] if getattr(val, "ndim", 0) == 1 else None
            d, v = slot(val, ok, width=width)
            outs.append((d, v, None))
        i += 1
    return outs


def segmented_aggregate(batch: ColumnarBatch, num_keys: int,
                        specs: Sequence[Tuple[int, str, bool, T.DataType]],
                        ) -> ColumnarBatch:
    """Groups ``batch`` by its first ``num_keys`` columns and reduces the
    remaining columns per ``specs``: (value_ordinal, kind, count_valid_only,
    out_dtype).  Returns keys+results, one row per group.

    The full pipeline (sort, boundaries, reductions) is one jit per
    signature; only the group count syncs to host.
    """
    jnp = _jx()
    from spark_rapids_tpu.ops.sort_ops import SortOrder, sortable_words
    if num_keys == 0:
        return _global_aggregate(batch, specs)
    bucket = batch.bucket
    spec_key = tuple((o, k, cv, str(dt)) for o, k, cv, dt in specs)
    key = ("segagg", tuple(_col_sig(c) for c in batch.columns), num_keys,
           spec_key)
    def build():
        # capture only scalars/types, never the batch (module-cache pinning)
        dtypes = [c.data_type for c in batch.columns]

        def run(arrs, row_count):
            cols = [DeviceColumn(d, v, bucket, dtypes[i], ln)
                    for i, (d, v, ln) in enumerate(arrs)]
            sel = jnp.arange(bucket, dtype=np.int32) < row_count
            return keyed_agg_trace(cols, sel, num_keys, specs, bucket, jnp)

        return run
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    fn = get_or_build("agg.segmented", key, build)
    from spark_rapids_tpu.columnar.column import DeferredCount, rc_traceable
    arrs = [(c.data, c.validity, c.lengths) for c in batch.columns]
    outs, ng = fn(arrs, rc_traceable(batch.row_count))
    n = DeferredCount(ng)      # group count stays on device
    names = (batch.names or [f"c{i}" for i in range(batch.num_columns)])
    out_names = names[:num_keys] + [f"a{j}" for j in range(len(specs))]
    cols = []
    for j, (d, v, ln) in enumerate(outs):
        if j < num_keys:
            dt = batch.columns[j].data_type
        else:
            dt = specs[j - num_keys][3]
            if ln is None and dt.np_dtype is not None and \
                    d.dtype != np.dtype(dt.np_dtype):
                d = d.astype(dt.np_dtype)
        cols.append(DeviceColumn(d, v, n, dt, ln))
    return ColumnarBatch(cols, n, out_names)


def _take_columns(cols, perm, bucket, jnp):
    """``cols`` with every plane moved by the row permutation."""
    return [DeviceColumn(
        jnp.take(c.data, perm, axis=0), jnp.take(c.validity, perm, axis=0),
        bucket, c.data_type,
        None if c.lengths is None else jnp.take(c.lengths, perm, axis=0))
        for c in cols]


def keyed_agg_trace(cols, sel, num_keys, specs, bucket, jnp):
    """Traceable keyed groupby pass over (cols, selection mask): sort by
    keys, detect segments, reduce.  Returns ([(data, valid, lengths)],
    num_groups).  Called by segmented_aggregate and the whole-stage fuser."""
    import jax
    from spark_rapids_tpu.ops.sort_ops import (SortOrder, _order_words,
                                               lex_sort_perm)
    orders = [SortOrder(i, True, True) for i in range(num_keys)]
    rowpos = jnp.arange(bucket, dtype=np.int32)
    inrow = sel
    row_count = jnp.sum(sel)  # selected rows sort to the front
    # ---- sort by keys (padding last): only the key words order; every
    # plane then moves by the permutation ----
    words = [~inrow]
    for o in orders:
        words.extend(_order_words(cols[o.ordinal], o, jnp))
    perm = lex_sort_perm(words, bucket, jnp)
    scols = _take_columns(cols, perm, bucket, jnp)
    inrow_s = jnp.take(inrow, perm, axis=0)  # still a prefix
    # ---- segment boundaries over masked key words ----
    boundary = jnp.zeros(bucket, dtype=bool).at[0].set(True)
    for kcol in scols[:num_keys]:
        for w in _masked_group_words(kcol, jnp):
            if w.ndim == 1:
                diff = w[1:] != w[:-1]
            else:
                diff = jnp.any(w[1:] != w[:-1], axis=-1)
            boundary = boundary.at[1:].max(diff)
    # first padding row opens its own (discarded) segment
    boundary = boundary | (rowpos == row_count)
    seg = prefix_sum(boundary.astype(np.int32), jnp) - 1
    num_groups = jnp.max(jnp.where(inrow_s, seg, -1)) + 1
    # ---- unique keys: value at each segment's first row ----
    outs = []
    first_pos = jax.ops.segment_min(
        jnp.where(inrow_s, rowpos.astype(np.int64), bucket), seg,
        num_segments=bucket)
    safe_first = jnp.clip(first_pos, 0, bucket - 1)
    gvalid = jnp.arange(bucket) < num_groups
    for kcol in scols[:num_keys]:
        d = jnp.take(kcol.data, safe_first, axis=0)
        v = jnp.take(kcol.validity, safe_first, axis=0) & gvalid
        ln = None if kcol.lengths is None else \
            jnp.take(kcol.lengths, safe_first, axis=0)
        outs.append((d, v, ln))
    # ---- reductions ----
    i = 0
    while i < len(specs):
        o, kind, cvo, _dt = specs[i]
        c = scols[o]
        if kind == "m2_cnt":
            # joint Chan merge over partial (cnt, mean, m2) triples
            oc, om, o2 = specs[i][0], specs[i + 1][0], specs[i + 2][0]
            cnt_c, mean_c, m2_c = scols[oc], scols[om], scols[o2]
            pres = cnt_c.validity & inrow_s
            n_i = jnp.where(pres, cnt_c.data, 0.0)
            mu_i = jnp.where(pres, mean_c.data, 0.0)
            m2_i = jnp.where(pres, m2_c.data, 0.0)
            tot = jax.ops.segment_sum(n_i, seg, num_segments=bucket)
            wsum = jax.ops.segment_sum(n_i * mu_i, seg,
                                       num_segments=bucket)
            mu = jnp.where(tot > 0, wsum / jnp.where(tot > 0, tot, 1),
                           0.0)
            dev = mu_i - jnp.take(mu, seg)
            m2 = jax.ops.segment_sum(m2_i + n_i * dev * dev, seg,
                                     num_segments=bucket)
            ok = jnp.ones(bucket, dtype=bool)
            outs.append((tot, ok, None))
            outs.append((mu, ok, None))
            outs.append((m2, ok, None))
            i += 3
            continue
        if kind == "m2":
            # update: needs this input's per-segment mean first
            x = c.data
            pres = c.validity & inrow_s
            z = jnp.where(pres, x, 0.0)
            n = jax.ops.segment_sum(pres.astype(x.dtype), seg,
                                    num_segments=bucket)
            s = jax.ops.segment_sum(z, seg, num_segments=bucket)
            mu = jnp.where(n > 0, s / jnp.where(n > 0, n, 1), 0.0)
            d = jnp.where(pres, x - jnp.take(mu, seg), 0.0)
            m2 = jax.ops.segment_sum(d * d, seg, num_segments=bucket)
            outs.append((m2, jnp.ones(bucket, dtype=bool), None))
            i += 1
            continue
        if c.lengths is not None and kind != "count":
            d, v, ln = _lengths_reduce(kind, c, c.validity, seg,
                                       inrow_s, bucket, jnp)
            outs.append((d, v, ln))
        else:
            d, v = _segment_reduce(kind, c.data, c.validity, seg,
                                   inrow_s, bucket, jnp,
                                   count_valid_only=cvo)
            outs.append((d, v, None))
        i += 1
    # mask group-slot padding in-trace (eager masking would cost one
    # dispatch per output column)
    gv = jnp.arange(bucket) < num_groups
    outs = [(d, v & gv, ln) for (d, v, ln) in outs]
    return outs, num_groups


# ---------------------------------------------------------------------------
# device collect_list / collect_set (reference: aggregateFunctions.scala
# collect ops over cuDF lists; TPU-first reformulation = stable sort by
# keys [+ value for sets], segment boundaries, scatter into a padded
# [group, max_len] plane)
# ---------------------------------------------------------------------------



def segmented_collect_many(batch: ColumnarBatch, num_keys: int,
                           slots):
    """Collects several value columns per group into device array
    columns: ``slots`` = [(value_ordinal, distinct)], returns one
    keys+array ColumnarBatch per slot, all sharing segmented_aggregate's
    group order.

    Null values are skipped (Spark collect semantics); ``distinct``
    dedupes by sorting (key, value) and keeping first occurrences — set
    ORDER is value-sorted, which Spark leaves unspecified.

    Sync discipline: ONE host fetch total for every slot's max group
    length (stacked — a fetch per slot would cost a host round trip
    each); group counts stay deferred."""
    phase1 = [_collect_phase1(batch, num_keys, o, d) for o, d in slots]
    maxws = np.asarray(_jx().stack([p[6] for p in phase1]))  # the one sync
    return [_collect_phase2(batch, num_keys, o, p, int(w))
            for (o, _d), p, w in zip(slots, phase1, maxws)]


def _collect_phase1(batch: ColumnarBatch, num_keys: int, value_ord: int,
                    distinct: bool):
    import jax
    from spark_rapids_tpu.columnar.column import rc_traceable
    from spark_rapids_tpu.ops.sort_ops import (SortOrder, _order_words,
                                               lex_sort_perm)
    jnp = _jx()
    bucket = batch.bucket
    sig = ("collect1", tuple(_col_sig(c) for c in batch.columns), num_keys,
           value_ord, distinct)
    def build():
        dtypes = [c.data_type for c in batch.columns]

        def phase1(arrs, row_count):
            cols = [DeviceColumn(d, v, bucket, dtypes[i], ln)
                    for i, (d, v, ln) in enumerate(arrs)]
            rowpos = jnp.arange(bucket, dtype=np.int32)
            inrow = rowpos < row_count
            orders = [SortOrder(i, True, True) for i in range(num_keys)]
            words = [~inrow]
            for o in orders:
                words.extend(_order_words(cols[o.ordinal], o, jnp))
            if distinct:
                words.extend(_order_words(
                    cols[value_ord], SortOrder(value_ord, True, True), jnp))
            perm = lex_sort_perm(words, bucket, jnp)
            scols = _take_columns(cols, perm, bucket, jnp)
            inrow_s = jnp.take(inrow, perm, axis=0)
            # group boundaries on KEY words only
            boundary = jnp.zeros(bucket, dtype=bool).at[0].set(True)
            for kcol in scols[:num_keys]:
                for w in _masked_group_words(kcol, jnp):
                    diff = (w[1:] != w[:-1]) if w.ndim == 1 else \
                        jnp.any(w[1:] != w[:-1], axis=-1)
                    boundary = boundary.at[1:].max(diff)
            boundary = boundary | (rowpos == row_count)
            seg = prefix_sum(boundary.astype(np.int32), jnp) - 1
            num_groups = jnp.max(jnp.where(inrow_s, seg, -1)) + 1
            sval = scols[value_ord]
            kept = inrow_s & sval.validity
            if distinct:
                first = boundary.copy()
                for w in _masked_group_words(sval, jnp):
                    diff = (w[1:] != w[:-1]) if w.ndim == 1 else \
                        jnp.any(w[1:] != w[:-1], axis=-1)
                    first = first.at[1:].max(diff)
                kept = kept & first
            # position within the group counting only kept rows
            ck = prefix_sum(kept.astype(np.int64), jnp)
            base = jax.ops.segment_min(
                jnp.where(inrow_s, ck - kept, 1 << 62), seg,
                num_segments=bucket)
            pos = ck - 1 - jnp.take(base, seg)
            lengths = jax.ops.segment_sum(kept.astype(np.int32), seg,
                                          num_segments=bucket)
            maxw = jnp.max(lengths)
            # group key rows (same rule as keyed_agg_trace)
            first_pos = jax.ops.segment_min(
                jnp.where(inrow_s, rowpos.astype(np.int64), bucket), seg,
                num_segments=bucket)
            key_outs = []
            safe_first = jnp.clip(first_pos, 0, bucket - 1)
            gvalid = jnp.arange(bucket) < num_groups
            for kcol in scols[:num_keys]:
                d = jnp.take(kcol.data, safe_first, axis=0)
                v = jnp.take(kcol.validity, safe_first, axis=0) & gvalid
                ln = None if kcol.lengths is None else \
                    jnp.take(kcol.lengths, safe_first, axis=0)
                key_outs.append((d, v, ln))
            return (sval.data, kept, seg, pos, lengths, num_groups, maxw,
                    key_outs)

        return phase1
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    fn = get_or_build("agg.collect_phase1", sig, build)
    arrs = [(c.data, c.validity, c.lengths) for c in batch.columns]
    return fn(arrs, rc_traceable(batch.row_count))


def _collect_phase2(batch: ColumnarBatch, num_keys: int, value_ord: int,
                    p1, maxw: int):
    from spark_rapids_tpu.columnar.column import (DeferredCount,
                                                  bucket_strlen)
    jnp = _jx()
    bucket = batch.bucket
    vcol = batch.columns[value_ord]
    (svals, kept, seg, pos, lengths, ng, _maxw_d, key_outs) = p1
    W = bucket_strlen(max(maxw, 1))
    sig2 = ("collect2", bucket, W, str(svals.dtype))
    def build():
        def phase2(svals, kept, seg, pos, lengths, ng):
            plane = jnp.zeros((bucket, W), dtype=svals.dtype)
            dest_g = jnp.where(kept, seg.astype(np.int64), bucket)
            dest_p = jnp.clip(pos, 0, W - 1)
            plane = plane.at[(dest_g, dest_p)].set(svals, mode="drop")
            ev = jnp.arange(W)[None, :] < lengths[:, None]
            gvalid = jnp.arange(bucket) < ng
            return plane, ev, gvalid

        return phase2
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    fn2 = get_or_build("agg.collect_phase2", sig2, build)
    plane, ev, gvalid = fn2(svals, kept, seg, pos, lengths, ng)
    n = DeferredCount(ng)
    arr_col = DeviceColumn(plane, gvalid, n,
                           T.ArrayType(vcol.data_type, contains_null=False),
                           lengths=lengths.astype(np.int32),
                           elem_valid=ev)
    cols = []
    names = (batch.names or [f"c{i}" for i in range(batch.num_columns)])
    for j, (d, v, ln) in enumerate(key_outs):
        cols.append(DeviceColumn(d, v, n, batch.columns[j].data_type, ln))
    cols.append(arr_col)
    return ColumnarBatch(cols, n, names[:num_keys] + ["collected"])
