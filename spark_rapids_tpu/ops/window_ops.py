"""Device window kernels: one fused sort + segmented-scan program per spec.

Reference: the cuDF rolling/scan aggregations behind GpuWindowExpression
(GpuWindowExpression.scala maps frames to RollingAggregation/ScanAggregation)
and the batched algorithms in window/GpuRunningWindowExec.scala etc.

TPU-first design: the whole spec group — sort by (partition, order) keys,
partition/peer boundary detection, and EVERY window column — is one jitted
XLA program over static shapes:

- running (unbounded-preceding) aggregates: ``cumsum`` / segmented
  ``associative_scan`` re-based at partition starts; RANGE frames gather the
  running value at each row's last peer (Spark's default frame includes
  peers of the current row).
- whole-partition aggregates: ``segment_*`` reductions broadcast back.
- bounded ROWS frames: sum/count/mean via prefix-array gathers
  (``c[hi] - c[lo-1]``); min/max via an unrolled gather over the (small,
  static) frame width — the exec tags wide frames back to CPU.
- ranking: row_number/rank/dense_rank from partition/peer first positions;
  lag/lead are bounds-checked gathers.

Window column specs (``funcs``) are tuples:
  ("row_number",) | ("rank",) | ("dense_rank",) | ("ntile", n)
  ("offset", value_ordinal, signed_row_offset)           # lag/lead
  ("agg", kind, value_ordinal, frame_kind, lo, hi, count_valid_only)
     kind in sum|count|min|max|mean; lo/hi are row/peer offsets or None
     (unbounded); frame_kind "rows"|"range" ("range" only with lo=None and
     hi in (0, None) — Spark's default frames)
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.ops.batch_ops import prefix_sum

# widest bounded ROWS frame lowered to the unrolled min/max gather
MAX_UNROLLED_FRAME = 256


def _jx():
    from spark_rapids_tpu.columnar.column import _jnp
    return _jnp()




def _col_sig(c: DeviceColumn) -> Tuple:
    return (str(c.data.dtype), tuple(c.data.shape), c.lengths is not None)


def _seg_scan(vals, boundary, combine, jnp):
    """Segmented inclusive scan: restarts ``combine`` at boundary rows."""
    import jax

    def op(a, b):
        av, af = a
        bv, bf = b
        return jnp.where(bf, bv, combine(av, bv)), af | bf

    out, _ = jax.lax.associative_scan(op, (vals, boundary))
    return out


def _identity_for(kind: str, dtype, jnp):
    if kind == "min":
        if jnp.issubdtype(dtype, jnp.inexact):
            return jnp.asarray(np.inf, dtype)
        return jnp.asarray(jnp.iinfo(dtype).max, dtype)
    if jnp.issubdtype(dtype, jnp.inexact):
        return jnp.asarray(-np.inf, dtype)
    return jnp.asarray(jnp.iinfo(dtype).min, dtype)


def compute_windows(batch: ColumnarBatch, num_payload: int, num_pkeys: int,
                    order_specs: Sequence[Tuple[int, bool, bool]],
                    funcs: Sequence[Tuple],
                    out_dtypes: Optional[Sequence[T.DataType]] = None,
                    ) -> ColumnarBatch:
    """``batch`` columns = payload ++ partition keys ++ order keys ++ value
    inputs; returns sorted payload ++ one column per func.  ``order_specs``
    are (ordinal, ascending, nulls_first) into the batch."""
    import jax
    jnp = _jx()
    from spark_rapids_tpu.ops.sort_ops import (SortOrder, _order_words,
                                               lex_sort_perm)
    from spark_rapids_tpu.ops.agg_ops import (_masked_group_words,
                                              _take_columns)
    bucket = batch.bucket
    funcs = tuple(tuple(f) for f in funcs)
    key = ("window", tuple(_col_sig(c) for c in batch.columns), num_payload,
           num_pkeys, tuple(order_specs), funcs)
    pk_range = range(num_payload, num_payload + num_pkeys)

    def build():
        dtypes = [c.data_type for c in batch.columns]
        orders = [SortOrder(i, True, True) for i in pk_range] + \
            [SortOrder(o, a, nf) for o, a, nf in order_specs]

        def run(arrs, row_count):
            cols = [DeviceColumn(d, v, bucket, dtypes[i], ln)
                    for i, (d, v, ln) in enumerate(arrs)]
            rowpos = jnp.arange(bucket, dtype=np.int64)
            inrow = rowpos < row_count
            # ---- sort by partition keys then order keys, padding last ----
            words = [~inrow]
            for o in orders:
                words.extend(_order_words(cols[o.ordinal], o, jnp))
            scols = _take_columns(
                cols, lex_sort_perm(words, bucket, jnp), bucket, jnp)
            # ---- partition / peer boundaries ----
            def boundaries(idxs):
                b = jnp.zeros(bucket, dtype=bool).at[0].set(True)
                for i in idxs:
                    for w in _masked_group_words(scols[i], jnp):
                        diff = w[1:] != w[:-1] if w.ndim == 1 else \
                            jnp.any(w[1:] != w[:-1], axis=-1)
                        b = b.at[1:].max(diff)
                return b | (rowpos == row_count)

            seg_b = boundaries(list(pk_range))
            peer_b = boundaries(list(pk_range) +
                                [o for o, _, _ in order_specs])
            seg = prefix_sum(seg_b.astype(np.int64), jnp) - 1
            # first/last row position of each row's partition / peer group
            def first_last(bnd):
                gid = prefix_sum(bnd.astype(np.int64), jnp) - 1
                fp = jax.ops.segment_min(rowpos, gid, num_segments=bucket)
                lp = jax.ops.segment_max(jnp.where(inrow, rowpos, -1), gid,
                                         num_segments=bucket)
                return jnp.take(fp, gid), jnp.take(lp, gid)

            sfp, slp = first_last(seg_b)
            pfp, plp = first_last(peer_b)
            slp = jnp.maximum(slp, sfp)    # all-padding tail safety
            plp = jnp.maximum(plp, pfp)
            outs = []
            for f in funcs:
                outs.append(_one_func(f, scols, jnp, rowpos, inrow, seg,
                                      sfp, slp, pfp, plp, bucket, row_count))
            payload = [(c.data, c.validity, c.lengths)
                       for c in scols[:num_payload]]
            return payload, outs

        return run
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    fn = get_or_build("window.frame", key, build)
    from spark_rapids_tpu.columnar.column import rc_traceable
    arrs = [(c.data, c.validity, c.lengths) for c in batch.columns]
    payload, outs = fn(arrs, rc_traceable(batch.row_count))
    cols = []
    for (d, v, ln), proto in zip(payload, batch.columns[:num_payload]):
        cols.append(DeviceColumn(d, v, batch.row_count, proto.data_type, ln))
    for i, ((d, v, ln), f) in enumerate(zip(outs, funcs)):
        dt = out_dtypes[i] if out_dtypes is not None else None
        if dt is not None and ln is None and dt.np_dtype is not None and \
                d.dtype != np.dtype(dt.np_dtype):
            d = d.astype(dt.np_dtype)
        cols.append(DeviceColumn(d, v, batch.row_count, dt, ln))
    return ColumnarBatch(cols, batch.row_count, None)


def _one_func(f, scols, jnp, rowpos, inrow, seg, sfp, slp, pfp, plp,
              bucket, row_count):
    """One window output column -> (data, valid, lengths)."""
    kind = f[0]
    if kind == "row_number":
        return ((rowpos - sfp + 1).astype(np.int32), inrow, None)
    if kind == "rank":
        return ((pfp - sfp + 1).astype(np.int32), inrow, None)
    if kind == "dense_rank":
        # segment-rebased count of peer-group starts
        peer_start = (rowpos == pfp).astype(np.int64)
        c = prefix_sum(peer_start, jnp)
        dense = c - jnp.take(c, sfp) + 1
        return (dense.astype(np.int32), inrow, None)
    if kind == "ntile":
        n = f[1]
        cnt = slp - sfp + 1
        pos = rowpos - sfp
        base, rem = cnt // n, cnt % n
        # first `rem` tiles get base+1 rows
        big = rem * (base + 1)
        tile = jnp.where(pos < big, pos // jnp.maximum(base + 1, 1),
                         rem + (pos - big) // jnp.maximum(base, 1))
        return ((tile + 1).astype(np.int32), inrow, None)
    if kind == "offset":
        _, vo, off, dflt = f
        c = scols[vo]
        idx = rowpos + off
        ok = (idx >= sfp) & (idx <= slp) & inrow
        safe = jnp.clip(idx, 0, bucket - 1)
        d = jnp.take(c.data, safe, axis=0)
        v = jnp.take(c.validity, safe, axis=0) & ok
        ln = None if c.lengths is None else jnp.take(c.lengths, safe, axis=0)
        if dflt is not None:     # scalar default for out-of-partition rows
            d = jnp.where(ok, d, jnp.asarray(dflt, dtype=d.dtype))
            v = v | (~ok & inrow)
        return (d, v, ln)
    if kind == "agg":
        _, agg, vo, fkind, lo, hi, cvo = f
        c = scols[vo]
        present = c.validity & inrow
        # frame end positions per row (row offsets, clamped to partition)
        if fkind == "range":
            if lo is not None:
                raise NotImplementedError("bounded RANGE start")
            lo_pos = sfp
            hi_pos = slp if hi is None else plp      # peers of current row
        else:
            lo_pos = sfp if lo is None else jnp.maximum(rowpos + lo, sfp)
            hi_pos = slp if hi is None else jnp.minimum(rowpos + hi, slp)
        empty = hi_pos < lo_pos
        if agg in ("sum", "count", "mean"):
            if agg == "count" and not cvo:
                src = inrow
            else:
                src = present

            def win(csum, zrow):
                at_hi = jnp.take(csum, jnp.clip(hi_pos, 0, bucket - 1),
                                 axis=0)
                lo_c = jnp.clip(lo_pos, 0, bucket - 1)
                at_lo = jnp.take(csum, lo_c, axis=0) - \
                    jnp.take(zrow, lo_c, axis=0)
                return at_hi - at_lo

            n_ = prefix_sum(src.astype(np.int64), jnp)
            cnt = win(n_, src.astype(np.int64))
            cnt = jnp.where(empty, 0, cnt)
            if agg == "count":
                return (cnt.astype(np.int64), inrow, None)
            x = c.data
            is_float = jnp.issubdtype(x.dtype, jnp.inexact)
            if is_float:
                # the prefix-sum difference trick NaN/inf-poisons: one NaN
                # (or inf: inf - inf = NaN) anywhere in the batch corrupts
                # every LATER window, across segment boundaries.  Sum the
                # finite values only and recover IEEE results from exact
                # integer occurrence counters per window.
                isn = jnp.isnan(x)
                isp = present & (x == np.inf)
                ism = present & (x == -np.inf)
                nan_i = (present & isn).astype(np.int64)
                z = jnp.where(present & ~isn & ~isp & ~ism, x,
                              jnp.zeros_like(x))
            else:
                z = jnp.where(present, x, jnp.zeros_like(x))
            cs = prefix_sum(z, jnp)
            s = win(cs, z)
            s = jnp.where(empty | (cnt == 0), jnp.zeros_like(s), s)
            if is_float:
                nan_w = win(prefix_sum(nan_i, jnp), nan_i) > 0
                p_i = isp.astype(np.int64)
                m_i = ism.astype(np.int64)
                p_w = win(prefix_sum(p_i, jnp), p_i) > 0
                m_w = win(prefix_sum(m_i, jnp), m_i) > 0
                s = jnp.where(nan_w | (p_w & m_w),
                              jnp.asarray(np.nan, s.dtype),
                              jnp.where(p_w, jnp.asarray(np.inf, s.dtype),
                                        jnp.where(m_w,
                                                  jnp.asarray(-np.inf,
                                                              s.dtype), s)))
            ok = inrow & (cnt > 0)
            if agg == "sum":
                return (s, ok, None)
            mean = s / jnp.where(cnt > 0, cnt, 1).astype(s.dtype)
            return (mean, ok, None)
        if agg in ("min", "max"):
            # Spark NaN-greatest float semantics: min skips NaN (NaN only
            # when the frame has no real value); max is NaN when any NaN
            # is present.  NaN must not ride jnp.minimum/maximum (both
            # propagate it unconditionally).
            ident = _identity_for(agg, c.data.dtype, jnp)
            is_float = jnp.issubdtype(c.data.dtype, jnp.inexact)
            if is_float:
                isn = jnp.isnan(c.data)
                pres_val = present & ~isn      # contributes a real value
                # aux indicator: min -> "any real value"; max -> "any NaN"
                pres_aux = pres_val if agg == "min" else (present & isn)
                nanv = jnp.asarray(np.nan, c.data.dtype)
            else:
                pres_val = present
                pres_aux = None
            z = jnp.where(pres_val, c.data, ident)
            op = jnp.minimum if agg == "min" else jnp.maximum

            def patch(d, aux):
                if not is_float:
                    return d
                if agg == "min":
                    return jnp.where(aux, d, nanv)
                return jnp.where(aux, nanv, d)

            bounded = lo is not None and hi is not None and fkind == "rows"
            if bounded:
                acc = jnp.full(bucket, ident, dtype=c.data.dtype)
                got = jnp.zeros(bucket, dtype=bool)
                got_aux = jnp.zeros(bucket, dtype=bool)
                for off in range(lo, hi + 1):
                    idx = rowpos + off
                    ok_i = (idx >= lo_pos) & (idx <= hi_pos)
                    safe = jnp.clip(idx, 0, bucket - 1)
                    val = jnp.take(z, safe, axis=0)
                    pv = jnp.take(pres_val, safe, axis=0) & ok_i
                    acc = jnp.where(pv, op(acc, val), acc)
                    got = got | (jnp.take(present, safe, axis=0) & ok_i)
                    if is_float:
                        got_aux = got_aux | \
                            (jnp.take(pres_aux, safe, axis=0) & ok_i)
                return (patch(acc, got_aux), got & inrow, None)
            seg_b_here = rowpos == sfp
            if lo is None and (hi is None or fkind == "range" or hi == 0):
                run_f = _seg_scan(z, seg_b_here, op, jnp)
                have_f = _seg_scan(present.astype(np.int32), seg_b_here,
                                   jnp.add, jnp) > 0
                aux_f = None if not is_float else _seg_scan(
                    pres_aux.astype(np.int32), seg_b_here, jnp.add, jnp) > 0
                if hi is None:       # whole partition
                    pos = slp
                else:
                    pos = plp if fkind == "range" else rowpos
                d = jnp.take(run_f, pos, axis=0)
                v = jnp.take(have_f, pos, axis=0)
                if is_float:
                    d = patch(d, jnp.take(aux_f, pos, axis=0))
                return (d, v & inrow, None)
            if hi is None and lo == 0 and fkind == "rows":
                # current-to-unbounded: reversed segmented scan
                z_r = z[::-1]
                # boundary in reversed domain = last row of each partition
                b_r = (rowpos == slp)[::-1]
                run_r = _seg_scan(z_r, b_r, op, jnp)[::-1]
                have_r = _seg_scan(present[::-1].astype(np.int32), b_r,
                                   jnp.add, jnp)[::-1] > 0
                d = run_r
                if is_float:
                    aux_r = _seg_scan(pres_aux[::-1].astype(np.int32), b_r,
                                      jnp.add, jnp)[::-1] > 0
                    d = patch(d, aux_r)
                return (d, have_r & inrow, None)
            raise NotImplementedError(f"min/max frame {fkind} {lo} {hi}")
        raise NotImplementedError(f"window agg {agg}")
    raise NotImplementedError(f"window func {kind}")
