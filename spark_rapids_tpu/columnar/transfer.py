"""Packed host<->device batch transfer.

Every host<->device transfer has a fixed cost (DMA set-up, a dispatch)
that dwarfs the per-byte cost of a typical batch column, so a batch is
shipped as ONE buffer per element
width instead of two transfers (data + validity) per column:

- all 8-byte planes (int64/float64/decimal limbs)  -> one int64 buffer
- all 4-byte planes (int32/float32/date32/lengths) -> one int32 buffer
- all 2-byte planes (int16)                        -> one int16 buffer
- all 1-byte planes (uint8 string bytes, bool)     -> one uint8 buffer

Width-grouping matters because same-width ``bitcast_convert_type`` is free
(metadata-only) while cross-width bitcasts reshape the physical layout and
are slow on TPU.  All-valid validity planes are never transferred at all; a
per-bucket cached ones-mask is shared on device.

Reference analog: JCudfSerialization packs a whole table into one host
buffer for the same reason (per-transfer overhead), see
sql-plugin/src/main/java/com/nvidia/spark/rapids/GpuColumnVector.java and
RapidsShuffleInternalManagerBase.scala's serialized-table path.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.aux import transitions as TR
from spark_rapids_tpu.aux.tracing import span
from spark_rapids_tpu.columnar.column import (DeviceColumn, HostColumn,
                                              _jnp, assemble_host_column,
                                              bucket_rows,
                                              is_device_array_type)

# canonical transport dtype per element width
_CANON = {8: np.dtype(np.int64), 4: np.dtype(np.int32),
          2: np.dtype(np.int16), 1: np.dtype(np.uint8)}


class _Plane:
    """One host numpy plane destined for the device, with its target dtype."""

    __slots__ = ("array", "target_dtype", "to_bool")

    def __init__(self, array: np.ndarray, target_dtype=None, to_bool=False):
        self.array = array
        self.target_dtype = target_dtype or array.dtype
        self.to_bool = to_bool


def _host_planes(col: HostColumn, bucket: int):
    """Decomposes one host column into (planes, descriptor, extra).

    descriptor: (kind, has_validity) where kind identifies how to
    reassemble: 'scalar' | 'dec128' | 'string' | 'array' | 'dict' |
    'rle'.  ``extra`` carries the Dictionary (dict) or the runs bucket
    (rle); None otherwise.
    """
    from spark_rapids_tpu.columnar import encoding as ENC
    n = len(col)
    dt = col.data_type
    enc = ENC.classify_host_column(col)
    if enc is not None and enc[0] == "dict":
        # encoded upload: ship ONLY the narrow code plane (+ validity);
        # the dictionary's value planes upload once per fingerprint
        _k, dic, codes, valid_np = enc
        planes = []
        all_valid = bool(valid_np.all())
        if not all_valid:
            v = np.zeros(bucket, dtype=np.uint8)
            v[:n] = valid_np
            planes.append(_Plane(v, to_bool=True))
        cbuf = np.zeros(bucket, dtype=codes.dtype)
        cbuf[:n] = codes
        planes.append(_Plane(cbuf, target_dtype=np.dtype(np.int32)))
        return planes, ("dict", not all_valid), dic
    if enc is not None and enc[0] == "rle":
        _k, rvals, rvalid, rends = enc
        n_runs = len(rvals)
        rbucket = ENC.bucket_rows(max(n_runs, 1), minimum=8)
        planes = []
        rv = np.zeros(rbucket, dtype=np.uint8)
        rv[:n_runs] = rvalid
        planes.append(_Plane(rv, to_bool=True))
        data = np.zeros(rbucket, dtype=rvals.dtype)
        data[:n_runs] = rvals
        planes.append(_Plane(data))
        ends = np.full(rbucket, np.iinfo(np.int32).max, dtype=np.int32)
        ends[:n_runs] = rends
        planes.append(_Plane(ends))
        return planes, ("rle", True), bucket
    if col.is_dict_encoded:
        # rejected dictionary (oversized / null values / unsupported
        # value type) or encoding disabled: decode ONCE here so the
        # plane accessors below don't each re-decode
        col = HostColumn(ENC.host_decoded(col.arrow), dt)
    valid_np = col.validity_np()
    all_valid = bool(valid_np.all())
    planes: List[Optional[_Plane]] = []

    def pad1(a, dtype=None):
        dtype = dtype or a.dtype
        out = np.zeros(bucket, dtype=dtype)
        out[:n] = a
        return out

    if not all_valid:
        v = np.zeros(bucket, dtype=np.uint8)
        v[:n] = valid_np
        planes.append(_Plane(v, to_bool=True))

    if is_device_array_type(dt):
        vals, lens, ev = col.list_np()
        w = vals.shape[1]
        data = np.zeros((bucket, w), dtype=vals.dtype)
        data[:n] = vals
        lengths = pad1(lens, np.int32)
        elem_valid = np.zeros((bucket, w), dtype=np.uint8)
        elem_valid[:n] = ev
        planes += [_Plane(data), _Plane(lengths),
                   _Plane(elem_valid, to_bool=True)]
        return planes, ("array", not all_valid), None
    if isinstance(dt, (T.StringType, T.BinaryType)):
        chars, lens = col.string_np()
        data = np.zeros((bucket, chars.shape[1]), dtype=np.uint8)
        data[:n] = chars
        planes += [_Plane(data), _Plane(pad1(lens, np.int32))]
        return planes, ("string", not all_valid), None
    raw = col.data_np()
    if isinstance(dt, T.DecimalType) and dt.is_decimal128:
        data = np.zeros((bucket, 2), dtype=np.int64)
        data[:n] = raw
        planes.append(_Plane(data))
        return planes, ("dec128", not all_valid), None
    data = np.zeros((bucket,) + raw.shape[1:], dtype=raw.dtype)
    data[:n] = raw
    planes.append(_Plane(data))
    return planes, ("scalar", not all_valid), None


def upload_host_batch(hb, bucket: Optional[int] = None):
    """HostColumnarBatch -> ColumnarBatch in <=4 device transfers total."""
    import jax
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    jnp = _jnp()
    n = hb.row_count
    b = bucket or bucket_rows(n)
    if not hb.columns:
        return ColumnarBatch([], n, hb.names)

    all_planes: List[_Plane] = []
    descs = []
    extras = []
    for col in hb.columns:
        planes, desc, extra = _host_planes(col, b)
        descs.append((desc, len(planes)))
        extras.append(extra)
        all_planes += planes

    # group plane payloads by element width
    groups: Dict[int, List[_Plane]] = {}
    for p in all_planes:
        groups.setdefault(p.array.dtype.itemsize, []).append(p)

    host_bufs = {}
    layout = []  # per-plane: (width, elem_offset, shape, str(target), to_bool)
    offsets = {w: 0 for w in groups}
    for p in all_planes:
        w = p.array.dtype.itemsize
        layout.append((w, offsets[w], p.array.shape,
                       str(p.target_dtype), p.to_bool))
        offsets[w] += p.array.size
    for w, ps in groups.items():
        canon = _CANON[w]
        buf = np.empty(sum(p.array.size for p in ps), dtype=canon)
        o = 0
        for p in ps:
            flat = np.ascontiguousarray(p.array).view(canon).ravel()
            buf[o:o + flat.size] = flat
            o += flat.size
        host_bufs[w] = buf

    n_allvalid = sum(1 for (d, _np) in descs if not d[1])
    widths = tuple(sorted(host_bufs))
    # row count is a TRACED argument: one compiled program serves every
    # batch sharing this (layout, bucket) — remainder batches with odd row
    # counts must not trigger recompiles
    key = (tuple(layout), widths,
           tuple(host_bufs[w].size for w in widths), b, n_allvalid > 0)
    def build():
        def unpack(bufs, rows):
            byw = dict(zip(widths, bufs))
            outs = []
            for (w, off, shape, tgt, to_bool) in layout:
                size = int(np.prod(shape))
                seg = byw[w][off:off + size].reshape(shape)
                tdt = np.dtype(tgt)
                if to_bool or tdt == np.bool_:
                    seg = seg.astype(jnp.bool_)
                elif tdt != seg.dtype:
                    if tdt.itemsize == seg.dtype.itemsize:
                        seg = jax.lax.bitcast_convert_type(seg, tdt)
                    else:
                        # width change (narrow dictionary codes -> the
                        # device's int32): a real convert, fused in-jit
                        seg = seg.astype(tdt)
                outs.append(seg)
            # shared all-valid row mask, created on device (no transfer);
            # one per batch so buffer lifetimes stay independent (spill may
            # delete any batch's arrays)
            ones = (jnp.arange(b) < rows) if n_allvalid else None
            return outs, ones

        return unpack
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    fn = get_or_build("transfer.unpack", key, build)

    # the ONE H2D boundary of the upload path: packed width-grouped
    # buffers cross in a single device_put dispatch (ledger: duration is
    # dispatch wall — the copy may complete asynchronously)
    with span("xfer.h2d", site="upload"):
        t0_h2d = time.perf_counter()
        dev_bufs = jax.device_put([host_bufs[w] for w in widths])
        dt_h2d = time.perf_counter() - t0_h2d
    TR.record_h2d(sum(buf.nbytes for buf in host_bufs.values()), dt_h2d,
                  kinds=",".join(sorted({d[0] for d, _ in descs})),
                  planes=len(all_planes))
    planes_dev, ones = fn(dev_bufs, n)

    cols = []
    i = 0
    n_dict = n_rle = enc_bytes = avoided = 0
    for col, ((kind, has_valid), np_count), extra in zip(hb.columns, descs,
                                                         extras):
        dt = col.data_type
        take = planes_dev[i:i + np_count]
        i += np_count
        validity = take[0] if has_valid else ones
        rest = take[1:] if has_valid else take
        if kind == "array":
            data, lengths, elem_valid = rest
            cols.append(DeviceColumn(data, validity, n, dt,
                                     lengths=lengths, elem_valid=elem_valid))
        elif kind == "string":
            data, lengths = rest
            cols.append(DeviceColumn(data, validity, n, dt, lengths=lengths))
        elif kind == "dict":
            from spark_rapids_tpu.columnar.encoding import DictionaryColumn
            dic = extra
            cols.append(DictionaryColumn(rest[0], validity, n, dt,
                                         None, None, dictionary=dic))
            n_dict += 1
            codes_bytes = 4 * b
            enc_bytes += codes_bytes
            vals_bytes = sum(buf.size for buf in dic.values.buffers()
                             if buf is not None)
            per_row = vals_bytes / max(dic.size, 1)
            avoided += int(max(0, n * per_row + 4 * b - codes_bytes))
        elif kind == "rle":
            from spark_rapids_tpu.columnar.encoding import RleColumn
            data, ends = rest
            cols.append(RleColumn(data, validity, n, dt, None, None,
                                  run_ends=ends, logical_bucket=b))
            n_rle += 1
            run_bytes = int(data.size * data.dtype.itemsize +
                            ends.size * 4 + validity.size)
            enc_bytes += run_bytes
            avoided += max(0, b * int(np.dtype(dt.np_dtype).itemsize)
                           - run_bytes)
        else:
            cols.append(DeviceColumn(rest[0], validity, n, dt))
    if n_dict or n_rle:
        from spark_rapids_tpu.columnar import encoding as ENC
        ENC.note_encoded_upload(n_dict, n_rle, enc_bytes, avoided)
    return ColumnarBatch(cols, n, hb.names)


# ---------------------------------------------------------------------------
# device -> host (packed download)
# ---------------------------------------------------------------------------

#: speculative row cap for single-round-trip downloads when the row count
#: is still deferred: planes are sliced to this many rows and the count is
#: packed INTO the buffer, so the fetch itself resolves whether it was
#: enough (results above the cap pay one extra round trip — rare: results
#: a user collects are small).  Default only — the D2H boundary exec
#: carries its conf value per instance (per-query conf travels with the
#: plan, not this module)
_DL_SPEC_ROWS = 8192


def _plane_words(seg, jnp):
    """Flat uint32 words carrying ``seg``'s device bits.

    TPU-safe: the X64 rewriter (f64 emulated as an f32 double-double pair,
    i64 as u32 pairs) implements NO 64-bit ``bitcast_convert_type``, so
    64-bit planes decompose arithmetically — f64 ships as its dd (hi, lo)
    f32 pair, which IS the exact device value (ops/f64bits.py docstring);
    i64/u64 split into (lo32, hi32) by shift/mask.  Sub-word types pack
    little-endian into u32 lanes."""
    import jax
    from spark_rapids_tpu.ops.f64bits import f64_bitcast_ok
    if seg.dtype == jnp.bool_:
        seg = seg.astype(np.uint8)
    flat = seg.ravel()
    dt = np.dtype(str(flat.dtype))
    if dt == np.float64:
        if f64_bitcast_ok():
            # real binary64 backend (CPU tests): exact bits, then split
            flat = jax.lax.bitcast_convert_type(flat, np.uint64)
            dt = np.dtype(np.uint64)
        else:
            hi = flat.astype(np.float32)
            lo = (flat - hi.astype(np.float64)).astype(np.float32)
            uh = jax.lax.bitcast_convert_type(hi, np.uint32)
            ul = jax.lax.bitcast_convert_type(lo, np.uint32)
            return jnp.stack([uh, ul], axis=-1).ravel()
    if dt.itemsize == 8:
        u = flat if dt == np.uint64 else flat.astype(np.uint64)
        lo = (u & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        hi = (u >> np.uint64(32)).astype(np.uint32)
        return jnp.stack([lo, hi], axis=-1).ravel()
    ut = {1: np.uint8, 2: np.uint16, 4: np.uint32}[dt.itemsize]
    if dt != ut:
        flat = jax.lax.bitcast_convert_type(flat, ut)
    if dt.itemsize == 4:
        return flat
    per = 4 // dt.itemsize
    pad = (-int(flat.shape[0])) % per
    if pad:
        flat = jnp.pad(flat, (0, pad))
    w = flat.astype(np.uint32).reshape(-1, per)
    shifts = jnp.arange(per, dtype=np.uint32) * np.uint32(8 * dt.itemsize)
    # lanes occupy disjoint bits, so a sum is a bitwise-or
    return jnp.sum(w << shifts[None, :], axis=1, dtype=np.uint32)


def _plane_nwords(shape, dtype) -> int:
    n = int(np.prod(shape))
    isz = 1 if str(dtype) == "bool" else np.dtype(str(dtype)).itemsize
    if isz == 8:
        return 2 * n
    if isz == 4:
        return n
    per = 4 // isz
    return -(-n // per)


def _pack_planes(planes, shrink: int, rc_traced):
    """One jitted program: slice every plane to ``shrink`` rows, encode to
    uint32 words, append the row count — ONE buffer, hence ONE host
    round trip.  ``jax.device_get`` on a list costs one blocking fetch PER
    array; a single packed buffer makes the whole download one sync."""
    jnp = _jnp()
    sig = tuple((str(p.dtype), tuple(p.shape)) for p in planes)
    key = (sig, shrink)
    def build():
        def run(ps, rc):
            chunks = [_plane_words(p[:shrink], jnp) for p in ps]
            u = jnp.asarray(rc, dtype=np.int64).astype(np.uint64).reshape(1)
            chunks.append(jnp.concatenate([
                (u & np.uint64(0xFFFFFFFF)).astype(np.uint32),
                (u >> np.uint64(32)).astype(np.uint32)]))
            return jnp.concatenate(chunks)

        return run
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    fn = get_or_build("transfer.pack", key, build)
    return fn(planes, rc_traced)


def _unpack_buffer(buf: np.ndarray, planes, shrink: int):
    """Host-side mirror of _pack_planes: decodes the uint32 word stream
    back into per-plane numpy arrays (little-endian lanes)."""
    out = []
    o = 0
    for p in planes:
        shape = (min(shrink, int(p.shape[0])),) + tuple(p.shape[1:])
        sdt = str(p.dtype)
        nw = _plane_nwords(shape, sdt)
        w = buf[o:o + nw]
        o += nw
        n = int(np.prod(shape))
        if sdt == "float64":
            from spark_rapids_tpu.ops.f64bits import f64_bitcast_ok
            pair = w.reshape(-1, 2)
            if f64_bitcast_ok():
                v = pair[:, 0].astype(np.uint64) | \
                    (pair[:, 1].astype(np.uint64) << np.uint64(32))
                arr = v.view(np.float64)
            else:
                hi = np.ascontiguousarray(pair[:, 0]).view(np.float32)
                lo = np.ascontiguousarray(pair[:, 1]).view(np.float32)
                arr = hi.astype(np.float64) + lo.astype(np.float64)
        elif sdt in ("int64", "uint64"):
            pair = w.reshape(-1, 2).astype(np.uint64)
            v = pair[:, 0] | (pair[:, 1] << np.uint64(32))
            arr = v.view(np.int64) if sdt == "int64" else v
        elif sdt == "bool":
            arr = w.view(np.uint8)[:n].astype(bool)
        else:
            dt = np.dtype(sdt)
            arr = w.view(dt)[:n] if dt.itemsize < 4 else \
                w.view(dt)
        out.append(arr[:n].reshape(shape))
    rc = int(buf[o] | (np.uint64(buf[o + 1]) << np.uint64(32)))
    return out, rc


def download_host_batch(cb, spec_rows=None) -> "object":
    """ColumnarBatch -> HostColumnarBatch in ONE device round trip.

    All planes are packed into a single uint8 buffer on device (cheap — a
    fused slice+bitcast+concat program) together with the row count, then
    fetched with one blocking call.  When the row count is deferred and the
    bucket is large, planes are speculatively sliced to ``spec_rows``
    (default ``_DL_SPEC_ROWS``; the D2H boundary exec passes its
    convert-time conf value) rows; the packed count reveals whether that
    was enough, and only an oversized result pays a second (exactly-sized)
    round trip.
    """
    from spark_rapids_tpu.columnar import encoding as ENC
    from spark_rapids_tpu.columnar.batch import HostColumnarBatch
    from spark_rapids_tpu.columnar.column import DeferredCount, rc_traceable
    if not cb.columns:
        return HostColumnarBatch([], int(cb.row_count), cb.names)
    # RLE planes are runs-shaped (per-column buckets would break the
    # shared slice-to-shrink program); dictionary columns download their
    # CODE planes — a D2H reduction — and reassemble against the
    # host-resident dictionary values below
    cb = ENC.materialize_rle_batch(cb, site="download")

    planes = []   # device arrays, in fixed role order per column
    descs = []    # (data_type, [role names present], Dictionary|None)
    for c in cb.columns:
        dt = c.data_type
        dic = c.dictionary if isinstance(c, ENC.DictionaryColumn) else None
        col_planes = []
        if not isinstance(dt, T.NullType):
            col_planes.append(("data", c.data))
        col_planes.append(("valid", c.validity))
        if c.lengths is not None:
            col_planes.append(("lens", c.lengths))
        if c.elem_valid is not None:
            col_planes.append(("ev", c.elem_valid))
        descs.append((dt, [r for r, _ in col_planes], dic))
        planes.extend(p for _, p in col_planes)

    rc = cb.row_count
    bucket = int(cb.columns[0].data.shape[0])
    deferred = isinstance(rc, DeferredCount) and not rc.is_forced
    if deferred:
        if spec_rows is None:   # explicit sentinel: small conf values
            spec_rows = _DL_SPEC_ROWS             # must stick
        shrink = min(bucket, bucket_rows(spec_rows, minimum=8))
    else:
        # known count: slice exactly (never ship padding rows)
        shrink = min(bucket, bucket_rows(max(int(rc), 1), minimum=8))
    # the ONE D2H boundary of the download path: all planes cross as a
    # single packed buffer per round trip (ledger: duration is the true
    # blocking fetch — counted as a transition, not a sync)
    with span("xfer.d2h", site="download"):
        t0_d2h = time.perf_counter()
        buf = np.asarray(_pack_planes(planes, shrink, rc_traceable(rc)))
        dt_d2h = time.perf_counter() - t0_d2h
    TR.record_d2h(buf.nbytes, dt_d2h, site="download", planes=len(planes))
    fetched, n = _unpack_buffer(buf, planes, shrink)
    if deferred:
        rc._val = n   # the fetch resolved the count: cache it
    if n > shrink:
        # speculation miss: fetch again at the exact size (one more trip)
        shrink = min(bucket, bucket_rows(max(n, 1), minimum=8))
        with span("xfer.d2h", site="download-miss"):
            t0_d2h = time.perf_counter()
            buf = np.asarray(_pack_planes(planes, shrink, n))
            dt_d2h = time.perf_counter() - t0_d2h
        TR.record_d2h(buf.nbytes, dt_d2h, site="download-miss",
                      planes=len(planes))
        fetched, _ = _unpack_buffer(buf, planes, shrink)

    cols = []
    i = 0
    for (dt, roles, dic) in descs:
        byrole = {}
        for r in roles:
            byrole[r] = fetched[i]
            i += 1
        raw = byrole.get("data")
        if dic is not None:
            cols.append(ENC.reassemble_host_dictionary(
                raw[:n], byrole["valid"][:n], dic, dt))
            continue
        cols.append(assemble_host_column(
            dt, n,
            None if raw is None else raw[:n],
            byrole["valid"][:n],
            None if "lens" not in byrole else byrole["lens"][:n],
            None if "ev" not in byrole else byrole["ev"][:n]))
    return HostColumnarBatch(cols, n, cb.names)
