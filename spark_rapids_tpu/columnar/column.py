"""Host and device column vectors.

Reference counterparts:
- ``GpuColumnVector.java`` (device column over cuDF ColumnVector, type
  mapping, batch<->Table) — here ``DeviceColumn`` over jax Arrays.
- ``RapidsHostColumnVector.java`` / ``RapidsHostColumnBuilder.java`` — here
  ``HostColumn`` over pyarrow Arrays (Arrow layout is the host/wire format,
  as JCudfSerialization's host layout is for the reference).

Design (TPU-first):
- A device column is (data, validity, row_count) where ``data``/``validity``
  are jax arrays whose leading dim is a *bucket* (next power of two >= rows,
  min 1024).  All kernels mask by validity and by ``iota < row_count``.
- Fixed-width types map 1:1 to a jax dtype.  float64 is kept f64 (XLA on TPU
  emulates; ops that are f64-hot are planner-tagged).  decimal64 is int64 data
  + scale in the DataType.  decimal128 is int64[bucket, 2] hi/lo limbs.
- Strings/binary: uint8[bucket, max_len] + int32 lengths.  max_len is padded
  to a power of two to bound compile cache size.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import numpy as np

from spark_rapids_tpu import types as T

MIN_ROW_BUCKET = 1024
MIN_STR_BUCKET = 8


def _next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def bucket_rows(n: int, minimum: int = MIN_ROW_BUCKET) -> int:
    """Padded leading-dim for ``n`` logical rows (static-shape discipline)."""
    return max(minimum, _next_pow2(n))


def bucket_strlen(n: int) -> int:
    return max(MIN_STR_BUCKET, _next_pow2(n))


#: the one floor for every shrink: an operator that sizes the batch it
#: hands on by a fetched count does so only for an input batch whose
#: bucket is above this, and never hands on a bucket under it.  A hash
#: join (``exec/joins.py``) sizes its pair table and output by the probe
#: batch's candidate total (sync site ``join-size``); a fused stage that
#: filters (``exec/fused.py``) sizes its output by the filter's live
#: count (sync site ``stage-size``).  A batch at or under the floor pays
#: no sync.  The floor is what keeps the shapes still: what a selective
#: operator keeps of a large batch lands in one bucket whatever its
#: parameters keep (a ladder that followed the rows down would compile
#: anew when a literal moves a count across an edge), and a program at
#: this size costs under a hundredth of one at a fact table's bucket.
#: Read on the chip for the smallest stage it engages (``date_dim``:
#: 73,049 rows in a 131,072-row bucket, of which a q3 keeps some 6,000
#: and a q55 some 30; PERF.md section 6, PR 36): sized,
#: ``store_scan_agg`` answers 3.83–3.85 queries/s where 3.59 stood and
#: ``served_streams`` 4.01–4.02 where 3.75–3.76 stood, so the stage has
#: no floor of its own
SIZED_MIN_BUCKET = 1 << 15


class DeferredCount:
    """A row count living on device until the host actually needs it.

    A host round trip stalls the dispatch queue (a scalar fetch is a
    device sync: the host waits for everything enqueued before it), so
    filters/aggregations keep their output row counts as 0-d
    device arrays.  Chained device kernels read ``traceable()`` (no sync);
    any host-side use (int conversion, comparisons, arithmetic) forces ONE
    cached sync.  The reference has no analog: cuDF kernels return counts
    synchronously because CUDA launch+sync latency is microseconds.
    """

    __slots__ = ("_dev", "_val")

    def __init__(self, dev, val=None):
        self._dev = dev
        self._val = val

    def traceable(self):
        """What device kernels should consume (0-d array; no sync)."""
        return self._dev if self._val is None else self._val

    @property
    def is_forced(self) -> bool:
        return self._val is not None

    def _force(self) -> int:
        if self._val is None:
            from spark_rapids_tpu.aux import transitions as TR
            self._val = TR.sync_int(self._dev, site="count-force")
        return self._val

    # device-side interop (jnp ops accept this without a sync)
    def __jax_array__(self):
        return _jnp().asarray(self.traceable())

    # host-side interop (forces the sync, once)
    def __int__(self):
        return self._force()

    def __index__(self):
        return self._force()

    def __bool__(self):
        return self._force() != 0

    def __hash__(self):
        return hash(self._force())

    def __repr__(self):
        return str(self._val) if self._val is not None else "<deferred>"

    @staticmethod
    def _v(o):
        return o._force() if isinstance(o, DeferredCount) else o

    def __eq__(self, o):
        if self is o:
            return True             # same deferred count: no sync needed
        return self._force() == DeferredCount._v(o)

    def __ne__(self, o):
        return not self.__eq__(o)

    def __lt__(self, o):
        return self._force() < DeferredCount._v(o)

    def __le__(self, o):
        return self._force() <= DeferredCount._v(o)

    def __gt__(self, o):
        return self._force() > DeferredCount._v(o)

    def __ge__(self, o):
        return self._force() >= DeferredCount._v(o)

    def __add__(self, o):
        return self._force() + DeferredCount._v(o)

    __radd__ = __add__

    def __sub__(self, o):
        return self._force() - DeferredCount._v(o)

    def __rsub__(self, o):
        return DeferredCount._v(o) - self._force()

    def __mul__(self, o):
        return self._force() * DeferredCount._v(o)

    __rmul__ = __mul__

    def __floordiv__(self, o):
        return self._force() // DeferredCount._v(o)

    def __truediv__(self, o):
        return self._force() / DeferredCount._v(o)

    def __rtruediv__(self, o):
        return DeferredCount._v(o) / self._force()

    def __mod__(self, o):
        return self._force() % DeferredCount._v(o)


def rc_traceable(rc):
    """Row count as a jit argument: device scalar if deferred (no sync)."""
    return rc.traceable() if isinstance(rc, DeferredCount) else rc


def known_empty(rc) -> bool:
    """True only when a row count is empty WITHOUT forcing a deferred
    count (forcing costs a host round trip per batch; callers treat
    "maybe non-empty" batches as live)."""
    if isinstance(rc, DeferredCount):
        return rc.is_forced and int(rc) == 0
    return int(rc) == 0


def fetch_stacked(arrs, site: str) -> list:
    """Small device arrays of one shape, fetched with ONE sync (stacked;
    one a device where they live on several: what a mesh's shards hold
    cannot be stacked by one program).  Host arrays in ``arrs``' order."""
    jnp = _jnp()
    from spark_rapids_tpu.aux import transitions as TR
    by_device: dict = {}
    for i, a in enumerate(arrs):
        where = frozenset(a.devices()) if hasattr(a, "devices") else None
        by_device.setdefault(where, []).append(i)
    out = [None] * len(arrs)
    for group in by_device.values():
        stacked = TR.fetch(jnp.stack([jnp.asarray(arrs[i]) for i in group]),
                           site=site)
        for i, v in zip(group, stacked):
            out[i] = v
    return out


def force_counts(rcs) -> None:
    """Forces many deferred counts with ONE device sync
    (:func:`fetch_stacked`).  Callers that need several batches' exact row
    counts (AQE partition sizing) must not pay a host round trip per
    batch."""
    pending = [rc for rc in rcs
               if isinstance(rc, DeferredCount) and not rc.is_forced]
    if not pending:
        return
    got = fetch_stacked([rc.traceable() for rc in pending],
                        site="count-force-batch")
    for rc, v in zip(pending, got):
        rc._val = int(v)


def learn_count(rc, value: int) -> None:
    """Tells a deferred count what the host has learned another way (an
    exchange's map batch: the sum of its fetched per-partition counts), so
    later readers of it neither sync nor stay blind."""
    if isinstance(rc, DeferredCount) and not rc.is_forced:
        rc._val = int(value)


def sum_counts(rcs) -> int:
    """Totals row counts with at most ONE device sync (batches already
    forced contribute host-side; the rest are summed on device first)."""
    jnp = _jnp()
    static = 0
    deferred = []
    for rc in rcs:
        if isinstance(rc, DeferredCount) and not rc.is_forced:
            deferred.append(rc.traceable())
        else:
            static += int(rc)
    if deferred:
        total = deferred[0]
        for d in deferred[1:]:
            total = total + d
        from spark_rapids_tpu.aux import transitions as TR
        static += TR.sync_int(total, site="count-sum")
    return static


_X64_READY = False


def _jnp():
    """jax.numpy with 64-bit types enforced.

    A SQL engine cannot live without int64/float64 (LongType, TimestampType,
    decimal limbs), so x64 mode is a hard requirement of the runtime — the
    reference equivalently requires 64-bit cuDF types throughout.
    """
    global _X64_READY
    import jax
    if not _X64_READY:
        jax.config.update("jax_enable_x64", True)
        _X64_READY = True
    return jax.numpy


def _ragged_indices(lens64: np.ndarray):
    """For per-row lengths, returns (row_idx, within) flat coordinates of
    every payload byte — shared by the rectangularize (scatter) and
    flatten (gather) directions."""
    total = int(lens64.sum())
    row_idx = np.repeat(np.arange(len(lens64), dtype=np.int64), lens64)
    head = np.repeat(np.cumsum(lens64) - lens64, lens64)
    within = np.arange(total, dtype=np.int64) - head
    return row_idx, within


def _validity_buffer(valid: np.ndarray):
    """(packed-bits arrow validity buffer or None, null_count)."""
    import pyarrow as pa
    valid = np.asarray(valid, dtype=bool)
    if valid.all():
        return None, 0
    return (pa.py_buffer(np.packbits(valid, bitorder="little").tobytes()),
            int((~valid).sum()))


def _decimal128_from_limbs(hi: np.ndarray, lo: np.ndarray, valid, dt):
    """Builds an arrow decimal128 array from int64 hi/lo limbs (vectorized)."""
    import pyarrow as pa
    n = len(lo)
    buf = np.empty((n, 2), dtype=np.int64)
    buf[:, 0] = lo  # little-endian: low limb first
    buf[:, 1] = hi
    vbuf, nulls = (None, 0) if valid is None else _validity_buffer(valid)
    return pa.Array.from_buffers(
        pa.decimal128(dt.precision, dt.scale), n,
        [vbuf, pa.py_buffer(buf.tobytes())], null_count=nulls)


def is_device_array_type(dt: T.DataType) -> bool:
    """Arrays of fixed-width scalars ride the device as a padded rectangular
    plane (data [bucket, max_elems] + lengths + element validity) — the same
    layout trick as strings.  Nested/string elements stay on the host tier."""
    if not isinstance(dt, T.ArrayType):
        return False
    e = dt.element_type
    return isinstance(e, (T.ByteType, T.ShortType, T.IntegerType, T.LongType,
                          T.FloatType, T.DoubleType, T.BooleanType,
                          T.DateType, T.TimestampType))


def _elem_np_dtype(elem: T.DataType):
    if isinstance(elem, T.DateType):
        return np.dtype(np.int32)
    if isinstance(elem, T.TimestampType):
        return np.dtype(np.int64)
    return elem.np_dtype


def _list_from_rectangular(vals: np.ndarray, lens: np.ndarray,
                           elem_valid: np.ndarray, valid: np.ndarray,
                           dt: T.ArrayType):
    """Builds an arrow ListArray from [n, w] values + lengths (vectorized)."""
    import pyarrow as pa
    n = len(lens)
    lens64 = np.where(valid, lens, 0).astype(np.int64)
    offsets = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(lens64, out=offsets[1:])
    if lens64.sum():
        row_idx, within = _ragged_indices(lens64)
        flat = np.ascontiguousarray(vals[row_idx, within])
        flat_valid = np.ascontiguousarray(elem_valid[row_idx, within])
    else:
        flat = np.zeros(0, dtype=vals.dtype)
        flat_valid = np.zeros(0, dtype=bool)
    elem_col = HostColumn.from_numpy(flat, flat_valid, dt.element_type)
    vbuf, nulls = _validity_buffer(valid)
    return pa.Array.from_buffers(
        pa.list_(T.to_arrow(dt.element_type)), n,
        [vbuf, pa.py_buffer(offsets.tobytes())],
        null_count=nulls, children=[elem_col.arrow])


def _binary_from_rectangular(chars: np.ndarray, lens: np.ndarray,
                             valid: np.ndarray):
    """Builds an arrow binary array from uint8[n, w] + lengths (vectorized)."""
    import pyarrow as pa
    n = len(lens)
    lens64 = np.where(valid, lens, 0).astype(np.int64)
    offsets = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(lens64, out=offsets[1:])
    if lens64.sum():
        row_idx, within = _ragged_indices(lens64)
        flat = np.ascontiguousarray(chars[row_idx, within])
    else:
        flat = np.zeros(0, dtype=np.uint8)
    vbuf, nulls = _validity_buffer(valid)
    return pa.Array.from_buffers(
        pa.binary(), n,
        [vbuf, pa.py_buffer(offsets.tobytes()), pa.py_buffer(flat.tobytes())],
        null_count=nulls)


# ---------------------------------------------------------------------------
# Host column
# ---------------------------------------------------------------------------

class HostColumn:
    """A host column: pyarrow Array + our logical DataType.

    The Arrow buffers are the host representation for IO, shuffle wire format
    and CPU-fallback compute (the reference's analog is JCudfSerialization's
    host columnar layout + RapidsHostColumnVector).
    """

    __slots__ = ("arrow", "data_type", "_plain_cache")

    def __init__(self, arrow_array, data_type: Optional[T.DataType] = None):
        import pyarrow as pa
        if isinstance(arrow_array, pa.ChunkedArray):
            arrow_array = arrow_array.combine_chunks()
        if pa.types.is_date64(arrow_array.type):
            # canonical date repr is date32 (days); date64 (ms) is ingested
            arrow_array = arrow_array.cast(pa.date32())
        self.arrow = arrow_array
        self.data_type = data_type or T.from_arrow(arrow_array.type)
        #: memoized decoded form of a dictionary-encoded array (columns
        #: are immutable; every value-plane accessor below would
        #: otherwise re-decode the full column)
        self._plain_cache = None

    # -- constructors -------------------------------------------------------
    @staticmethod
    def from_numpy(data: np.ndarray, validity: Optional[np.ndarray] = None,
                   data_type: Optional[T.DataType] = None) -> "HostColumn":
        import pyarrow as pa
        dt = data_type or T.from_numpy_dtype(data.dtype)
        if data.dtype.kind == "M":
            # normalize datetime64 of any unit to our canonical physical repr
            if isinstance(dt, T.DateType):
                data = data.astype("datetime64[D]").astype(np.int32)
            else:
                data = data.astype("datetime64[us]").astype(np.int64)
        mask = None if validity is None else ~np.asarray(validity, dtype=bool)
        if isinstance(dt, T.NullType):
            arr = pa.nulls(len(data))
        elif isinstance(dt, T.DecimalType):
            # unscaled repr: int64 for decimal64, [n,2] (hi,lo) limbs for 128
            if dt.is_decimal128 and data.ndim == 2:
                hi, lo = data[:, 0].astype(np.int64), data[:, 1].astype(np.int64)
            else:
                lo = data.astype(np.int64)
                hi = np.where(lo < 0, np.int64(-1), np.int64(0))
            arr = _decimal128_from_limbs(hi, lo,
                                         None if mask is None else ~mask, dt)
        elif isinstance(dt, T.TimestampType):
            arr = pa.array(data.astype(np.int64), type=pa.int64(),
                           mask=mask).cast(pa.timestamp("us", tz="UTC"))
        elif isinstance(dt, T.DateType):
            arr = pa.array(data.astype(np.int32), type=pa.int32(),
                           mask=mask).cast(pa.date32())
        else:
            arr = pa.array(data, type=T.to_arrow(dt), mask=mask)
        return HostColumn(arr, dt)

    @staticmethod
    def from_pylist(values, data_type: Optional[T.DataType] = None) -> "HostColumn":
        import pyarrow as pa
        if data_type is not None:
            return HostColumn(pa.array(values, type=T.to_arrow(data_type)),
                              data_type)
        arr = pa.array(values)
        return HostColumn(arr)

    # -- accessors ----------------------------------------------------------
    def __len__(self) -> int:
        return len(self.arrow)

    @property
    def is_dict_encoded(self) -> bool:
        import pyarrow as pa
        return pa.types.is_dictionary(self.arrow.type)

    def _plain(self):
        """The non-dictionary arrow form (value-plane accessors below
        need real buffers; dictionary indices would masquerade as data).
        Decode routes through the one sanctioned host decode helper and
        is memoized (immutable column, many accessors)."""
        if not self.is_dict_encoded:
            return self.arrow
        if self._plain_cache is None:
            from spark_rapids_tpu.columnar.encoding import host_decoded
            self._plain_cache = host_decoded(self.arrow)
        return self._plain_cache

    @property
    def null_count(self) -> int:
        if self.is_dict_encoded:
            # a valid index pointing at a null dictionary VALUE is a
            # null row; only the decoded form counts those
            return self._plain().null_count
        return self.arrow.null_count

    def validity_np(self) -> np.ndarray:
        """Returns bool[rows], True where valid."""
        import pyarrow.compute as pc
        arr = self._plain()
        if arr.null_count == 0:
            return np.ones(len(arr), dtype=bool)
        return pc.is_valid(arr).to_numpy(zero_copy_only=False)

    def data_np(self) -> np.ndarray:
        """Dense data as numpy, nulls filled with zeros (use validity_np)."""
        import pyarrow as pa
        import pyarrow.compute as pc
        dt = self.data_type
        if isinstance(dt, (T.StringType, T.BinaryType)):
            raise TypeError("use string_np() for string columns")
        if isinstance(dt, T.ArrayType):
            raise TypeError("use list_np() for array columns")
        if isinstance(dt, T.DecimalType):
            # vectorized unscaled-limb extraction straight from the arrow
            # 16-byte little-endian buffer (reference: cuDF DECIMAL64/128
            # columns expose unscaled values the same way)
            arr = self._plain()
            if not pa.types.is_decimal128(arr.type):
                arr = arr.cast(pa.decimal128(dt.precision, dt.scale))
            n = len(arr)
            raw = np.frombuffer(arr.buffers()[1], dtype=np.int64,
                                offset=arr.offset * 16, count=2 * n).reshape(n, 2)
            lo = raw[:, 0].copy()
            hi = raw[:, 1].copy()
            valid = self.validity_np()
            lo[~valid] = 0
            hi[~valid] = 0
            if dt.is_decimal128:
                return np.stack([hi, lo], axis=1)  # device layout is [hi, lo]
            return lo
        arr = self._plain()
        if isinstance(dt, T.TimestampType):
            arr = arr.cast("int64")
        elif isinstance(dt, T.DateType):
            arr = arr.cast("int32")
        elif isinstance(dt, T.NullType):
            return np.zeros(len(arr), dtype=np.int8)
        if arr.null_count:
            import pyarrow as pa
            zero = pa.scalar(0, type=arr.type) if dt.np_dtype.kind != "b" \
                else pa.scalar(False, type=arr.type)
            arr = pc.fill_null(arr, zero)
        return arr.to_numpy(zero_copy_only=False).astype(dt.np_dtype, copy=False)

    def string_np(self, max_len: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray]:
        """Rectangularizes to (uint8[rows, max_len], int32 lengths)."""
        import pyarrow as pa
        import pyarrow.compute as pc
        arr = self._plain()
        if pa.types.is_string(arr.type):
            arr = arr.cast(pa.binary())
        elif pa.types.is_large_string(arr.type) or pa.types.is_large_binary(arr.type):
            arr = arr.cast(pa.binary())
        filled = pc.fill_null(arr, b"")
        lens = pc.binary_length(filled).to_numpy(zero_copy_only=False).astype(np.int32)
        ml = int(lens.max()) if len(lens) else 0
        width = bucket_strlen(max(ml, 1) if max_len is None else max_len)
        out = np.zeros((len(arr), width), dtype=np.uint8)
        combined = filled.combine_chunks() if isinstance(filled, pa.ChunkedArray) else filled
        buf = combined.buffers()
        # arrow binary: buffers = [validity, offsets(int32), data]
        offsets = np.frombuffer(buf[1], dtype=np.int32,
                                count=len(arr) + 1, offset=combined.offset * 4)
        databuf = np.frombuffer(buf[2], dtype=np.uint8) if buf[2] is not None \
            else np.zeros(0, dtype=np.uint8)
        np.minimum(lens, width, out=lens)
        # vectorized ragged->rectangular scatter
        if lens.sum():
            lens64 = lens.astype(np.int64)
            row_idx, within = _ragged_indices(lens64)
            starts = np.repeat(offsets[:-1].astype(np.int64), lens64)
            out[row_idx, within] = databuf[starts + within]
        return out, lens

    def list_np(self, max_len: Optional[int] = None):
        """Rectangularizes a list column to (values[rows, w], int32 lengths,
        elem_valid[rows, w]) — the device array-plane layout."""
        import pyarrow as pa
        import pyarrow.compute as pc
        dt = self.data_type
        if not isinstance(dt, T.ArrayType):
            raise TypeError("list_np on a non-array column")
        arr = self._plain()
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        if pa.types.is_large_list(arr.type):
            arr = arr.cast(pa.list_(arr.type.value_type))
        lens = pc.list_value_length(arr)
        lens = pc.fill_null(lens, 0).to_numpy(zero_copy_only=False)\
            .astype(np.int32)
        ml = int(lens.max()) if len(lens) else 0
        width = bucket_strlen(max(ml, 1) if max_len is None else max_len)
        edt = _elem_np_dtype(dt.element_type)
        out = np.zeros((len(arr), width), dtype=edt)
        ev = np.zeros((len(arr), width), dtype=bool)
        np.minimum(lens, width, out=lens)
        if lens.sum():
            # flatten() drops null-row slots, so align via raw offsets
            offsets = np.frombuffer(arr.buffers()[1], dtype=np.int32,
                                    count=len(arr) + 1, offset=arr.offset * 4)
            values = HostColumn(arr.values, dt.element_type)
            vdata = values.data_np()
            vvalid = values.validity_np()
            lens64 = lens.astype(np.int64)
            row_idx, within = _ragged_indices(lens64)
            starts = np.repeat(offsets[:-1].astype(np.int64), lens64)
            out[row_idx, within] = vdata[starts + within]
            ev[row_idx, within] = vvalid[starts + within]
        return out, lens, ev

    def to_pylist(self):
        return self.arrow.to_pylist()

    def slice(self, offset: int, length: int) -> "HostColumn":
        return HostColumn(self.arrow.slice(offset, length), self.data_type)

    def nbytes(self) -> int:
        n = sum(b.size for b in self.arrow.buffers() if b is not None)
        if self.is_dict_encoded:
            # .buffers() on a DictionaryArray covers only the indices;
            # the dictionary's value buffers are real host bytes too
            n += sum(b.size
                     for b in self.arrow.dictionary.buffers()
                     if b is not None)
        return n

    def __repr__(self):
        return f"HostColumn({self.data_type}, rows={len(self)}, nulls={self.null_count})"


# ---------------------------------------------------------------------------
# Device column
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DeviceColumn:
    """A device column vector (reference: GpuColumnVector over cudf).

    Invariants:
      - ``data.shape[0] == validity.shape[0] == bucket >= row_count``
      - rows in ``[row_count, bucket)`` have ``validity == False``
      - scalar types: data is 1-D jax array of the mapped dtype
      - string/binary: data is uint8[bucket, strwidth]; ``lengths`` int32[bucket]
      - decimal128: data is int64[bucket, 2] (hi limb, lo limb-as-int64-bits)
      - array<fixed-width>: data is elem[bucket, max_elems]; ``lengths``
        int32[bucket]; ``elem_valid`` bool[bucket, max_elems]
    """

    data: Any                      # jax Array
    validity: Any                  # jax bool Array [bucket]
    row_count: int
    data_type: T.DataType
    lengths: Any = None            # jax int32 Array [bucket] (strings/arrays)
    elem_valid: Any = None         # jax bool Array [bucket, w] (arrays only)

    # -- constructors -------------------------------------------------------
    @staticmethod
    def from_host(col: HostColumn, bucket: Optional[int] = None) -> "DeviceColumn":
        jnp = _jnp()
        n = len(col)
        b = bucket_rows(n) if bucket is None else bucket
        if b < n:
            raise ValueError(f"bucket {b} smaller than row count {n}")
        if b & (b - 1):
            raise ValueError(f"bucket {b} must be a power of two "
                             "(static-shape compile-cache discipline)")
        valid = np.zeros(b, dtype=bool)
        valid[:n] = col.validity_np()
        dt = col.data_type
        if is_device_array_type(dt):
            vals, lens, ev = col.list_np()
            w = vals.shape[1]
            data = np.zeros((b, w), dtype=vals.dtype)
            data[:n] = vals
            lengths = np.zeros(b, dtype=np.int32)
            lengths[:n] = lens
            elem_valid = np.zeros((b, w), dtype=bool)
            elem_valid[:n] = ev
            return DeviceColumn(jnp.asarray(data), jnp.asarray(valid), n, dt,
                                lengths=jnp.asarray(lengths),
                                elem_valid=jnp.asarray(elem_valid))
        if isinstance(dt, (T.StringType, T.BinaryType)):
            chars, lens = col.string_np()
            data = np.zeros((b, chars.shape[1]), dtype=np.uint8)
            data[:n] = chars
            lengths = np.zeros(b, dtype=np.int32)
            lengths[:n] = lens
            return DeviceColumn(jnp.asarray(data), jnp.asarray(valid), n, dt,
                                lengths=jnp.asarray(lengths))
        raw = col.data_np()
        if isinstance(dt, T.DecimalType) and dt.is_decimal128:
            data = np.zeros((b, 2), dtype=np.int64)
            data[:n] = raw
        else:
            data = np.zeros((b,) + raw.shape[1:], dtype=raw.dtype)
            data[:n] = raw
        return DeviceColumn(jnp.asarray(data), jnp.asarray(valid), n, dt)

    @staticmethod
    def from_parts(data, validity, row_count: int, data_type: T.DataType,
                   lengths=None, elem_valid=None) -> "DeviceColumn":
        return DeviceColumn(data, validity, row_count, data_type, lengths,
                            elem_valid)

    # -- accessors ----------------------------------------------------------
    @property
    def bucket(self) -> int:
        return int(self.data.shape[0])

    def __len__(self) -> int:
        return self.row_count

    @property
    def is_string(self) -> bool:
        return isinstance(self.data_type, (T.StringType, T.BinaryType))

    def nbytes(self) -> int:
        n = self.data.size * self.data.dtype.itemsize + self.validity.size
        if self.lengths is not None:
            n += self.lengths.size * 4
        if self.elem_valid is not None:
            n += self.elem_valid.size
        return int(n)

    def to_host(self) -> HostColumn:
        n = self.row_count
        return assemble_host_column(
            self.data_type, n,
            None if isinstance(self.data_type, T.NullType)
            else np.asarray(self.data)[:n],
            np.asarray(self.validity)[:n],
            None if self.lengths is None else np.asarray(self.lengths)[:n],
            None if self.elem_valid is None
            else np.asarray(self.elem_valid)[:n])

    def with_row_count(self, n: int) -> "DeviceColumn":
        return DeviceColumn(self.data, self.validity, n, self.data_type,
                            self.lengths, self.elem_valid)

    def __repr__(self):
        return (f"DeviceColumn({self.data_type}, rows={self.row_count}, "
                f"bucket={self.bucket})")


def assemble_host_column(dt: T.DataType, n: int, raw, valid,
                         lens=None, ev=None) -> HostColumn:
    """Rebuilds a HostColumn from already-fetched numpy planes (shared by
    DeviceColumn.to_host and the packed batch download in transfer.py)."""
    import pyarrow as pa
    if isinstance(dt, T.NullType):
        return HostColumn(pa.nulls(n), dt)
    if isinstance(dt, T.ArrayType):
        return HostColumn(_list_from_rectangular(raw, lens, ev, valid, dt),
                          dt)
    if isinstance(dt, (T.StringType, T.BinaryType)):
        binary = _binary_from_rectangular(raw, lens, valid)
        if isinstance(dt, T.StringType):
            try:
                return HostColumn(binary.cast(pa.string()), dt)
            except pa.ArrowInvalid:
                # kernel produced non-UTF8 bytes; decode with replacement
                py = [None if v is None else v.decode("utf-8", "replace")
                      for v in binary.to_pylist()]
                return HostColumn(pa.array(py, type=pa.string()), dt)
        return HostColumn(binary, dt)
    if isinstance(dt, T.DecimalType):
        if dt.is_decimal128:
            hi, lo = raw[:, 0], raw[:, 1]
        else:
            lo = raw.astype(np.int64)
            hi = np.where(lo < 0, np.int64(-1), np.int64(0))
        return HostColumn(_decimal128_from_limbs(hi, lo, valid, dt), dt)
    return HostColumn.from_numpy(raw, valid, dt)
