"""Encoded columnar execution: dictionary / RLE columns kept alive past
the scan.

"GPU Acceleration of SQL Analytics on Compressed Data" (PAPERS.md) shows
filters, joins and aggregations can run directly over dictionary- and
run-length-encoded columns without materializing; the reference plugin
keeps cuDF's encoded columns alive and runs nvcomp codecs on the byte
paths.  The TPU port:

- ``DictionaryColumn``: device codes (int32) + a process-cached
  ``Dictionary`` (host values + lazily-uploaded device value planes).
  The dictionary uploads ONCE per distinct content fingerprint; batches
  upload only their narrow code planes.
- ``RleColumn``: run values + run ends, padded to a pow2 *runs* bucket —
  sorted/constant fixed-width columns ship runs instead of rows.
- **Code-space predicates**: a filter conjunct whose only column input is
  one dictionary column evaluates ONCE over the (tiny) dictionary values
  on the CPU oracle backend, producing a bool lookup table the compiled
  program indexes by code — ``col = lit`` / ``IN`` / range / LIKE all
  reduce to one gather.  Tables are pow2-padded RUNTIME ARGUMENTS, so
  encoded filter chains compile to one executable across dictionaries
  and literal values alike (the encoded analog of literal promotion).
- **Late materialization**: filters compact code planes; only surviving
  rows ever gather through the dictionary, and only where an operator
  genuinely needs values.

Every decode funnels through ``decode_dictionary``/``decode_rle`` in
THIS module (lint rule ``encoded-materialize``): callers use the
``materialize*`` helpers, which count decoded bytes and emit the
``encodingFallback`` events the AutoTuner and ``tools profile`` read.
Every piece degrades per column to eager decode (oversized / null-valued
/ non-unique dictionaries, mismatched join/merge dictionaries, unsorted
sort keys), so ``spark.rapids.sql.encoding.enabled=false`` — or any
unsupported shape — reproduces the plain path bit-identically.
"""

from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import dataclasses

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.column import (DeviceColumn, HostColumn, _jnp,
                                              bucket_rows)

#: synced from spark.rapids.sql.encoding.* by TpuOverrides.apply
ENCODING_ENABLED = True
LATE_MATERIALIZATION = True
MAX_DICTIONARY_SIZE = 1 << 16
RLE_ENABLED = False

#: minimum runs-per-row advantage before an upload RLE-encodes a column
_RLE_MIN_RATIO = 8

_STATS_LOCK = threading.Lock()
_STATS = {
    "encoded_columns": 0,        # device columns that arrived encoded
    "rle_columns": 0,
    "encoded_bytes_in": 0,       # H2D bytes shipped for encoded planes
    "encoded_bytes_out": 0,      # D2H bytes shipped as codes
    "decode_avoided_bytes": 0,   # plain-plane bytes the encoding skipped
    "decoded_bytes": 0,          # bytes actually materialized later
    "dict_fallbacks": 0,         # per-column decodes forced by operators
}


def encoding_stats() -> dict:
    with _STATS_LOCK:
        return dict(_STATS)


def _bump(**kv) -> None:
    with _STATS_LOCK:
        for k, v in kv.items():
            _STATS[k] += v


# ---------------------------------------------------------------------------
# Dictionary: process-cached values, uploaded once per content fingerprint
# ---------------------------------------------------------------------------

_DICT_CACHE: "OrderedDict[tuple, Dictionary]" = OrderedDict()
_DICT_CACHE_MAX = 256
#: byte bound on cached dictionary VALUE payloads (host values + the
#: lazily-uploaded device planes track them ~1:1): the planes live
#: outside the BufferCatalog's accounting, so the cache — not the spill
#: framework — must bound their residency
_DICT_CACHE_MAX_BYTES = 64 << 20
_DICT_LOCK = threading.Lock()


class Dictionary:
    """The value side of a dictionary-encoded column.

    Host values stay resident (translation / D2H reassembly); the device
    value planes upload lazily, once per fingerprint, through the normal
    packed-transfer path.  Content-addressed: two parquet row groups (or
    two files) writing the same dictionary share one instance, so join
    sides and merged aggregation partials compare codes directly.
    """

    __slots__ = ("values", "fingerprint", "size", "value_type",
                 "_dev", "_sorted", "_tables", "_lock")

    def __init__(self, values, fingerprint: tuple):
        self.values = values            # pyarrow Array, no nulls
        self.fingerprint = fingerprint
        self.size = len(values)
        self.value_type = T.from_arrow(values.type)
        self._dev: Optional[DeviceColumn] = None
        self._sorted: Optional[bool] = None
        self._tables: Dict[tuple, Any] = {}
        self._lock = threading.Lock()

    @staticmethod
    def _fingerprint_of(values) -> tuple:
        h = hashlib.sha1()
        for buf in values.buffers():
            if buf is not None:
                h.update(memoryview(buf))
        return (h.hexdigest(), len(values), str(values.type))

    @classmethod
    def of(cls, values) -> "Dictionary":
        """The cached Dictionary for an arrow values array (LRU-bounded;
        holding the entry keeps both host values and device planes
        alive)."""
        fp = cls._fingerprint_of(values)
        with _DICT_LOCK:
            hit = _DICT_CACHE.get(fp)
            if hit is not None:
                _DICT_CACHE.move_to_end(fp)
                return hit
        dic = cls(values, fp)
        with _DICT_LOCK:
            _DICT_CACHE[fp] = dic
            total = sum(d.value_nbytes for d in _DICT_CACHE.values())
            while len(_DICT_CACHE) > 1 and \
                    (len(_DICT_CACHE) > _DICT_CACHE_MAX or
                     total > _DICT_CACHE_MAX_BYTES):
                _k, evicted = _DICT_CACHE.popitem(last=False)
                total -= evicted.value_nbytes
        return dic

    @property
    def value_nbytes(self) -> int:
        return sum(b.size for b in self.values.buffers()
                   if b is not None)

    @property
    def is_sorted(self) -> bool:
        """Values ascending (bytewise for strings — the device sort
        order): code order is then value order and sorts ride the codes."""
        if self._sorted is None:
            import pyarrow.compute as pc
            if self.size <= 1:
                self._sorted = True
            else:
                a = self.values.slice(0, self.size - 1)
                b = self.values.slice(1)
                self._sorted = bool(pc.all(pc.less_equal(a, b)).as_py())
        return self._sorted

    def device_column(self) -> DeviceColumn:
        """Device value planes (data/validity/lengths), uploaded once.
        Empty dictionaries get one invalid dummy row so gathers stay
        in-bounds (every code is null anyway)."""
        if self._dev is not None:
            return self._dev
        with self._lock:
            if self._dev is None:
                import pyarrow as pa
                vals = self.values
                if self.size == 0:
                    vals = pa.nulls(1, type=self.values.type)
                hc = HostColumn(vals, self.value_type)
                b = bucket_rows(max(len(vals), 1), minimum=8)
                dev = DeviceColumn.from_host(hc, bucket=b)
                _bump(encoded_bytes_in=dev.nbytes())
                self._dev = dev
        return self._dev

    def host_column(self) -> HostColumn:
        return HostColumn(self.values, self.value_type)

    def lookup_table(self, key: tuple, build) -> Any:
        """Device-resident pow2-padded bool table for one translated
        predicate, cached per (predicate identity) on this dictionary."""
        with self._lock:
            hit = self._tables.get(key)
            if hit is not None:
                return hit
        table = build()
        with self._lock:
            self._tables[key] = table
        return table

    @property
    def table_bucket(self) -> int:
        return bucket_rows(max(self.size, 1), minimum=8)

    def __repr__(self):
        return (f"Dictionary(size={self.size}, {self.value_type}, "
                f"fp={self.fingerprint[0][:8]})")


def reassemble_host_dictionary(codes_np: np.ndarray, valid_np: np.ndarray,
                               dic: "Dictionary", dt) -> HostColumn:
    """Host dictionary array from fetched code/validity planes (shared
    by ``DictionaryColumn.to_host`` and the packed download): null rows
    mask out, empty dictionaries get one dummy null value so the arrow
    array stays constructible."""
    import pyarrow as pa
    codes = codes_np.astype(np.int32, copy=False)
    _bump(encoded_bytes_out=codes.nbytes + valid_np.nbytes)
    idx = pa.array(np.where(valid_np, codes, 0), type=pa.int32(),
                   mask=~valid_np)
    values = dic.values if dic.size else pa.nulls(1, type=dic.values.type)
    return HostColumn(pa.DictionaryArray.from_arrays(idx, values), dt)


@dataclasses.dataclass
class DictionaryColumn(DeviceColumn):
    """Device column whose ``data`` plane holds int32 dictionary CODES;
    ``data_type`` stays the LOGICAL type.  Only encoding-aware paths may
    consume the codes; everything else must pass through
    ``materialize*`` (enforced by the encoded-materialize lint rule)."""

    dictionary: Any = None

    def to_host(self) -> HostColumn:
        n = int(self.row_count)
        return reassemble_host_dictionary(
            np.asarray(self.data)[:n], np.asarray(self.validity)[:n],
            self.dictionary, self.data_type)

    def with_row_count(self, n) -> "DictionaryColumn":
        return DictionaryColumn(self.data, self.validity, n, self.data_type,
                                None, None, dictionary=self.dictionary)

    def __repr__(self):
        return (f"DictionaryColumn({self.data_type}, rows={self.row_count}, "
                f"dict={self.dictionary.size})")


@dataclasses.dataclass
class RleColumn(DeviceColumn):
    """Run-length-encoded fixed-width device column: ``data`` holds the
    run VALUES, ``validity`` the run validity — both padded to a pow2
    RUNS bucket (smaller than the row bucket) — and ``run_ends`` the
    exclusive cumulative row end of each run (padding runs end at
    int32 max).  ``bucket`` reports the LOGICAL row bucket so the batch
    invariant holds; every row-shaped consumer must materialize first."""

    run_ends: Any = None           # int32 [runs_bucket]
    logical_bucket: int = 0

    @property
    def bucket(self) -> int:
        return self.logical_bucket

    @property
    def runs_bucket(self) -> int:
        return int(self.data.shape[0])

    def to_host(self) -> HostColumn:
        n = int(self.row_count)
        vals = np.asarray(self.data)
        rvalid = np.asarray(self.validity)
        ends = np.asarray(self.run_ends).astype(np.int64)
        _bump(encoded_bytes_out=vals.nbytes + rvalid.nbytes + ends.nbytes)
        idx = np.searchsorted(ends, np.arange(n, dtype=np.int64),
                              side="right")
        idx = np.clip(idx, 0, len(vals) - 1)
        from spark_rapids_tpu.columnar.column import assemble_host_column
        return assemble_host_column(self.data_type, n, vals[idx],
                                    rvalid[idx])

    def with_row_count(self, n) -> "RleColumn":
        return RleColumn(self.data, self.validity, n, self.data_type,
                         None, None, run_ends=self.run_ends,
                         logical_bucket=self.logical_bucket)

    def __repr__(self):
        return (f"RleColumn({self.data_type}, rows={self.row_count}, "
                f"runs_bucket={self.runs_bucket})")


def is_encoded(col: DeviceColumn) -> bool:
    return isinstance(col, (DictionaryColumn, RleColumn))


def batch_has_encoded(batch) -> bool:
    return any(is_encoded(c) for c in batch.columns)


def rewrap_like(proto: DeviceColumn, data, validity, rc, lengths=None,
                elem_valid=None) -> DeviceColumn:
    """Rebuilds a column from transformed planes, preserving dictionary
    encoding when the prototype carried one (row-space ops — gather,
    compact, concat, slice — transform code planes like any other int
    plane).  RLE prototypes must be materialized BEFORE row-space ops."""
    if isinstance(proto, DictionaryColumn):
        return DictionaryColumn(data, validity, rc, proto.data_type,
                                None, None, dictionary=proto.dictionary)
    return DeviceColumn(data, validity, rc, proto.data_type, lengths,
                        elem_valid)


# ---------------------------------------------------------------------------
# host-side decode (the ONE sanctioned arrow decode site)
# ---------------------------------------------------------------------------

def host_decoded(arrow_array):
    """Plain (non-dictionary) form of an arrow array; identity for
    already-plain arrays.  All host consumers that need value planes
    route here (columnar/column.py accessors)."""
    import pyarrow as pa
    if isinstance(arrow_array, pa.ChunkedArray):
        arrow_array = arrow_array.combine_chunks()
    if pa.types.is_dictionary(arrow_array.type):
        return arrow_array.dictionary_decode()
    return arrow_array


# ---------------------------------------------------------------------------
# device decode primitives (in-trace; everything funnels through these)
# ---------------------------------------------------------------------------

def decode_dictionary(codes, valid, vplanes, jnp):
    """Gathers value planes by code.  ``vplanes`` = (vdata, vvalid,
    vlens) from ``Dictionary.device_column()``.  Traced or eager.

    Null rows get ZEROED planes, not the gathered value-0 bytes: the
    engine-wide invariant (eager upload zero-fills null slots) that
    lets sort/partition word comparisons treat all null rows as equal
    without re-masking data everywhere."""
    vdata, vvalid, vlens = vplanes
    safe = jnp.clip(codes.astype(np.int32), 0, vdata.shape[0] - 1)
    v = valid & jnp.take(vvalid, safe)
    data = jnp.take(vdata, safe, axis=0)
    vmask = v.reshape(v.shape + (1,) * (data.ndim - 1))
    data = jnp.where(vmask, data, jnp.zeros_like(data))
    lens = None
    if vlens is not None:
        lens = jnp.where(v, jnp.take(vlens, safe),
                         jnp.zeros((), dtype=vlens.dtype))
    return data, v, lens


def decode_rle(run_vals, run_valid, run_ends, bucket, jnp):
    """Expands runs to rows: row i belongs to the first run whose end
    exceeds i (padding runs end at int32 max and are invalid).  Null
    rows decode to zeroed data (same invariant as decode_dictionary)."""
    rowpos = jnp.arange(bucket, dtype=np.int32)
    idx = jnp.searchsorted(run_ends, rowpos, side="right")
    idx = jnp.clip(idx, 0, run_vals.shape[0] - 1)
    v = jnp.take(run_valid, idx)
    data = jnp.take(run_vals, idx, axis=0)
    vmask = v.reshape(v.shape + (1,) * (data.ndim - 1))
    return jnp.where(vmask, data, jnp.zeros_like(data)), v


def _dict_planes(dic: Dictionary):
    dev = dic.device_column()
    return (dev.data, dev.validity, dev.lengths)


def _note_fallback(site: str, detail: str, nbytes: int) -> None:
    _bump(dict_fallbacks=1, decoded_bytes=nbytes)
    from spark_rapids_tpu.aux.events import emit
    emit("encodingFallback", site=site, detail=detail, bytes=nbytes)


def materialize(col: DeviceColumn, site: str = "operator",
                detail: str = "") -> DeviceColumn:
    """THE sanctioned eager decode: one compiled program per column
    shape.  Counts decoded bytes and (for operator-forced decodes)
    emits the ``encodingFallback`` evidence the AutoTuner reads."""
    jnp = _jnp()
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    if isinstance(col, DictionaryColumn):
        dic = col.dictionary
        planes = _dict_planes(dic)
        key = ("dict", str(col.data.dtype), tuple(col.data.shape),
               tuple((str(p.dtype), tuple(p.shape))
                     for p in planes if p is not None),
               planes[2] is not None)

        def build():
            def run(codes, valid, vplanes):
                return decode_dictionary(codes, valid, vplanes, jnp)
            return run

        fn = get_or_build("encoding.decode", key, build)
        data, v, lens = fn(col.data, col.validity, planes)
        out = DeviceColumn(data, v, col.row_count, col.data_type,
                           lengths=lens)
    elif isinstance(col, RleColumn):
        bucket = col.logical_bucket
        key = ("rle", str(col.data.dtype), tuple(col.data.shape), bucket)

        def build():
            def run(run_vals, run_valid, run_ends):
                return decode_rle(run_vals, run_valid, run_ends, bucket,
                                  jnp)
            return run

        fn = get_or_build("encoding.decode", key, build)
        data, v = fn(col.data, col.validity, col.run_ends)
        out = DeviceColumn(data, v, col.row_count, col.data_type)
    else:
        return col
    _note_fallback(site, detail or str(col.data_type), out.nbytes())
    return out


def materialize_batch(batch, ordinals: Optional[Sequence[int]] = None,
                      site: str = "operator"):
    """Batch with the selected (default: all) encoded columns decoded;
    returns the input unchanged when nothing decodes."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    want = set(range(len(batch.columns))) if ordinals is None \
        else set(ordinals)
    if not any(is_encoded(c) for i, c in enumerate(batch.columns)
               if i in want):
        return batch
    cols = [materialize(c, site=site, detail=(batch.names[i]
                                              if batch.names else str(i)))
            if i in want and is_encoded(c) else c
            for i, c in enumerate(batch.columns)]
    return ColumnarBatch(cols, batch.row_count, batch.names)


def materialize_rle_batch(batch, site: str = "operator"):
    """Row-space batch ops handle dictionary codes natively but cannot
    see through runs; this decodes only the RLE columns."""
    rle = [i for i, c in enumerate(batch.columns)
           if isinstance(c, RleColumn)]
    if not rle:
        return batch
    return materialize_batch(batch, ordinals=rle, site=site)


def align_batches(batches: List, site: str = "merge") -> List:
    """Makes a batch list safe to combine column-wise: RLE decodes, and a
    dictionary column position keeps its codes only when EVERY batch
    carries the SAME dictionary fingerprint there (else that position
    decodes in every batch)."""
    batches = [materialize_rle_batch(b, site=site) for b in batches]
    if not batches:
        return batches
    ncols = len(batches[0].columns)
    bad: List[int] = []
    for ci in range(ncols):
        cols = [b.columns[ci] for b in batches]
        encs = [c for c in cols if isinstance(c, DictionaryColumn)]
        if not encs:
            continue
        fps = {c.dictionary.fingerprint for c in encs}
        if len(encs) != len(cols) or len(fps) != 1:
            bad.append(ci)
    if not bad:
        return batches
    return [materialize_batch(b, ordinals=bad, site=site) for b in batches]


# ---------------------------------------------------------------------------
# upload / download classification (columnar/transfer.py hooks)
# ---------------------------------------------------------------------------

#: logical value types whose dictionary planes the device decode handles
#: (1-D data planes; decimal128's 2-limb plane is excluded)
_DICT_VALUE_OK = (T.StringType, T.BinaryType, T.ByteType, T.ShortType,
                  T.IntegerType, T.LongType, T.FloatType, T.DoubleType,
                  T.BooleanType, T.DateType, T.TimestampType)


def classify_host_column(col: HostColumn):
    """Upload-side decision for one host column:

    - ``("dict", Dictionary, codes_np, valid_np)``: keep encoded.
    - ``("rle", vals_np, valid_np, ends_np)``: runs beat rows.
    - ``None``: upload plain (decoding dictionary-typed arrows first is
      the caller's job via ``host_decoded``).
    """
    import pyarrow as pa
    import pyarrow.compute as pc
    arr = col.arrow
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    if pa.types.is_dictionary(arr.type):
        if not ENCODING_ENABLED:
            return None
        values = arr.dictionary
        ok = isinstance(T.from_arrow(values.type), _DICT_VALUE_OK) and \
            not isinstance(col.data_type, T.DecimalType)
        reason = None
        if not ok:
            reason = "valueType"
        elif len(values) > MAX_DICTIONARY_SIZE:
            reason = "maxDictionarySize"
        elif values.null_count:
            reason = "nullsInDictionary"
        elif len(values) and pc.count_distinct(values).as_py() != \
                len(values):
            # duplicated values would break code-space equality
            reason = "duplicateValues"
        if reason is not None:
            _bump(dict_fallbacks=1)
            from spark_rapids_tpu.aux.events import emit
            emit("encodingFallback", site="upload", detail=reason,
                 bytes=0, dict_size=len(values))
            return None
        dic = Dictionary.of(values)
        idx = arr.indices
        valid = pc.is_valid(arr).to_numpy(zero_copy_only=False)
        codes = pc.fill_null(idx, 0).to_numpy(zero_copy_only=False)
        codes = codes.astype(_narrow_code_dtype(dic.size), copy=False)
        return ("dict", dic, codes, valid)
    if RLE_ENABLED and ENCODING_ENABLED:
        dt = col.data_type
        npdt = getattr(dt, "np_dtype", None)
        if npdt is not None and not dt.is_nested and \
                not isinstance(dt, (T.StringType, T.BinaryType,
                                    T.DecimalType)) and len(col) >= 64:
            vals = col.data_np()
            if vals.ndim == 1:
                valid = col.validity_np()
                change = np.empty(len(vals), dtype=bool)
                change[0] = True
                np.not_equal(vals[1:], vals[:-1], out=change[1:])
                change[1:] |= valid[1:] != valid[:-1]
                starts = np.flatnonzero(change)
                if len(starts) * _RLE_MIN_RATIO <= len(vals):
                    ends = np.empty(len(starts), dtype=np.int32)
                    ends[:-1] = starts[1:]
                    ends[-1] = len(vals)
                    return ("rle", vals[starts], valid[starts], ends)
    return None


def _narrow_code_dtype(size: int):
    """Narrowest transfer dtype for codes (device codes are int32; the
    unpack program widens for free inside the jit)."""
    if size <= (1 << 7):
        return np.dtype(np.int8)
    if size <= (1 << 15):
        return np.dtype(np.int16)
    return np.dtype(np.int32)


def note_encoded_upload(n_dict: int, n_rle: int, encoded_bytes: int,
                        avoided_bytes: int) -> None:
    _bump(encoded_columns=n_dict, rle_columns=n_rle,
          encoded_bytes_in=encoded_bytes,
          decode_avoided_bytes=max(0, avoided_bytes))
    from spark_rapids_tpu.aux.events import emit
    emit("encodedBatch", dict_columns=n_dict, rle_columns=n_rle,
         encoded_bytes=encoded_bytes,
         decode_avoided_bytes=max(0, avoided_bytes))


# ---------------------------------------------------------------------------
# code-space predicates inside fused stages
# ---------------------------------------------------------------------------

class DictContains:
    """Internal translated predicate: ``table[code]`` where ``table`` is
    the conjunct evaluated once over the dictionary values.  Lives only
    inside a fused-stage trace (built per batch by ``plan_fused_stage``;
    never part of a logical plan).  Mimics the Expression eval protocol
    the chain tracer calls.

    Null rows take the conjunct's NULL-INPUT verdict (``null_keep``, a
    runtime arg next to the table): ``s IS NULL`` or ``coalesce(s, d) =
    d`` keep null rows in row space and must keep them here too."""

    __slots__ = ("ordinal", "slot")
    children: tuple = ()

    def __init__(self, ordinal: int, slot: int):
        self.ordinal = ordinal
        self.slot = slot

    @property
    def data_type(self):
        return T.BOOLEAN

    def sql(self) -> str:
        return f"dict_contains(input[{self.ordinal}], $tab{self.slot})"

    def eval_tpu(self, ctx):
        from spark_rapids_tpu.expressions.base import TCol
        jnp = _jnp()
        tc = ctx.cols[self.ordinal]
        table, null_keep = ctx.enc_tables[self.slot]
        safe = jnp.clip(tc.data.astype(np.int32), 0, table.shape[0] - 1)
        keep = jnp.where(tc.valid, jnp.take(table, safe), null_keep)
        return TCol(keep, True, T.BOOLEAN)

    def eval(self, ctx):
        return self.eval_tpu(ctx)


def _refs(expr) -> List[int]:
    from spark_rapids_tpu.expressions.base import BoundReference
    return [e.ordinal for e in
            expr.collect(lambda n: isinstance(n, BoundReference))]


def _all_deterministic(expr) -> bool:
    return not expr.collect(lambda n: not getattr(n, "deterministic", True))


def _strip_alias(expr):
    from spark_rapids_tpu.expressions.base import Alias
    while isinstance(expr, Alias):
        expr = expr.children[0]
    return expr


def _eval_conjunct_over(values_hc: HostColumn, n: int, expr, ordinal: int,
                        ncols: int) -> np.ndarray:
    """keep-mask of ``expr`` over ``n`` rows of host values at position
    ``ordinal`` on the CPU oracle backend: True only where definitively
    true (null and false both drop, exactly like the row-space filter)."""
    from spark_rapids_tpu.expressions.base import EvalContext
    from spark_rapids_tpu.expressions.evaluator import host_batch_tcols
    from spark_rapids_tpu.columnar.batch import HostColumnarBatch
    hb = HostColumnarBatch([values_hc], n, ["v"])
    cols: List = [None] * ncols
    cols[ordinal] = host_batch_tcols(hb)[0]
    ctx = EvalContext(cols, "cpu", n)
    tc = expr.eval_cpu(ctx)
    if tc.is_scalar:
        return np.full(n, bool(tc.valid) and bool(tc.data))
    data = np.asarray(tc.data, dtype=bool)
    valid = np.asarray(tc.valid)
    if valid.ndim == 0:
        valid = np.full(n, bool(valid))
    return data[:n] & valid[:n]


def _build_lookup_table(dic: Dictionary, expr, ordinal: int, ncols: int):
    """One translated conjunct's runtime binding: (device bool table
    padded to the dictionary's pow2 bucket, null-input verdict).  The
    null verdict comes from evaluating the SAME conjunct over one null
    value — ``IS NULL``-shaped predicates keep their null rows."""
    import pyarrow as pa
    jnp = _jnp()
    padded = dic.table_bucket
    table = np.zeros(padded, dtype=bool)
    if dic.size:
        table[:dic.size] = _eval_conjunct_over(
            dic.host_column(), dic.size, expr, ordinal, ncols)
    null_hc = HostColumn(pa.nulls(1, type=dic.values.type),
                         dic.value_type)
    null_keep = bool(_eval_conjunct_over(null_hc, 1, expr, ordinal,
                                         ncols)[0])
    return (jnp.asarray(table), jnp.asarray(null_keep))


def _table_cache_key(expr) -> tuple:
    """Identity of a translated conjunct for the per-dictionary table
    cache.  ``sql()`` renders promoted literals as value-independent
    slots, so their concrete VALUES must ride along — two parameterized
    queries sharing a program must not share a lookup table."""
    from spark_rapids_tpu.plan.stages import PromotedLiteral
    lits = expr.collect(lambda n: isinstance(n, PromotedLiteral))
    return (expr.sql(), tuple(repr(p.value) for p in lits))


class FusedEncodingPlan:
    """Per-(stage, batch-encoding) translation of a fused op chain.

    - ``ops``: the chain with translatable conjuncts swapped for
      ``DictContains`` lookups.
    - ``decode_ordinals``: input ordinals decoded IN-TRACE (columns some
      expression needs as values); their dictionary planes ride as
      runtime args — still one program, no extra dispatch.
    - ``tables``: device bool tables, runtime args (value-independent
      program).
    - ``final_dicts``: per post-chain output position, the Dictionary a
      kept (passthrough) column still carries — late materialization.
    """

    __slots__ = ("ops", "tables", "decode_ordinals", "decode_dicts",
                 "rle_ordinals", "rle_buckets", "final_dicts", "sig")

    def __init__(self, ops, tables, decode_ordinals, decode_dicts,
                 rle_ordinals, rle_buckets, final_dicts, sig):
        self.ops = ops
        self.tables = tables
        self.decode_ordinals = decode_ordinals
        self.decode_dicts = decode_dicts
        self.rle_ordinals = rle_ordinals
        self.rle_buckets = rle_buckets
        self.final_dicts = final_dicts
        self.sig = sig

    def runtime_args(self, batch):
        """Per-call arg binding (plans are cached and shared across
        concurrent partition tasks — no per-batch state lives on the
        plan): tables and dictionary planes are batch-independent, RLE
        run planes come from THIS batch's columns."""
        dplanes = tuple(_dict_planes(d) for d in self.decode_dicts)
        rplanes = tuple((batch.columns[i].data, batch.columns[i].validity,
                         batch.columns[i].run_ends)
                        for i in self.rle_ordinals)
        return (tuple(self.tables), dplanes, rplanes)

    def prepare_cols(self, cols, enc_args, jnp):
        """In-trace column prep: decode-mode dictionaries gather through
        their value-plane args; RLE expands.  Kept columns stay as code
        TCols only ``DictContains`` / bare passthrough may touch."""
        _tables, dplanes, rplanes = enc_args
        cols = list(cols)
        for k, o in enumerate(self.decode_ordinals):
            from spark_rapids_tpu.expressions.base import TCol
            tc = cols[o]
            data, v, lens = decode_dictionary(tc.data, tc.valid,
                                              dplanes[k], jnp)
            cols[o] = TCol(data, v, tc.dtype, lengths=lens)
        for k, o in enumerate(self.rle_ordinals):
            from spark_rapids_tpu.expressions.base import TCol
            tc = cols[o]
            bucket = self.rle_buckets[k]
            rv, rvalid, rends = rplanes[k]
            data, v = decode_rle(rv, rvalid, rends, bucket, jnp)
            cols[o] = TCol(data, v, tc.dtype)
        return cols


def _batch_enc_fingerprint(batch) -> tuple:
    out = []
    for i, c in enumerate(batch.columns):
        if isinstance(c, DictionaryColumn):
            out.append((i, "d", c.dictionary.fingerprint))
        elif isinstance(c, RleColumn):
            out.append((i, "r", tuple(c.data.shape), c.logical_bucket))
    return tuple(out)


def plan_fused_stage(ops, batch, key_exprs=(), other_exprs=(),
                     cache: Optional[dict] = None
                     ) -> Optional[FusedEncodingPlan]:
    """Translates a fused [filter|project]* chain for one batch's column
    encodings.  ``key_exprs`` (hash-agg grouping) may consume kept codes
    as bare references; ``other_exprs`` (agg value inputs) force a
    decode of any encoded column they touch.  Returns None when the
    batch carries no encoded columns."""
    dict_in = {i: c for i, c in enumerate(batch.columns)
               if isinstance(c, DictionaryColumn)}
    rle_in = {i: c for i, c in enumerate(batch.columns)
              if isinstance(c, RleColumn)}
    if not dict_in and not rle_in:
        return None
    cache_key = None
    if cache is not None:
        cache_key = _batch_enc_fingerprint(batch)
        hit = cache.get(cache_key)
        if hit is not None:
            return hit
    from spark_rapids_tpu.expressions import predicates as P
    from spark_rapids_tpu.expressions.base import BoundReference
    ncols = len(batch.columns)
    decode: set = set()

    def analyze(extra_decode: set):
        """One pass over the chain; returns (translated ops, final
        provenance map: post-chain position -> kept input ordinal,
        table slots as (src ordinal, chain position, conjunct)).
        Conjuncts a later use invalidates land in ``extra_decode`` and
        the caller re-runs to a fixed point."""
        prov: List[Optional[int]] = list(range(ncols))
        table_slots: List[tuple] = []   # (src ordinal, position, expr)

        def kept(pos: int) -> Optional[int]:
            src = prov[pos] if pos < len(prov) else None
            if src is None or src not in dict_in or src in decode or \
                    src in extra_decode:
                return None
            return src

        def visit_pred(e):
            if isinstance(e, P.And):
                kids = [visit_pred(c) for c in e.children]
                return e.with_children(kids)
            rs = _refs(e)
            enc = sorted({r for r in rs if kept(r) is not None})
            if not enc:
                return e
            if len(set(rs)) == 1 and len(enc) == 1 and \
                    _all_deterministic(e) and \
                    isinstance(getattr(e, "data_type", None),
                               T.BooleanType):
                slot = len(table_slots)
                # the conjunct's BoundReference carries the CURRENT chain
                # position; the table is keyed by the INPUT dictionary
                table_slots.append((kept(enc[0]), enc[0], e))
                return DictContains(enc[0], slot)
            for r in enc:
                extra_decode.add(kept(r))
            return e

        new_ops = []
        for kind, payload in ops:
            if kind == "filter":
                new_ops.append(("filter", visit_pred(payload)))
            else:
                new_prov: List[Optional[int]] = []
                for e in payload:
                    base = _strip_alias(e)
                    if isinstance(base, BoundReference) and \
                            kept(base.ordinal) is not None:
                        new_prov.append(prov[base.ordinal])
                    else:
                        for r in _refs(e):
                            if r < len(prov) and kept(r) is not None:
                                extra_decode.add(kept(r))
                        new_prov.append(None)
                new_ops.append(("project", payload))
                prov = new_prov
        # post-chain consumers (hash-agg inputs)
        for e in key_exprs:
            base = _strip_alias(e)
            if isinstance(base, BoundReference) and \
                    kept(base.ordinal) is not None:
                continue
            for r in _refs(e):
                if r < len(prov) and kept(r) is not None:
                    extra_decode.add(kept(r))
        for e in other_exprs:
            for r in _refs(e):
                if r < len(prov) and kept(r) is not None:
                    extra_decode.add(kept(r))
        return new_ops, prov, table_slots

    # iterate to a fixed point: translating under a decode set that a
    # later use (or a failed table build) invalidates re-runs the
    # analysis with the wider decode set
    tables: List = []
    for _ in range(2 * ncols + 2):
        extra: set = set()
        new_ops, prov, table_slots = analyze(extra)
        if extra:
            decode |= extra
            continue
        # build the lookup tables (cached per dictionary + conjunct); a
        # conjunct whose oracle evaluation fails is not translatable —
        # decode its column and re-plan instead of failing the query
        tables = []
        failed: set = set()
        for src, pos, expr in table_slots:
            dic = dict_in[src].dictionary
            key = _table_cache_key(expr)
            try:
                tables.append(dic.lookup_table(
                    key, lambda d=dic, e=expr, o=pos:
                    _build_lookup_table(d, e, o, max(ncols, o + 1))))
            except Exception:  # noqa: BLE001 — translation is an
                failed.add(src)  # optimization, never a query error
        if not failed:
            break
        decode |= failed
    decode_ordinals = sorted(decode)
    rle_ordinals = sorted(rle_in)
    final_dicts: List[Optional[Dictionary]] = []
    for pos in range(len(prov)):
        src = prov[pos]
        final_dicts.append(dict_in[src].dictionary
                           if src is not None and src in dict_in and
                           src not in decode else None)
    sig = (tuple(decode_ordinals),
           tuple((i, tuple(rle_in[i].data.shape),
                  rle_in[i].logical_bucket) for i in rle_ordinals),
           tuple(int(t[0].shape[0]) for t in tables),
           tuple(i for i, d in enumerate(final_dicts) if d is not None))
    plan = FusedEncodingPlan(
        new_ops, tables, decode_ordinals,
        [dict_in[o].dictionary for o in decode_ordinals],
        rle_ordinals, [rle_in[o].logical_bucket for o in rle_ordinals],
        final_dicts, sig)
    if cache is not None:
        if len(cache) > 64:
            cache.clear()
        cache[cache_key] = plan
    return plan


def eval_exprs_keep_encoded(exprs, batch, names=None):
    """``eval_exprs_tpu`` that passes bare-reference outputs of
    dictionary columns through ENCODED (the aggregate's final projection
    of grouped keys, e.g.) — codes then ride all the way to the
    download boundary, which reassembles them against the host
    dictionary without ever gathering values."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.expressions import evaluator as EV
    from spark_rapids_tpu.expressions.base import BoundReference
    keep: Dict[int, int] = {}
    for i, e in enumerate(exprs):
        base = _strip_alias(e)
        if isinstance(base, BoundReference) and \
                base.ordinal < len(batch.columns) and \
                isinstance(batch.columns[base.ordinal], DictionaryColumn):
            keep[i] = base.ordinal
    if not keep:
        return EV.eval_exprs_tpu(exprs, batch, names)
    others = [e for i, e in enumerate(exprs) if i not in keep]
    ob = EV.eval_exprs_tpu(others, batch) if others else None
    oc = iter(ob.columns) if ob is not None else iter(())
    cols = []
    for i, e in enumerate(exprs):
        if i in keep:
            c = batch.columns[keep[i]]
            if c.row_count is not batch.row_count:
                c = c.with_row_count(batch.row_count)
            cols.append(c)
        else:
            cols.append(next(oc))
    return ColumnarBatch(cols, batch.row_count,
                         names or EV._out_names(exprs))


# ---------------------------------------------------------------------------
# join / sort helpers
# ---------------------------------------------------------------------------

def join_key_dicts(batch, keys) -> List[Optional[Dictionary]]:
    """Per join key: the Dictionary when the key is a bare reference to
    a dictionary column of this batch (code-space join candidate)."""
    from spark_rapids_tpu.expressions.base import BoundReference
    out: List[Optional[Dictionary]] = []
    for k in keys:
        base = _strip_alias(k)
        dic = None
        if ENCODING_ENABLED and isinstance(base, BoundReference) and \
                base.ordinal < len(batch.columns):
            c = batch.columns[base.ordinal]
            if isinstance(c, DictionaryColumn):
                dic = c.dictionary
        out.append(dic)
    return out


def codes_key_column(batch, key_expr) -> DeviceColumn:
    """The int32 code plane of a bare-ref dictionary key, shaped as a
    plain INT column for the hash-join/sort word machinery."""
    from spark_rapids_tpu.expressions.base import BoundReference
    base = _strip_alias(key_expr)
    assert isinstance(base, BoundReference)
    col = batch.columns[base.ordinal]
    return DeviceColumn(col.data, col.validity, batch.row_count, T.INT)


def shadow_sort_batch(batch, specs) -> Tuple[Any, Any]:
    """Sort prep: RLE decodes; a dictionary SORT KEY keeps its codes
    only when the dictionary is value-sorted (codes are then
    order-isomorphic), else it materializes; payload dictionary columns
    ride the gather as int planes.  Returns (shadow batch, rewrap fn)
    mapping sorted outputs back to their encodings."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.expressions.base import BoundReference
    batch = materialize_rle_batch(batch, site="sort")
    if not batch_has_encoded(batch):
        return batch, lambda out: out
    key_ords = set()
    expr_ref_ords = set()
    for s in specs:
        base = _strip_alias(s.expr)
        if isinstance(base, BoundReference):
            key_ords.add(base.ordinal)
        else:
            expr_ref_ords.update(_refs(s.expr))
    shadow = []
    wrap: Dict[int, Dictionary] = {}
    for i, c in enumerate(batch.columns):
        if not isinstance(c, DictionaryColumn):
            shadow.append(c)
            continue
        unsorted_key = i in key_ords and not c.dictionary.is_sorted
        if unsorted_key or i in expr_ref_ords:
            shadow.append(materialize(c, site="sort",
                                      detail=(batch.names[i]
                                              if batch.names else str(i))))
            continue
        shadow.append(DeviceColumn(c.data, c.validity, c.row_count,
                                   T.INT))
        wrap[i] = c.dictionary
    shadow_b = ColumnarBatch(shadow, batch.row_count, batch.names)
    if not wrap:
        return shadow_b, lambda out: out
    logical = [c.data_type for c in batch.columns]

    def rewrap(out):
        cols = list(out.columns)
        for i, dic in wrap.items():
            c = cols[i]
            cols[i] = DictionaryColumn(c.data, c.validity, c.row_count,
                                       logical[i], None, None,
                                       dictionary=dic)
        return ColumnarBatch(cols, out.row_count, out.names)

    return shadow_b, rewrap
