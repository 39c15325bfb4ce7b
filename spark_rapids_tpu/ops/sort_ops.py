"""Device sort kernels: multi-key lexicographic sort.

Reference: GpuSortExec.scala + SortUtils.scala lower sorting to cuDF
``Table.sortOrder``/``gather``.  TPU-first redesign: every key column is
normalized into one or more integer "sortable words" such that plain
ascending integer order == the SQL order (nulls-first/last, asc/desc, NaN
ordering, string lexicographic order); ``lex_sort_perm`` turns the words
into the row permutation, and every data, validity and length plane then
moves by ``jnp.take`` with it.  Static shapes throughout — no comparator
callbacks, no dynamic shapes.

Only what orders is sorted, and it is sorted as ONE packed 32-bit word at
a time: XLA:TPU's build time for a variadic ``lax.sort`` grows steeply
with its operand count (v5e, 2^20 rows: 1 operand 4 s, 3 operands 39 s,
6 operands 156 s, ten operands with int64 payloads over 4 minutes), while
the one-operand sort inside a ``lax.scan`` is built once whatever the key
width (``scripts/tpu_rehearsal.py`` is how to check a new program).

Normalization rules:
- padding rows (>= row_count) sort last via a leading global rank word
- null rank word per key: 0/1 per nulls_first
- floats: IEEE bit trick (flip all bits when negative, flip sign bit when
  positive) -> unsigned order; NaN canonicalized positive (sorts after +inf,
  Spark semantics), -0.0 normalized to 0.0
- strings/binary: bytes+1 packed 7-per-uint64 big-endian (pad rank 0) so a
  prefix sorts first and embedded NULs stay ordered; exact, not truncated
- decimal128: hi limb signed word, lo limb unsigned word
- descending: bitwise-NOT of the word (monotone order reversal, no overflow)
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.expressions.base import expr_key


def _jx():
    from spark_rapids_tpu.columnar.column import _jnp
    return _jnp()


@dataclasses.dataclass(frozen=True)
class SortOrder:
    """One sort key (reference: Spark SortOrder child/direction/nullOrdering).

    ``ordinal`` indexes the batch being sorted; exec layers project key
    expressions into leading columns first.
    """
    ordinal: int
    ascending: bool = True
    nulls_first: bool = True   # Spark default: NULLS FIRST for ASC, LAST for DESC

    @staticmethod
    def asc(ordinal: int) -> "SortOrder":
        return SortOrder(ordinal, True, True)

    @staticmethod
    def desc(ordinal: int) -> "SortOrder":
        return SortOrder(ordinal, False, False)


def _float_sortable(x, jnp, ubits_dtype):
    # f32: one u32 word; f64: TWO u32 words via double-double split —
    # the TPU X64 rewriter has no f64 bitcast (see ops/f64bits.py)
    from spark_rapids_tpu.ops.f64bits import (f32_sortable_u32,
                                              f64_sortable_words)
    if np.dtype(ubits_dtype).itemsize == 8:
        return f64_sortable_words(x, jnp)
    return [f32_sortable_u32(x, jnp)]


def _string_words(col: DeviceColumn, jnp) -> List:
    """Packs bytes+1 (pad=0) 7-per-word big-endian -> uint64 words."""
    data = col.data          # uint8 [bucket, w]
    lens = col.lengths
    w = int(data.shape[1]) if data.ndim == 2 else 0
    if w == 0:
        return [jnp.zeros(data.shape[0], dtype=np.uint64)]
    pos = jnp.arange(w, dtype=np.int32)
    vals = jnp.where(pos[None, :] < lens[:, None],
                     data.astype(np.uint64) + 1, 0)
    words = []
    for start in range(0, w, 7):
        chunk = vals[:, start:start + 7]
        word = jnp.zeros(data.shape[0], dtype=np.uint64)
        k = chunk.shape[1]
        for j in range(k):
            word = word | (chunk[:, j] << np.uint64(9 * (6 - j)))
        words.append(word)
    return words


def sortable_words(col: DeviceColumn, jnp) -> List:
    """Key words in ascending-SQL order; nulls carry garbage (rank separates).

    Used both by sort (with null-rank words) and by group-boundary detection
    (with null masking)."""
    import jax
    dt = col.data_type
    if isinstance(dt, (T.StringType, T.BinaryType)):
        return _string_words(col, jnp)
    if isinstance(dt, T.DecimalType) and dt.is_decimal128:
        hi = col.data[:, 0]
        lo = jax.lax.bitcast_convert_type(col.data[:, 1], np.uint64)
        return [hi, lo]
    if isinstance(dt, T.FloatType):
        return _float_sortable(col.data, jnp, np.uint32)
    if isinstance(dt, T.DoubleType):
        return _float_sortable(col.data, jnp, np.uint64)
    if isinstance(dt, T.BooleanType):
        return [col.data.astype(bool)]
    # integral / date / timestamp / decimal64: native integer order
    return [col.data]


def _order_words(col: DeviceColumn, order: SortOrder, jnp) -> List:
    """null-rank word + (possibly flipped) value words for one sort key.
    The rank is a bool (one key bit): False sorts first."""
    words = [col.validity if order.nulls_first else ~col.validity]
    for w in sortable_words(col, jnp):
        if not order.ascending:
            w = ~w
        words.append(w)
    return words


def _unsigned_word(w, jnp):
    """(unsigned array, bit width) whose unsigned order is ``w``'s order:
    bool is one bit, a signed integer flips its sign bit."""
    import jax
    if isinstance(w, tuple):
        return w                       # caller-declared (unsigned, nbits)
    dt = np.dtype(w.dtype)
    if dt == np.bool_:
        return w.astype(np.uint32), 1
    bits = dt.itemsize * 8
    if dt.kind == "i":
        udt = np.dtype(f"uint{bits}")
        w = jax.lax.bitcast_convert_type(w, udt) ^ udt.type(1 << (bits - 1))
    elif dt.kind != "u":
        raise TypeError(f"sort word of dtype {dt}: want bool or integer")
    return w, bits


def lex_sort_perm(words: Sequence, n: int, jnp):
    """int32[n] stable permutation that sorts rows by ``words``, most
    significant first.  A word is a bool or integer array (ordered as its
    dtype orders), or ``(unsigned array, nbits)`` where the caller knows
    that only the low ``nbits`` bits vary.

    Least-significant-digit radix sort: the key bits are cut into digits of
    ``32 - log2(n)`` bits; each pass packs (digit, current position) into
    one uint32 and sorts that alone, unstable — positions are unique, so
    the result is the stable order.  The passes run in one ``lax.scan``, so
    the compiler builds one one-operand sort whatever the key width."""
    import jax
    logn = max(1, (n - 1).bit_length())
    if logn > 30:
        raise ValueError(f"lex_sort_perm: {n} rows do not fit a packed key")
    digit_bits = 32 - logn
    digits: List = []
    cur, fill = None, 0
    for word in reversed(list(words)):
        u, nbits = _unsigned_word(word, jnp)
        off = 0
        while off < nbits:
            take = min(digit_bits - fill, nbits - off)
            piece = u if off == 0 else u >> u.dtype.type(off)
            if off + take < np.dtype(u.dtype).itemsize * 8:
                piece = piece & u.dtype.type((1 << take) - 1)
            piece = piece.astype(np.uint32)
            if fill:
                piece = piece << np.uint32(fill)
            cur = piece if cur is None else cur | piece
            fill += take
            off += take
            if fill == digit_bits:
                digits.append(cur)
                cur, fill = None, 0
    if cur is not None:
        digits.append(cur)
    pos = jnp.arange(n, dtype=np.uint32)
    low = np.uint32((1 << logn) - 1)

    def rank(digit):
        packed = jax.lax.sort((digit << np.uint32(logn)) | pos,
                              is_stable=False)
        return (packed & low).astype(np.int32)

    if not digits:
        return pos.astype(np.int32)
    if len(digits) == 1:
        return rank(digits[0])

    def one_pass(perm, digit):
        idx = rank(jnp.take(digit, perm, axis=0))
        return jnp.take(perm, idx, axis=0), None

    perm, _ = jax.lax.scan(one_pass, pos.astype(np.int32),
                           jnp.stack(digits))
    return perm


# ---------------------------------------------------------------------------
# numpy twin (CPU oracle paths, e.g. RangePartitioning.partition_ids_cpu):
# same normalization semantics, classic host-side bit tricks
# ---------------------------------------------------------------------------

def _float_sortable_np(x: np.ndarray) -> np.ndarray:
    x = np.where(x == 0, np.zeros((), dtype=x.dtype), x)
    x = np.where(np.isnan(x), np.array(np.nan, dtype=x.dtype), x)
    ub = np.uint64 if x.dtype == np.float64 else np.uint32
    u = np.ascontiguousarray(x).view(ub)
    nbits = np.dtype(ub).itemsize * 8
    sign = ub(1) << ub(nbits - 1)
    allbits = ~ub(0)
    return np.where((u & sign) != 0, u ^ allbits, u | sign)


def _string_words_np(chars: np.ndarray, lens: np.ndarray) -> List[np.ndarray]:
    w = chars.shape[1] if chars.ndim == 2 else 0
    if w == 0:
        return [np.zeros(chars.shape[0], dtype=np.uint64)]
    pos = np.arange(w, dtype=np.int32)
    vals = np.where(pos[None, :] < lens[:, None],
                    chars.astype(np.uint64) + 1, np.uint64(0))
    words = []
    for start in range(0, w, 7):
        chunk = vals[:, start:start + 7]
        word = np.zeros(chars.shape[0], dtype=np.uint64)
        for j in range(chunk.shape[1]):
            word = word | (chunk[:, j] << np.uint64(9 * (6 - j)))
        words.append(word)
    return words


def host_order_words(col, order: SortOrder,
                     string_width: Optional[int] = None,
                     string_pair=None) -> List[np.ndarray]:
    """Numpy order words for one HostColumn: [null-rank] + value words in
    the same SQL order as the device path.  ``string_width`` pads string
    rectangles so two batches (rows vs range bounds) agree on word count;
    ``string_pair`` reuses an already-rectangularized (chars, lens) so
    callers that probed the width don't pay the ragged->rect scatter twice."""
    dt = col.data_type
    valid = col.validity_np()
    rank_null = np.int8(0 if order.nulls_first else 1)
    rank_val = np.int8(1 if order.nulls_first else 0)
    words: List[np.ndarray] = [np.where(valid, rank_val, rank_null)]
    if isinstance(dt, (T.StringType, T.BinaryType)):
        if string_pair is not None:
            chars, lens = string_pair
            if string_width and chars.shape[1] < string_width:
                chars = np.pad(chars,
                               ((0, 0), (0, string_width - chars.shape[1])))
        else:
            chars, lens = col.string_np(max_len=string_width)
        vw = _string_words_np(chars, lens)
    elif isinstance(dt, T.DecimalType) and dt.is_decimal128:
        raw = col.data_np()
        vw = [raw[:, 0], np.ascontiguousarray(raw[:, 1]).view(np.uint64)]
    elif isinstance(dt, (T.FloatType, T.DoubleType)):
        vw = [_float_sortable_np(col.data_np())]
    elif isinstance(dt, T.BooleanType):
        vw = [col.data_np().astype(np.int8)]
    else:
        vw = [col.data_np()]
    for w in vw:
        w = np.where(valid, w, np.zeros((), dtype=w.dtype))
        if not order.ascending:
            w = ~w
        words.append(w)
    return words




def _col_sig(c: DeviceColumn) -> Tuple:
    return (str(c.data.dtype), tuple(c.data.shape), c.lengths is not None)


def sort_permutation(batch: ColumnarBatch, orders: Sequence[SortOrder]):
    """Returns int32[bucket] permutation placing rows in SQL order,
    padding rows last.  One jitted program per (shapes, orders) signature."""
    jnp = _jx()
    orders = tuple(orders)
    key = ("perm", tuple(_col_sig(c) for c in batch.columns), orders)
    def build():
        bucket = batch.bucket
        # capture only scalars/types, never the batch itself: the jitted
        # closure lives in the module cache and would pin device buffers
        dtypes = [c.data_type for c in batch.columns]

        def run(arrs, row_count):
            cols = [DeviceColumn(d, v, bucket, dtypes[i], ln)
                    for i, (d, v, ln) in enumerate(arrs)]
            rowpos = jnp.arange(bucket, dtype=np.int32)
            words = [rowpos >= row_count]  # padding last
            for o in orders:
                words.extend(_order_words(cols[o.ordinal], o, jnp))
            return lex_sort_perm(words, bucket, jnp)

        return run
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    fn = get_or_build("sort.perm", key, build)
    from spark_rapids_tpu.columnar.column import rc_traceable
    arrs = [(c.data, c.validity, c.lengths) for c in batch.columns]
    return fn(arrs, rc_traceable(batch.row_count))


def sort_gather_batch(batch: ColumnarBatch, orders: Sequence[SortOrder],
                      key_exprs: Sequence = ()) -> ColumnarBatch:
    """Fused sort-key prep + permutation + payload gather: ONE compiled
    program.  ``key_exprs`` are non-reference sort keys evaluated
    IN-TRACE (ordinals past the payload width address them), so an
    expression sort pays zero extra dispatches — previously key
    projection, permutation and gather were three programs (the gather
    even dispatched per column).  The payload keeps the input layout;
    key columns never materialize in HBM."""
    jnp = _jx()
    orders = tuple(orders)
    key_exprs = list(key_exprs or ())
    key = ("sortgather", tuple(_col_sig(c) for c in batch.columns),
           tuple((c.elem_valid is not None) for c in batch.columns),
           orders, tuple(expr_key(e) for e in key_exprs),
           batch.bucket)

    def build():
        bucket = batch.bucket
        dtypes = [c.data_type for c in batch.columns]
        exprs = list(key_exprs)

        def run(arrs, row_count):
            from spark_rapids_tpu.expressions.base import EvalContext, TCol
            from spark_rapids_tpu.expressions.evaluator import \
                tcol_to_device_column
            cols = [DeviceColumn(d, v, bucket, dtypes[i], ln, ev)
                    for i, (d, v, ln, ev) in enumerate(arrs)]
            keycols = list(cols)
            if exprs:
                tcols = [TCol(c.data, c.validity, c.data_type,
                              lengths=c.lengths, elem_valid=c.elem_valid)
                         for c in cols]
                ctx = EvalContext(tcols, "tpu", bucket)
                for e in exprs:
                    dc = tcol_to_device_column(e.eval_tpu(ctx), 0, bucket,
                                               jnp)
                    keycols.append(DeviceColumn(dc.data, dc.validity,
                                                bucket, e.data_type,
                                                dc.lengths))
            rowpos = jnp.arange(bucket, dtype=np.int32)
            words = [rowpos >= row_count]  # padding last
            for o in orders:
                words.extend(_order_words(keycols[o.ordinal], o, jnp))
            perm = lex_sort_perm(words, bucket, jnp)
            outs = []
            for c in cols:
                d = jnp.take(c.data, perm, axis=0)
                v = jnp.take(c.validity, perm, axis=0)
                ln = None if c.lengths is None else \
                    jnp.take(c.lengths, perm, axis=0)
                ev = None if c.elem_valid is None else \
                    jnp.take(c.elem_valid, perm, axis=0)
                outs.append((d, v, ln, ev))
            return outs

        return run

    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    fn = get_or_build("sort.fused", key, build)
    from spark_rapids_tpu.columnar.column import rc_traceable
    arrs = [(c.data, c.validity, c.lengths, c.elem_valid)
            for c in batch.columns]
    outs = fn(arrs, rc_traceable(batch.row_count))
    cols = [DeviceColumn(d, v, batch.row_count, c.data_type, ln, ev)
            for (d, v, ln, ev), c in zip(outs, batch.columns)]
    return ColumnarBatch(cols, batch.row_count, batch.names)


def sort_batch(batch: ColumnarBatch, orders: Sequence[SortOrder]) -> ColumnarBatch:
    return sort_gather_batch(batch, orders)
