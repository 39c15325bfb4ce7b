"""Device join kernels: equi-joins and nested-loop pair generation.

Reference: GpuHashJoin (execution/GpuHashJoin.scala) lowers joins to cuDF
hash-table gather maps; JoinGatherer.scala applies them.  TPU-first
redesign — XLA has no device hash tables, but sorts and prefix-sums well,
so an equi-join becomes:

1. hash every row's key columns into one uint64 word (padding/invalid rows
   get a sentinel hash);
2. sort the BUILD side by hash (``sort_ops.lex_sort_perm``) and, in the
   same program, count its live rows by the hash's top ``k`` bits and
   prefix-sum the counts into a bucket-start table (``_bucket_starts``);
3. look each PROBE hash's top ``k`` bits up in that table -> a candidate
   range [lo, lo + count) of sorted build positions per probe row, two
   32-bit gathers and no search (static shapes throughout).  The range
   holds every build row of equal hash and, on average, under
   ``1 / _TABLE_LOAD`` rows of another hash (fewer where the probe side
   is the larger: ``_PROBE_LOAD``), which step 5 drops;
4. expand candidate pairs into a padded pair table: output position ``r``
   belongs to the last probe row whose offset is ``<= r``, found for all
   positions at once by a histogram of the offsets and a prefix sum
   (``batch_ops.expand_positions``; the positions are an iota, so no
   search is needed), then three gathers give the build row (the only
   host syncs are the candidate total and the final row count);
5. VERIFY true key equality per pair (hash collisions and null semantics are
   resolved here, on masked sortable words), and
6. finalize per join type: compact kept pairs, append null-extended
   unmatched rows for outer joins, or reduce to per-row match flags for
   semi/anti.

Where the build side allows it, a DIRECT-ADDRESS table replaces steps 1-5
(``build_side``; DuckDB's perfect hash join): one equi-join key,
integral on both sides (byte, short, int, long, date, or a code-space
key's int32 codes), not null-safe, whose live non-null build keys are
unique and span ``max - min + 1 <= 2^k`` slots, ``k`` being what
``_table_bits`` gives the hash path for the same two buckets, so the
direct table is never larger than the table it replaces.  The build
program scatters each live build row's index to ``table[key - min]``
and returns ``min``, ``max`` and a duplicate flag, which the host
fetches once a built side (site ``join-build``) to choose the path.  A
probe row then makes ONE 32-bit gather, ``table[key - min]`` (the
build row, or -1), so it has at most one pair: the pairs come out dense
in probe order, with no false candidate to verify and nothing to
compact.  Any other join (several keys, strings outside code space,
floating keys, duplicate or too widely spread build keys, ``<=>``)
takes the hash path.

Nested-loop (cross / condition-only) joins reuse steps 4-6 with the
candidate set = the full cartesian product of in-row positions.

Null semantics match Spark: null keys never match (unless the key is
null-safe, i.e. ``<=>``); NaN == NaN and -0.0 == 0.0 for join keys (the
sortable-word normalization gives this for free, sort_ops.py).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import DeviceColumn, bucket_rows
from spark_rapids_tpu.ops.batch_ops import (compaction_perm,
                                            expand_positions, prefix_sum)


def _jx():
    from spark_rapids_tpu.columnar.column import _jnp
    return _jnp()


# Join types (reference: Spark JoinType; GpuHashJoin supports all of these)
INNER = "inner"
LEFT_OUTER = "left_outer"
RIGHT_OUTER = "right_outer"
FULL_OUTER = "full_outer"
LEFT_SEMI = "left_semi"
LEFT_ANTI = "left_anti"
CROSS = "cross"

_SENTINEL = np.uint64(0xFFFFFFFFFFFFFFFF)


def _mix64(h, jnp):
    """murmur3 fmix64 — avalanches a uint64 word."""
    h = h ^ (h >> np.uint64(33))
    h = h * np.uint64(0xFF51AFD7ED558CCD)
    h = h ^ (h >> np.uint64(33))
    h = h * np.uint64(0xC4CEB9FE1A85EC53)
    h = h ^ (h >> np.uint64(33))
    return h


def _key_words(col: DeviceColumn, jnp, width_words: Optional[int] = None):
    """(validity-rank word, masked value words) for one key column; equal
    keys (with both-null == both-null) produce identical word tuples.
    ``width_words`` pads string word lists so both sides agree."""
    from spark_rapids_tpu.ops.sort_ops import sortable_words
    words = []
    for w in sortable_words(col, jnp):
        words.append(jnp.where(col.validity, w, jnp.zeros_like(w)))
    if width_words is not None:
        while len(words) < width_words:
            words.append(jnp.zeros(col.bucket, dtype=np.uint64))
    return [col.validity.astype(np.int8)] + words


def _n_value_words(col: DeviceColumn) -> int:
    """How many value words _key_words yields for this column (static)."""
    dt = col.data_type
    if isinstance(dt, (T.StringType, T.BinaryType)):
        w = int(col.data.shape[1]) if col.data.ndim == 2 else 0
        return max(1, -(-w // 7))
    if isinstance(dt, T.DecimalType) and dt.is_decimal128:
        return 2
    if isinstance(dt, T.DoubleType):
        from spark_rapids_tpu.ops.f64bits import f64_word_count
        return f64_word_count()   # 1 exact u64 (CPU) / 2 dd u32s (TPU)
    return 1


def _hash_rows(cols: List[DeviceColumn], widths: List[int], inrow, jnp):
    """uint64 hash per row over all key columns; padding rows -> sentinel."""
    h = jnp.full(cols[0].bucket if cols else inrow.shape[0], 0x9E3779B97F4A7C15,
                 dtype=np.uint64)
    for c, w in zip(cols, widths):
        for word in _key_words(c, jnp, w):
            u = word.astype(np.uint64) if word.dtype != np.uint64 else word
            h = _mix64(h ^ _mix64(u, jnp), jnp)
    return jnp.where(inrow, h, _SENTINEL)


def _col_sig(c: DeviceColumn) -> Tuple:
    return (str(c.data.dtype), tuple(c.data.shape), c.lengths is not None,
            c.elem_valid is not None)


#: Slots of the bucket-start table for each row of the build bucket (a
#: power of two).  A probe row's candidates are its equal-hash build rows
#: plus the other live rows of its slot, ``live build rows / slots <= 1 /
#: _TABLE_LOAD`` on average: an additive ``probe rows / _TABLE_LOAD`` on
#: the candidate total at most, whatever the join selects.  8 by a chip
#: reading (v5e, 4.2M-row probe bucket; PERF.md section 6, PR 32): the
#: probe program takes 69.7 ms against any table of 2^17 to 2^23 slots,
#: 76.5 ms against 2^24 (64 MiB: the 1.9M-row build side at 8) and 145.6
#: ms against 2^25 (the same side at 16).
_TABLE_LOAD = 8
#: Slots for each row of the PROBE bucket, where that asks for more than
#: the build bucket does.  A large join's pair table and the batch it
#: hands on take the bucket of the candidate total (``exec/joins.py``),
#: so a false candidate is no longer free: 27,440 live rows of a
#: 32,768-row build bucket at ``_TABLE_LOAD`` alone (2^18 slots) gave
#: the fact table's 2.88M probe rows 300,000 false candidates, a
#: 524,288-row pair table where 65,536 hold the pairs, and every join
#: above ran at that (0.21 s a star query in ``join.gather`` alone).
#: The probe's share is capped at ``2^_TABLE_FREE_BITS`` slots.  Read on
#: the chip, build and probe together, under the fact table's
#: 4,194,304-row bucket (one call, same seeds; PERF.md section 6, PR 36):
#: tables of 2^22, 2^23 and 2^24 slots answer 1.841, 1.822-1.840 and
#: 1.811-1.816 star queries/s, the lookups of a query taking 67.9, 68.0
#: and 74.1 ms (flat up to 2^23, as PR 32 read the lookup alone), so the
#: cap is 2^23.  What the rule costs a join that had few false
#: candidates to lose: the table's zero fill and prefix sum over 8M
#: slots, 2 ms a build (``store_scan_agg`` 3.846-3.857 queries/s without
#: the rule, 3.804-3.818 with it).  1 slot a probe row would halve that
#: and reads the same in the star cell, but leaves a star query's
#: candidate total at 89% of its 65,536-row bucket where 2 leaves it at
#: 75%.
_PROBE_LOAD = 2
_TABLE_FREE_BITS = 23
#: No table has more than ``2^_TABLE_MAX_BITS`` slots (512 MiB of int32),
#: which is ``_TABLE_LOAD`` slots a row up to a build bucket of 2^24 rows.
#: Above that a slot holds ``bucket / 2^27`` rows of another hash on
#: average (1/4 at 2^25 rows, 8 at the 2^30 rows ``join.pair`` admits):
#: more pairs for ``verify`` to drop, and the same rows kept.
_TABLE_MAX_BITS = 27
#: Gathers a probe row makes to find its candidate range (the counter
#: ``probe_gather_rounds``): ``starts[b]`` and ``starts[b + 1]``; a
#: direct-address table's probe makes ``DIRECT_GATHER_ROUNDS``.
PROBE_GATHER_ROUNDS = 2
DIRECT_GATHER_ROUNDS = 1
#: Key types whose value is one integral word, so that ``key - min``
#: addresses a direct table (a code-space key arrives as INT codes).
_DIRECT_TYPES = (T.ByteType, T.ShortType, T.IntegerType, T.LongType,
                 T.DateType)
_INT64_MIN, _INT64_MAX = np.iinfo(np.int64).min, np.iinfo(np.int64).max


def _table_bits(bucket: int, probe_bucket: int) -> int:
    """``k``: the table over a ``bucket``-row build side that a
    ``probe_bucket``-row batch probes has ``2^k`` slots.  A function of
    the two buckets alone, so of the programs' shapes.  A direct-address
    table takes the same ``2^k`` slots and its keys' span is capped by
    them: its probe's gather costs what the bucket-start table's did (flat
    up to 2^23 slots), it never holds more bytes than the table it
    replaces, and a span that varies with a query's literals never
    changes a program's shape."""
    slots = max(_TABLE_LOAD * bucket,
                min(_PROBE_LOAD * probe_bucket, 1 << _TABLE_FREE_BITS))
    return min(slots.bit_length() - 1, _TABLE_MAX_BITS)


def _slot_of(h, k: int):
    """The hash's top ``k`` bits as int32 (0 for ``k`` = 0: the shift is
    split because a shift by the whole width is undefined)."""
    return ((h >> np.uint64(1)) >> np.uint64(63 - k)).astype(np.int32)


def _bucket_starts(slot_sorted, live_sorted, k: int, jnp):
    """int32[2^k + 1]: ``starts[b]`` is the first sorted build position
    whose slot is ``>= b``, counting live rows only, so ``starts[b + 1] -
    starts[b]`` rows of slot ``b`` begin at ``starts[b]`` and no padding
    row is in any range.  A histogram of the slots (ascending: the rows
    are sorted by hash, padding last) and its prefix sum, as
    ``batch_ops.expand_positions`` counts offsets."""
    slots = 1 << k
    hist = jnp.zeros(slots, dtype=np.int32).at[
        jnp.where(live_sorted, slot_sorted, slots)].add(
            1, mode="drop", indices_are_sorted=True)
    return jnp.concatenate([jnp.zeros(1, dtype=np.int32),
                            prefix_sum(hist, jnp)])


@dataclasses.dataclass
class BuiltSide:
    """The build side, tabulated for probing — reusable across many probe
    batches (reference: the build-side hash table in GpuHashJoin).  The
    hash path sorts it by key hash (``starts``, ``perm``); the direct path
    addresses it by key (``direct``, ``bounds``) and has neither."""
    batch: ColumnarBatch          # original build batch
    key_ordinals: Tuple[int, ...]
    starts: object                # int32[2^k + 1]: _bucket_starts
    perm: object                  # int32[bucket]: sorted pos -> original row
    widths: List[int]             # string word widths agreed with probe side
    direct: object = None         # int32[2^k]: build row of key - min, or -1
    bounds: object = None         # int64[3] on device: min, max, duplicates

    @property
    def gather_rounds(self) -> int:
        """Gathers a probe row makes (the counter ``probe_gather_rounds``)."""
        return DIRECT_GATHER_ROUNDS if self.direct is not None \
            else PROBE_GATHER_ROUNDS


def build_side(batch: ColumnarBatch, key_ordinals: Sequence[int],
               probe_key_cols: Sequence[DeviceColumn],
               null_safe: Sequence[bool]) -> BuiltSide:
    """Tabulates the build side: a direct-address table where the join's
    key and the build side's keys allow one (module docstring), else the
    hash path's bucket-start table."""
    key_ordinals = tuple(key_ordinals)
    kcols = [batch.columns[i] for i in key_ordinals]
    if len(kcols) == 1 and not null_safe[0] and all(
            isinstance(c.data_type, _DIRECT_TYPES)
            for c in (kcols[0], probe_key_cols[0])):
        built = _direct_side(batch, key_ordinals, probe_key_cols[0])
        if built is not None:
            return built
    return _hash_side(batch, key_ordinals, probe_key_cols)


def _direct_side(batch: ColumnarBatch, key_ordinals: Tuple[int, ...],
                 probe_key: DeviceColumn) -> Optional[BuiltSide]:
    """One program scatters each live build row's index to ``table[key -
    min]`` over ``2^k`` slots and returns the live keys' ``min``, ``max``
    and whether two rows share a key (a row that lost its slot to another
    row: exact, and all a scatter needs); the host fetches the three once
    (site ``join-build``).  ``None`` where the keys repeat or span more
    than ``2^k`` (the span taken in Python ints, so no extreme key
    overflows it): the caller takes the hash path."""
    from spark_rapids_tpu.aux import transitions as TR
    from spark_rapids_tpu.columnar.column import rc_traceable
    jnp = _jx()
    col = batch.columns[key_ordinals[0]]
    bucket = col.bucket
    k = _table_bits(bucket, probe_key.bucket)
    key = ("direct", (_col_sig(col),), k)
    def build():
        import jax
        slots = 1 << k

        def run(data, valid, row_count):
            rowpos = jnp.arange(bucket, dtype=np.int32)
            live = valid & (rowpos < row_count)
            x = data.astype(np.int64)
            lo = jnp.min(jnp.where(live, x, _INT64_MAX))
            hi = jnp.max(jnp.where(live, x, _INT64_MIN))
            # key - min as uint64 is exact for every live key, whatever
            # the span; a key past the slots is no live row's slot
            off = jax.lax.bitcast_convert_type(x, np.uint64) - \
                jax.lax.bitcast_convert_type(lo, np.uint64)
            fits = live & (off < np.uint64(slots))
            slot = jnp.where(fits, off, slots).astype(np.int32)
            table = jnp.full(slots, -1, dtype=np.int32).at[slot].set(
                rowpos, mode="drop")
            lost = fits & (jnp.take(table, jnp.minimum(slot, slots - 1))
                           != rowpos)
            return table, jnp.stack([lo, hi, jnp.any(lost).astype(np.int64)])

        return run
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    fn = get_or_build("join.build", key, build)
    table, bounds = fn(col.data, col.validity, rc_traceable(batch.row_count))
    lo, hi, dup = (int(v) for v in TR.fetch(bounds, site="join-build"))
    if dup or hi - lo + 1 > 1 << k:
        return None
    return BuiltSide(batch, key_ordinals, None, None, [1], direct=table,
                     bounds=bounds)


def _hash_side(batch: ColumnarBatch, key_ordinals: Tuple[int, ...],
               probe_key_cols: Sequence[DeviceColumn]) -> BuiltSide:
    """Sorts the build side by key hash and tabulates where each slot of
    the hash's top bits starts (one jitted program)."""
    from spark_rapids_tpu.ops.sort_ops import lex_sort_perm
    jnp = _jx()
    kcols = [batch.columns[i] for i in key_ordinals]
    widths = [max(_n_value_words(b), _n_value_words(p))
              for b, p in zip(kcols, probe_key_cols)]
    bucket = kcols[0].bucket if kcols else batch.bucket
    k = _table_bits(bucket,
                    probe_key_cols[0].bucket if probe_key_cols else 0)
    key = ("build", tuple(_col_sig(c) for c in kcols), tuple(widths), k)
    def build():
        dtypes = [c.data_type for c in kcols]

        def run(arrs, row_count):
            cols = [DeviceColumn(d, v, bucket, dtypes[i], ln)
                    for i, (d, v, ln) in enumerate(arrs)]
            rowpos = jnp.arange(bucket, dtype=np.int32)
            inrow = rowpos < row_count
            h = _hash_rows(cols, widths, inrow, jnp)
            # stable: a live row whose hash is the sentinel's sorts before
            # every padding row
            perm = lex_sort_perm([h], bucket, jnp)
            starts = _bucket_starts(jnp.take(_slot_of(h, k), perm),
                                    perm < row_count, k, jnp)
            return starts, perm

        return run
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    fn = get_or_build("join.build", key, build)
    from spark_rapids_tpu.columnar.column import rc_traceable
    arrs = [(c.data, c.validity, c.lengths) for c in kcols]
    starts, perm = fn(arrs, rc_traceable(batch.row_count))
    return BuiltSide(batch, key_ordinals, starts, perm, widths)


def _probe_ranges(probe_keys: List[DeviceColumn], built: BuiltSide):
    """Per-probe-row candidate range in the sorted build side: the rows of
    the probe hash's slot, read from ``built.starts`` with
    ``PROBE_GATHER_ROUNDS`` 32-bit gathers and no search.
    Returns (lo, counts, offsets, total) — total is the one host sync.
    Against a direct table ``lo`` is the build row (``_direct_probe``)."""
    if built.direct is not None:
        return _direct_probe(probe_keys[0], built)
    jnp = _jx()
    key = ("probe", tuple(_col_sig(c) for c in probe_keys),
           built.starts.shape, tuple(built.widths))
    def build():
        import jax
        bucket = probe_keys[0].bucket
        dtypes = [c.data_type for c in probe_keys]
        widths = built.widths
        k = (int(built.starts.shape[0]) - 1).bit_length() - 1

        def run(arrs, row_count, starts):
            # the named scopes are the engine's names for the program's
            # phases in every XLA op's op_name (metadata only)
            with jax.named_scope("hash"):
                cols = [DeviceColumn(d, v, bucket, dtypes[i], ln)
                        for i, (d, v, ln) in enumerate(arrs)]
                rowpos = jnp.arange(bucket, dtype=np.int32)
                inrow = rowpos < row_count
                h = _hash_rows(cols, widths, inrow, jnp)
            with jax.named_scope("lookup"):
                b = _slot_of(h, k)
                lo = jnp.take(starts, b)
                hi = jnp.take(starts, b + 1)
            with jax.named_scope("offsets"):
                # the table counts live build rows only, so a range never
                # holds a padding row; a candidate total may pass 2^31
                counts = jnp.where(inrow, hi - lo, 0).astype(np.int64)
                offsets = prefix_sum(counts, jnp) - counts
                return lo, counts, offsets, jnp.sum(counts)

        return run
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    fn = get_or_build("join.probe", key, build)
    arrs = [(c.data, c.validity, c.lengths) for c in probe_keys]
    from spark_rapids_tpu.columnar.column import rc_traceable
    lo, counts, offsets, total = fn(arrs, rc_traceable(probe_keys[0].row_count),
                                    built.starts)
    return lo, counts, offsets, total   # total: 0-d device (caller decides)


def _direct_probe(probe_key: DeviceColumn, built: BuiltSide):
    """``_probe_ranges`` against a direct table: a live probe row whose key
    lies in ``[min, max]`` (tested before the subtraction, so no key
    overflows it) reads ``row = table[key - min]``, one 32-bit gather, and
    has one candidate where ``row >= 0``; that candidate is its pair.
    Returns (row, counts, offsets, total): int32 counts and offsets (a
    total never passes the probe's bucket), an int64 total."""
    jnp = _jx()
    key = ("direct_probe", _col_sig(probe_key), tuple(built.direct.shape))
    def build():
        import jax
        bucket = probe_key.bucket

        def run(data, valid, row_count, table, bounds):
            # the named scopes are the hash path's names for the same phases
            with jax.named_scope("lookup"):
                x = data.astype(np.int64)
                inrow = jnp.arange(bucket, dtype=np.int32) < row_count
                hit = valid & inrow & (x >= bounds[0]) & (x <= bounds[1])
                slot = (jnp.where(hit, x, bounds[0]) - bounds[0]).astype(
                    np.int32)
                row = jnp.where(hit, jnp.take(table, slot), -1)
            with jax.named_scope("offsets"):
                counts = (row >= 0).astype(np.int32)
                offsets = prefix_sum(counts, jnp) - counts
                return row, counts, offsets, jnp.sum(counts, dtype=np.int64)

        return run
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    from spark_rapids_tpu.columnar.column import rc_traceable
    fn = get_or_build("join.probe", key, build)
    return fn(probe_key.data, probe_key.validity,
              rc_traceable(probe_key.row_count), built.direct, built.bounds)


def _direct_pairs(rows, offsets, total, out_bucket: int):
    """``_expand_verify`` against a direct table: pair ``r`` is the probe
    row ``p`` whose offset is ``r`` and its build row ``rows[p]``, one
    gather a pair; every candidate is a pair, so nothing is verified and
    the kept pairs are the first ``total``, in probe order."""
    jnp = _jx()
    p_bucket = int(rows.shape[0])
    key = ("direct_pairs", out_bucket, p_bucket)
    def build():
        import jax

        def run(rows, offsets, total):
            with jax.named_scope("expand"):
                r = jnp.arange(out_bucket, dtype=np.int32)
                p = jnp.clip(expand_positions(offsets, out_bucket, jnp), 0,
                             p_bucket - 1)
                b = jnp.take(rows, p)
                return p.astype(np.int64), b.astype(np.int64), r < total

        return run
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    fn = get_or_build("join.pair", key, build)
    l_idx, r_idx, keep = fn(rows, offsets, total)
    return l_idx, r_idx, keep, out_bucket


def _expand_verify(probe: ColumnarBatch, probe_ordinals, built: BuiltSide,
                   null_safe: Tuple[bool, ...], lo, offsets, total,
                   out_bucket: int):
    """Expands candidate ranges to a padded pair table and verifies true key
    equality.  Returns (l_idx, r_idx, keep, pair_bucket).  ``total`` may be
    a 0-d device scalar (speculative sizing: caller picked ``out_bucket``
    and tracks overflow via ops/speculation.py) or a host int (exact).
    Against a direct table nothing needs verifying (``_direct_pairs``)."""
    if built.direct is not None:
        return _direct_pairs(lo, offsets, total, out_bucket)
    jnp = _jx()
    pkeys = [probe.columns[i] for i in probe_ordinals]
    bkeys = [built.batch.columns[i] for i in built.key_ordinals]
    key = ("pairs", out_bucket, tuple(_col_sig(c) for c in pkeys),
           tuple(_col_sig(c) for c in bkeys), null_safe, tuple(built.widths))
    def build():
        import jax
        p_bucket = probe.bucket
        b_bucket = built.batch.bucket
        pdt = [c.data_type for c in pkeys]
        bdt = [c.data_type for c in bkeys]
        widths = built.widths
        if max(out_bucket, b_bucket) > 1 << 30:
            # lo + j must fit 32 bits (``lex_sort_perm`` holds the build
            # side and the compaction of the pairs to the same limit)
            raise ValueError(f"join.pair: a pair table of {out_bucket} rows "
                             f"over a build side of {b_bucket} does not fit "
                             "32-bit positions")

        def run(parrs, barrs, lo, offsets, total, perm, p_count, b_count):
            pcols = [DeviceColumn(d, v, p_bucket, pdt[i], ln)
                     for i, (d, v, ln) in enumerate(parrs)]
            bcols = [DeviceColumn(d, v, b_bucket, bdt[i], ln)
                     for i, (d, v, ln) in enumerate(barrs)]
            with jax.named_scope("expand"):
                # positions are 32-bit inside the program (every one is
                # bounded by a bucket, and a 64-bit gather costs the TPU
                # two); only what is returned is widened
                r = jnp.arange(out_bucket, dtype=np.int32)
                # probe row for each output pair: last offset <= r
                p = expand_positions(offsets, out_bucket, jnp)
                p = jnp.clip(p, 0, p_bucket - 1)
                # offsets[p] <= r < out_bucket for every r: clamping the
                # 64-bit offsets (a candidate total may pass 2^31) changes
                # no offset that is read
                j = r - jnp.take(
                    jnp.minimum(offsets, out_bucket).astype(np.int32), p)
                # position in sorted build
                spos = jnp.take(lo.astype(np.int32), p) + j
                spos = jnp.clip(spos, 0, b_bucket - 1)
                # original build row
                b = jnp.take(perm, spos)
                live = r < total
                keep = live & (p < p_count) & (b < b_count)
            # verify true equality on masked words (collisions + nulls)
            with jax.named_scope("verify"):
                for ki, (pc, bc) in enumerate(zip(pcols, bcols)):
                    pw = _key_words(pc, jnp, widths[ki])
                    bw = _key_words(bc, jnp, widths[ki])
                    eq = jnp.ones(out_bucket, dtype=bool)
                    for a, bword in zip(pw, bw):
                        av = jnp.take(a, p, axis=0)
                        bv = jnp.take(bword, b, axis=0)
                        eq = eq & (av == bv)
                    if not null_safe[ki]:
                        eq = eq & jnp.take(pc.validity, p) & \
                            jnp.take(bc.validity, b)
                    keep = keep & eq
            return p.astype(np.int64), b.astype(np.int64), keep

        return run
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    fn = get_or_build("join.pair", key, build)
    parrs = [(c.data, c.validity, c.lengths) for c in pkeys]
    barrs = [(c.data, c.validity, c.lengths) for c in bkeys]
    from spark_rapids_tpu.columnar.column import rc_traceable as _rt
    l_idx, r_idx, keep = fn(parrs, barrs, lo, offsets, total, built.perm,
                            _rt(probe.row_count), _rt(built.batch.row_count))
    return l_idx, r_idx, keep, out_bucket


def cross_pairs(probe: ColumnarBatch, build: ColumnarBatch):
    """Candidate set for nested-loop joins: full cartesian product.
    Returns (l_idx, r_idx, keep, pair_bucket)."""
    jnp = _jx()
    from spark_rapids_tpu.columnar.column import rc_traceable
    total = int(probe.row_count) * int(build.row_count)
    out_bucket = bucket_rows(max(total, 1))
    key = ("cross", out_bucket)
    def build_fn():
        def run(total, b_count):
            r = jnp.arange(out_bucket, dtype=np.int64)
            bc = jnp.maximum(b_count, 1)
            p = r // bc
            b = r % bc
            keep = r < total
            return p, b, keep

        return run
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    fn = get_or_build("join.cross_pairs", key, build_fn)
    l_idx, r_idx, keep = fn(total, rc_traceable(build.row_count))
    return l_idx, r_idx, keep, out_bucket


def matched_flags(idx, keep, side_bucket: int):
    """Per-row "has >= 1 kept pair" flags (semi/anti/outer bookkeeping)."""
    jnp = _jx()
    key = ("flags", int(idx.shape[0]), side_bucket)
    def build():
        def run(idx, keep):
            safe = jnp.clip(idx, 0, side_bucket - 1)
            return jnp.zeros(side_bucket, dtype=bool).at[safe].max(keep)

        return run
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    fn = get_or_build("join.matched_flags", key, build)
    return fn(idx, keep)


def compact_pairs(l_idx, r_idx, keep):
    """Moves kept pairs to the front; returns (l, r, count).

    The count stays a :class:`DeferredCount` — forcing it here would cost a
    host round trip per probe batch; consumers size their output by the
    pair bucket
    (static) and mask by the deferred count instead."""
    from spark_rapids_tpu.columnar.column import DeferredCount
    jnp = _jx()
    key = ("cpairs", int(l_idx.shape[0]))
    def build():
        def run(l_idx, r_idx, keep):
            order = compaction_perm(keep, jnp)
            return (jnp.take(l_idx, order), jnp.take(r_idx, order),
                    jnp.sum(keep))

        return run
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    fn = get_or_build("join.compact_pairs", key, build)
    l, r, n = fn(l_idx, r_idx, keep)
    return l, r, DeferredCount(n)


def unmatched_positions(flags, row_count: int):
    """Row positions with no kept match, compacted; returns
    (idx, DeferredCount) — no host sync (see compact_pairs)."""
    from spark_rapids_tpu.columnar.column import DeferredCount
    jnp = _jx()
    bucket = int(flags.shape[0])
    key = ("unmatched", bucket)
    def build():
        def run(flags, row_count):
            rowpos = jnp.arange(bucket, dtype=np.int64)
            want = (~flags) & (rowpos < row_count)
            order = compaction_perm(want, jnp)
            return jnp.take(rowpos, order), jnp.sum(want)

        return run
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    fn = get_or_build("join.unmatched", key, build)
    from spark_rapids_tpu.columnar.column import rc_traceable as _rt2
    idx, n = fn(flags, _rt2(row_count))
    return idx, DeferredCount(n)


def gather_join_output(probe: ColumnarBatch, build: ColumnarBatch,
                       l_map, r_map, count,
                       names: Optional[List[str]] = None,
                       out_bucket: Optional[int] = None,
                       cols: Optional[Sequence[int]] = None
                       ) -> ColumnarBatch:
    """Materializes join output rows: probe columns gathered by ``l_map``,
    build columns by ``r_map``; a negative map entry yields a null row for
    that side (outer-join null extension).  ``cols`` names the output
    columns as ordinals over ``probe.columns + build.columns``, in output
    order (``None``: all of them): a column it leaves out is not
    gathered.  ``count`` may be a :class:`DeferredCount` (no host sync)
    when ``out_bucket`` is given; either map may be ``None``, meaning "all
    null rows for that side" (the constant -1 map is generated inside the
    program — shipping a bucket-sized host constant would cost a real
    transfer)."""
    from spark_rapids_tpu.columnar.column import (DeferredCount,
                                                  rc_traceable)
    jnp = _jx()
    if out_bucket is None:
        out_bucket = bucket_rows(max(int(count), 1))
    # pad maps to a bucketed length so the program caches across batches
    some_map = l_map if l_map is not None else r_map
    maps_bucket = bucket_rows(max(int(some_map.shape[0]), 1))

    def _pad(m):
        if m is None or int(m.shape[0]) == maps_bucket:
            return m
        pad = maps_bucket - int(m.shape[0])
        return jnp.pad(jnp.asarray(m), (0, pad), constant_values=-1)

    l_map, r_map = _pad(l_map), _pad(r_map)
    n_probe = len(probe.columns)
    protos = list(probe.columns) + list(build.columns)
    if cols is not None:
        protos = [protos[o] for o in cols]
    from_build = tuple(o >= n_probe for o in
                       (range(len(protos)) if cols is None else cols))
    key = ("jgather", out_bucket, maps_bucket,
           l_map is None, r_map is None,
           tuple(zip(from_build, (_col_sig(c) for c in protos))))
    def build_fn():
        no_l, no_r = l_map is None, r_map is None

        def rows(m, absent):
            # the map's first ``out_bucket`` entries, by a static slice
            # (-1 past its end): positions past ``count`` are dead rows
            if absent:
                return jnp.full(out_bucket, -1, dtype=np.int64)
            if maps_bucket >= out_bucket:
                return m[:out_bucket]
            return jnp.concatenate(
                [m, jnp.full(out_bucket - maps_bucket, -1, dtype=m.dtype)])

        def run(arrs, l_map, r_map, count):
            live = jnp.arange(out_bucket, dtype=np.int64) < count
            lm, rm = rows(l_map, no_l), rows(r_map, no_r)
            outs = []
            for right, (d, v, ln, ev) in zip(from_build, arrs):
                m = rm if right else lm
                s = jnp.clip(m, 0, d.shape[0] - 1)
                nd = jnp.take(d, s, axis=0)
                nv = jnp.take(v, s, axis=0) & (m >= 0) & live
                nl = None if ln is None else jnp.take(ln, s, axis=0)
                ne = None if ev is None else jnp.take(ev, s, axis=0)
                outs.append((nd, nv, nl, ne))
            return outs

        return run
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    fn = get_or_build("join.gather", key, build_fn)
    arrs = [(c.data, c.validity, c.lengths, c.elem_valid) for c in protos]
    zero = np.zeros(0, np.int64)
    outs = fn(arrs,
              zero if l_map is None else l_map,
              zero if r_map is None else r_map,
              rc_traceable(count))
    if isinstance(count, DeferredCount) and count.is_forced:
        count = int(count)
    from spark_rapids_tpu.columnar.encoding import rewrap_like
    # dictionary payload columns gather their code planes and stay
    # encoded through the join (late materialization)
    return ColumnarBatch([rewrap_like(proto, d, v, count, ln, ev)
                          for (d, v, ln, ev), proto in zip(outs, protos)],
                         count, names)


def concat_matched_unmatched(l, r, n, ul, un):
    """Concatenates the matched-pair maps (l, r, count n) with null-extended
    unmatched probe rows (positions ul, count un) entirely on device:
    returns (l_map, r_map, DeferredCount(total), out_bucket).  The
    fragments keep their kept entries front-compacted, so writing fragment
    2 at traced offset ``n`` overwrites fragment 1's dead tail; positions
    past ``n + un`` are masked by the deferred total downstream."""
    import jax
    from spark_rapids_tpu.columnar.column import DeferredCount, rc_traceable
    jnp = _jx()
    b1, b2 = int(l.shape[0]), int(ul.shape[0])
    out_bucket = bucket_rows(max(b1 + b2, 1))
    key = ("concat_mu", b1, b2)
    def build():
        def run(l, r, n, ul, un):
            lmap = jnp.full(out_bucket, -1, dtype=np.int64)
            rmap = jnp.full(out_bucket, -1, dtype=np.int64)
            lmap = jax.lax.dynamic_update_slice(
                lmap, l.astype(np.int64), (jnp.zeros((), np.int64),))
            rmap = jax.lax.dynamic_update_slice(
                rmap, r.astype(np.int64), (jnp.zeros((), np.int64),))
            lmap = jax.lax.dynamic_update_slice(
                lmap, ul.astype(np.int64), (n.astype(np.int64),))
            rmap = jax.lax.dynamic_update_slice(
                rmap, jnp.full(b2, -1, dtype=np.int64),
                (n.astype(np.int64),))
            return lmap, rmap, n + un
        return run
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    fn = get_or_build("join.concat_maps", key, build)
    jnp_n = jnp.asarray(rc_traceable(n), dtype=np.int64)
    jnp_un = jnp.asarray(rc_traceable(un), dtype=np.int64)
    lmap, rmap, total = fn(l, r, jnp_n, ul, jnp_un)
    return lmap, rmap, DeferredCount(total), out_bucket
