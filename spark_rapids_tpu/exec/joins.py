"""Join execs: shuffled hash join, broadcast hash join, nested-loop join.

Reference: GpuShuffledHashJoinExec / GpuBroadcastHashJoinExecBase /
GpuBroadcastNestedLoopJoinExec over the common core GpuHashJoin
(org/apache/spark/sql/rapids/execution/GpuHashJoin.scala) + JoinGatherer.
The reference streams the probe side against a built hash table and
supports an extra non-equi ``condition`` evaluated per candidate pair (its
AST path); ours evaluates the condition as a fused XLA program over the
padded candidate-pair table (ops/join_ops.py).

Structure per partition (TPU path):
  build side  = concat of the build child's batches, sorted by key hash once
  probe side  = streamed; per batch: candidate ranges -> pair expand+verify
                -> optional condition -> finalize per join type
  right/full outer: build-row matched flags accumulate across probe batches;
                unmatched build rows are emitted after the stream drains
                (correct per-partition because the shuffle hash-partitions
                both sides by the same keys).

Sizing of a hash join's pair table and of the batch it hands on, by what
the join can see in its input (``_TpuJoinCore._join_device``):
  probe bucket > SIZED_MIN_BUCKET (``columnar/column.py``, the one floor
                for every shrink): by the probe's candidate total,
                fetched (one counted scalar sync a probe batch, site
                ``join-size``): the padding of a large probe side costs
                the device seconds, the fetch milliseconds, and every
                operator above runs at the size of what the join kept
  probe bucket <= the floor: speculatively (ops/speculation.py), probe
                bucket x SPECULATIVE_PAIR_HEADROOM and no sync: there the
                padding is cheap and the round trip is not
  replay / speculativeSizing.enabled=false: exactly, a fetch every join
Outer joins append the unmatched probe rows and semi/anti joins answer
per probe row, so those still hand on a probe-sized batch.

Sort-merge join: not built — the reference itself prefers converting SMJ to
shuffled hash join (GpuSortMergeJoinMeta.scala); we always plan hash joins.
"""

from __future__ import annotations

import contextlib
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.aux import transitions as TR
from spark_rapids_tpu.aux.tracing import add_count, span
from spark_rapids_tpu.columnar import column as COL
from spark_rapids_tpu.columnar.batch import (ColumnarBatch, HostColumnarBatch,
                                             batch_from_arrow,
                                             concat_host_batches)
from spark_rapids_tpu.expressions.base import EvalContext, Expression
from spark_rapids_tpu.ops import join_ops as J
from spark_rapids_tpu.ops import speculation
from spark_rapids_tpu.plan.base import BinaryExec, Exec

_PAIR_TYPES = (J.INNER, J.LEFT_OUTER, J.RIGHT_OUTER, J.FULL_OUTER, J.CROSS)

#: defaults for the build-side-swap knobs (spark.rapids.sql.join.
#: buildSideSwap.*); the convert-time values travel on each join
#: INSTANCE (conf must ride the plan, not the process — concurrent
#: sessions with different confs share these modules)
BUILD_SWAP_ENABLED = True
BUILD_SWAP_MAX_BYTES = 256 << 20

#: speculative-join verification headroom: candidate pairs are expanded
#: and verified over ``probe_bucket * HEADROOM`` so collision/null
#: candidates that verification rejects never flag overflow; the output
#: table stays at the probe bucket (post-verify pairs truncate back)
SPECULATIVE_PAIR_HEADROOM = 2


from spark_rapids_tpu.columnar.column import known_empty as _known_empty


def _normalize_how(how: str) -> str:
    h = how.lower().replace("_", "").replace(" ", "")
    return {
        "inner": J.INNER,
        "left": J.LEFT_OUTER, "leftouter": J.LEFT_OUTER,
        "right": J.RIGHT_OUTER, "rightouter": J.RIGHT_OUTER,
        "full": J.FULL_OUTER, "fullouter": J.FULL_OUTER, "outer": J.FULL_OUTER,
        "semi": J.LEFT_SEMI, "leftsemi": J.LEFT_SEMI,
        "anti": J.LEFT_ANTI, "leftanti": J.LEFT_ANTI,
        "cross": J.CROSS,
    }[h]


def expand_struct_key_pairs(left_keys, right_keys, null_safe=None):
    """Struct-CONSTRUCTOR key pairs -> field-wise NULL-SAFE pairs (Spark
    struct equality).  Shared by join construction AND the hash
    partitionings the planner builds above the join (both sides must
    shuffle by the same decomposed keys)."""
    from spark_rapids_tpu.expressions.collections import \
        CreateNamedStruct as _CNS
    ns_in = list(null_safe or [False] * len(list(left_keys)))
    lks, rks, nss = [], [], []
    for lk, rk, ns in zip(list(left_keys), list(right_keys), ns_in):
        if isinstance(lk, _CNS) and isinstance(rk, _CNS) and \
                len(lk.children) == len(rk.children):
            lks.extend(lk.children)
            rks.extend(rk.children)
            nss.extend([True] * len(lk.children))
        else:
            lks.append(lk)
            rks.append(rk)
            nss.append(ns)
    return lks, rks, nss


class _JoinBase(BinaryExec):
    """Shared schema/condition plumbing for all join execs."""

    def __init__(self, left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression], join_type: str,
                 condition: Optional[Expression], left: Exec, right: Exec,
                 null_safe: Optional[Sequence[bool]] = None):
        super().__init__(left, right)
        # struct-CONSTRUCTOR key pairs decompose into field-wise NULL-SAFE
        # pairs (Spark struct equality semantics; constructors are never
        # null themselves) — no device struct plane needed
        lks, rks, nss = expand_struct_key_pairs(left_keys, right_keys,
                                                null_safe)
        self.left_keys = lks
        self.right_keys = rks
        self.join_type = join_type
        self.condition = condition
        self.null_safe = tuple(nss)
        if len(self.left_keys) != len(self.right_keys):
            raise ValueError("left/right key counts differ")
        for lk, rk in zip(self.left_keys, self.right_keys):
            if str(lk.data_type) != str(rk.data_type):
                raise ValueError(
                    f"join key type mismatch: {lk.data_type} vs "
                    f"{rk.data_type}; add explicit casts")
            if lk.data_type.is_nested:
                raise TypeError(
                    f"equi-join key of type {lk.data_type.simple_name} "
                    "is not supported (arrays/structs/maps join as "
                    "payload, not keys)")

    @property
    def schema(self) -> T.StructType:
        ls, rs = self.left.schema, self.right.schema
        if self.join_type in (J.LEFT_SEMI, J.LEFT_ANTI):
            return ls
        lnull = self.join_type in (J.RIGHT_OUTER, J.FULL_OUTER)
        rnull = self.join_type in (J.LEFT_OUTER, J.FULL_OUTER)
        fields = [T.StructField(f.name, f.data_type, f.nullable or lnull)
                  for f in ls.fields]
        fields += [T.StructField(f.name, f.data_type, f.nullable or rnull)
                   for f in rs.fields]
        return T.StructType(fields)

    @property
    def _out_names(self) -> List[str]:
        return self.schema.names

    def node_desc(self):
        keys = ", ".join(k.sql() for k in self.left_keys)
        cond = f", cond={self.condition.sql()}" if self.condition is not None \
            else ""
        return (f"{self.name}[{self.join_type}, keys=[{keys}]{cond}]")


# ---------------------------------------------------------------------------
# CPU core (the differential oracle): arrow hash join for the pair set,
# numpy for finalization
# ---------------------------------------------------------------------------

def _empty_host(schema: T.StructType) -> HostColumnarBatch:
    import pyarrow as pa
    arrays = [pa.array([], type=T.to_arrow(f.data_type))
              for f in schema.fields]
    return batch_from_arrow(pa.Table.from_arrays(arrays, names=schema.names))


def _concat_or_empty(batches: List[HostColumnarBatch],
                     schema: T.StructType) -> HostColumnarBatch:
    batches = [b for b in batches if b.row_count > 0]
    if not batches:
        return _empty_host(schema)
    return concat_host_batches(batches)


def _encode_key_array(hc, null_safe: bool):
    """HostColumn -> arrow array usable as an Acero hash-join key with Spark
    match semantics (NaN==NaN, -0.0==0.0 via bit canonicalization)."""
    import pyarrow as pa
    import pyarrow.compute as pc
    dt = hc.data_type
    arr = hc.arrow
    if isinstance(dt, (T.FloatType, T.DoubleType)):
        bits = np.dtype(np.int32) if isinstance(dt, T.FloatType) \
            else np.dtype(np.int64)
        x = hc.data_np().copy()
        x[x == 0] = 0.0                       # -0.0 -> 0.0
        x[np.isnan(x)] = np.nan               # canonical NaN bits
        arr = pa.array(x.view(bits), mask=~hc.validity_np())
    if isinstance(dt, (T.DateType, T.TimestampType)):
        # equality on temporals == equality on their integer storage;
        # the null-safe filler below cannot be cast to temporal types
        storage = pa.int32() if isinstance(dt, T.DateType) else pa.int64()
        arr = arr.view(storage) if hasattr(arr, "view") else arr.cast(storage)
    if null_safe:
        nulls = pc.is_null(arr)
        if pa.types.is_string(arr.type) or pa.types.is_binary(arr.type):
            filler = pa.scalar("", type=arr.type)
        else:
            filler = pa.scalar(0, type=pa.int8()).cast(arr.type)
        return pc.coalesce(arr, filler), nulls
    return arr, None


def _cpu_key_pairs(lkb: HostColumnarBatch, rkb: HostColumnarBatch,
                   null_safe: Tuple[bool, ...]):
    """All key-equal (lidx, ridx) pairs via an arrow inner hash join."""
    import pyarrow as pa
    key_names: List[str] = []

    def key_table(kb, idx_name):
        arrays, names = [], []
        for i, c in enumerate(kb.columns):
            arr, nulls = _encode_key_array(c, null_safe[i])
            arrays.append(arr)
            names.append(f"k{i}")
            if nulls is not None:
                arrays.append(nulls)
                names.append(f"k{i}n")
        arrays.append(pa.array(np.arange(kb.row_count, dtype=np.int64)))
        names.append(idx_name)
        return pa.Table.from_arrays(arrays, names=names), \
            [n for n in names if n != idx_name]

    lt, key_names = key_table(lkb, "__lidx")
    rt, _ = key_table(rkb, "__ridx")
    joined = lt.join(rt, keys=key_names, join_type="inner")
    lidx = joined.column("__lidx").to_numpy(zero_copy_only=False)
    ridx = joined.column("__ridx").to_numpy(zero_copy_only=False)
    return lidx.astype(np.int64), ridx.astype(np.int64)


def _take_with_nulls(hb: HostColumnarBatch, idx: np.ndarray,
                     names: List[str]):
    """Arrow take where a negative index produces an all-null row."""
    import pyarrow as pa
    mask = idx < 0
    safe = np.where(mask, 0, idx)
    indices = pa.array(safe, mask=mask)
    tab = pa.Table.from_arrays([c.arrow for c in hb.columns],
                               names=[f"c{i}" for i in
                                      range(hb.num_columns)])
    taken = tab.take(indices)
    cols = [batch_from_arrow(taken).columns[i]
            for i in range(hb.num_columns)]
    return cols


def _cpu_assemble(left: HostColumnarBatch, right: HostColumnarBatch,
                  lmap: np.ndarray, rmap: np.ndarray,
                  names: List[str]) -> HostColumnarBatch:
    cols = _take_with_nulls(left, lmap, names) + \
        _take_with_nulls(right, rmap, names)
    return HostColumnarBatch(cols, len(lmap), names)


class _CpuJoinCore(_JoinBase):
    """Join over fully-materialized host sides (per partition)."""

    def _pair_condition_keep(self, left, right, lidx, ridx):
        from spark_rapids_tpu.expressions.evaluator import host_batch_tcols
        pair = _cpu_assemble(left, right, lidx, ridx,
                             [f"p{i}" for i in
                              range(left.num_columns + right.num_columns)])
        cols = host_batch_tcols(pair)
        ctx = EvalContext(cols, "cpu", pair.row_count)
        pred = self.condition.eval_cpu(ctx)
        if pred.is_scalar:
            ok = bool(pred.valid) and bool(pred.data)
            return np.full(len(lidx), ok)
        keep = np.asarray(pred.data, dtype=bool) & np.asarray(pred.valid)
        return keep[:len(lidx)]

    def _join_host(self, left: HostColumnarBatch,
                   right: HostColumnarBatch) -> HostColumnarBatch:
        from spark_rapids_tpu.expressions.evaluator import eval_exprs_cpu
        jt = self.join_type
        nl, nr = left.row_count, right.row_count
        if jt == J.CROSS or not self.left_keys:
            lidx = np.repeat(np.arange(nl, dtype=np.int64), nr)
            ridx = np.tile(np.arange(nr, dtype=np.int64), nl)
        else:
            lkb = eval_exprs_cpu(self.left_keys, left,
                                 [f"k{i}" for i in
                                  range(len(self.left_keys))])
            rkb = eval_exprs_cpu(self.right_keys, right,
                                 [f"k{i}" for i in
                                  range(len(self.right_keys))])
            lidx, ridx = _cpu_key_pairs(lkb, rkb, self.null_safe)
        if self.condition is not None and len(lidx):
            keep = self._pair_condition_keep(left, right, lidx, ridx)
            lidx, ridx = lidx[keep], ridx[keep]
        names = self._out_names
        if jt in (J.INNER, J.CROSS):
            return _cpu_assemble(left, right, lidx, ridx, names)
        if jt in (J.LEFT_SEMI, J.LEFT_ANTI):
            matched = np.zeros(nl, dtype=bool)
            matched[lidx] = True
            rows = np.flatnonzero(matched if jt == J.LEFT_SEMI else ~matched)
            cols = _take_with_nulls(left, rows.astype(np.int64), names)
            return HostColumnarBatch(cols, len(rows), names)
        parts_l, parts_r = [lidx], [ridx]
        if jt in (J.LEFT_OUTER, J.FULL_OUTER):
            matched = np.zeros(nl, dtype=bool)
            matched[lidx] = True
            ul = np.flatnonzero(~matched).astype(np.int64)
            parts_l.append(ul)
            parts_r.append(np.full(len(ul), -1, dtype=np.int64))
        if jt in (J.RIGHT_OUTER, J.FULL_OUTER):
            matched = np.zeros(nr, dtype=bool)
            matched[ridx] = True
            ur = np.flatnonzero(~matched).astype(np.int64)
            parts_l.append(np.full(len(ur), -1, dtype=np.int64))
            parts_r.append(ur)
        lmap = np.concatenate(parts_l)
        rmap = np.concatenate(parts_r)
        return _cpu_assemble(left, right, lmap, rmap, names)


# ---------------------------------------------------------------------------
# TPU core
# ---------------------------------------------------------------------------

def _empty_device(schema: T.StructType) -> ColumnarBatch:
    return _empty_host(schema).to_device()


def _chain_then_close(consumed, it):
    """Replays already-sampled probe batches then continues the live
    stream; closing this generator early closes the underlying stream
    (the swap-sampling path must not strand a half-drained child)."""
    from spark_rapids_tpu.plan.base import close_iter
    try:
        yield from consumed
        yield from it
    finally:
        close_iter(it)


@contextlib.contextmanager
def _build_once(lock, **attrs):
    """Holds a broadcast join's build lock, under a ``broadcast.build``
    span (``None``: the join of one partition pair, whose build cache no
    other task sees: no lock, no span).  A task that has to wait for the
    lock drops its device admission first: the builder may need the
    permit."""
    if lock is None:
        yield
        return
    if not lock.acquire(blocking=False):
        from spark_rapids_tpu.plan.base import release_semaphore_for_wait
        release_semaphore_for_wait()
        lock.acquire()
    try:
        with span("broadcast.build", **attrs):
            yield
    finally:
        lock.release()


class _TpuJoinCore(_JoinBase):
    """Streamed probe vs built side on device (see module docstring)."""

    is_device = True

    #: conf-at-convert-time build-side-swap knobs
    #: (spark.rapids.sql.join.buildSideSwap.*); ``None`` falls back to
    #: the module defaults so directly-driven test execs keep working.
    #: Instance-threaded on purpose: per-query conf travels with the
    #: plan, never through process-global module state (concurrent
    #: sessions with different confs share this module)
    build_swap_enabled: Optional[bool] = None
    build_swap_max_bytes: Optional[int] = None

    def _augment_keys(self, batch: ColumnarBatch, keys,
                      enc_keys=None) -> ColumnarBatch:
        """Appends evaluated key columns; returns (augmented, ordinals).

        ``enc_keys`` (per key: Dictionary | None) marks keys that join
        in CODE SPACE: both sides carry the SAME dictionary, so equality
        on int32 codes is equality on values — the hash/probe machinery
        sees one int word instead of string word planes."""
        from spark_rapids_tpu.columnar import encoding as ENC
        from spark_rapids_tpu.expressions.evaluator import eval_exprs_tpu
        if not keys:
            return batch, ()
        enc_keys = enc_keys or [None] * len(keys)
        plain = [k for k, d in zip(keys, enc_keys) if d is None]
        kb_cols = iter(eval_exprs_tpu(plain, batch).columns) if plain \
            else iter(())
        key_cols = [ENC.codes_key_column(batch, k) if d is not None
                    else next(kb_cols)
                    for k, d in zip(keys, enc_keys)]
        aug = ColumnarBatch(list(batch.columns) + key_cols,
                            batch.row_count)
        ords = tuple(range(batch.num_columns,
                           batch.num_columns + len(keys)))
        return aug, ords

    def _condition_keep(self, probe_pay, build_pay, l_idx, r_idx, keep,
                        pair_bucket):
        """Applies the non-equi condition over the padded pair table."""
        from spark_rapids_tpu.expressions.base import valid_array
        from spark_rapids_tpu.expressions.evaluator import device_batch_tcols
        pair = J.gather_join_output(probe_pay, build_pay, l_idx, r_idx,
                                    pair_bucket)
        cols = device_batch_tcols(pair)
        ctx = EvalContext(cols, "tpu", pair.bucket)
        pred = self.condition.eval_tpu(ctx)
        ok = valid_array(pred, ctx)
        if pred.is_scalar:
            ok = ok & bool(pred.data)
        else:
            ok = ok & pred.data
        # pair table rows map 1:1 to pair positions (same bucket)
        return keep & ok[:keep.shape[0]]

    @staticmethod
    def _concat_build(build_batches: List[ColumnarBatch],
                      schema: T.StructType) -> ColumnarBatch:
        """The build side as one batch."""
        from spark_rapids_tpu.columnar import encoding as ENC
        from spark_rapids_tpu.ops.batch_ops import concat_batches
        build_batches = [ENC.materialize_rle_batch(b, site="join")
                         for b in build_batches
                         if not _known_empty(b.row_count)]
        build = concat_batches(build_batches) if build_batches else \
            _empty_device(schema)
        # concat_batches passes a single input through unchanged —
        # never mutate it (it may be a shared/cached batch); rewrap
        # to drop names instead
        return ColumnarBatch(build.columns, build.row_count)

    def _join_device(self, probe_batches: Iterator[ColumnarBatch],
                     build_batches: List[ColumnarBatch],
                     build_cache: Optional[dict] = None,
                     swapped: bool = False):
        """Yields output batches for one partition.  ``build_cache`` (dict)
        carries the concatenated/keyed/sorted build side across calls —
        broadcast joins pass a per-exec dict so the build work happens once
        for all probe partitions.

        ``swapped=True`` (inner equi-joins only): the PROBE stream is the
        RIGHT child and the build side the LEFT — the runtime build-side
        choice (reference: Spark/GpuShuffledHashJoinExec pick the smaller
        side to build; our planner joins in SQL order, which puts fact
        tables on the build side in star queries).  Output column order
        stays left-then-right via argument swap at gather time."""
        from spark_rapids_tpu.columnar import encoding as ENC
        jt = self.join_type
        names = self._out_names
        ls, rs = self.left.schema, self.right.schema
        probe_keys = self.right_keys if swapped else self.left_keys
        build_keys = self.left_keys if swapped else self.right_keys
        cache = build_cache if build_cache is not None else {}
        # a broadcast join's cache is shared by its probe tasks: what a
        # task finds missing it builds under the join's lock, so sibling
        # tasks that arrive together build once and not once each
        lock = cache.get("lock")
        use_hash = bool(self.left_keys) and jt != J.CROSS
        if "build" in cache:
            build = cache["build"]
        else:
            build = cache["build"] = self._concat_build(
                build_batches, ls if swapped else rs)
        build_key_dicts = ENC.join_key_dicts(build, build_keys) \
            if use_hash else []
        # augmented build sides keyed by the code-space signature (one
        # per dictionary combination a probe stream presents), each with
        # its own string-width sub-cache
        aug_cache = cache.setdefault("aug", {})
        build_matched = None
        semi_anti = jt in (J.LEFT_SEMI, J.LEFT_ANTI)
        empty_right = ColumnarBatch([], 0) if semi_anti else None
        for probe in probe_batches:
            if _known_empty(probe.row_count):
                continue
            probe = ENC.materialize_rle_batch(probe, site="join")
            if use_hash:
                # a key joins in code space only when BOTH sides carry
                # the same dictionary; otherwise it falls back to value
                # comparison (the probe-side key eval materializes)
                probe_dicts = ENC.join_key_dicts(probe, probe_keys)
                enc_keys = [bd if (bd is not None and pd is not None and
                                   bd.fingerprint == pd.fingerprint)
                            else None
                            for bd, pd in zip(build_key_dicts,
                                              probe_dicts)]
                enc_sig = tuple(None if d is None else d.fingerprint
                                for d in enc_keys)
                probe_aug, probe_ords = self._augment_keys(probe,
                                                           probe_keys,
                                                           enc_keys)
                pk = [probe_aug.columns[i] for i in probe_ords]
                wkey = tuple(J._n_value_words(c) for c in pk)
                entry = aug_cache.get(enc_sig)
                if entry is None or wkey not in entry[1]:
                    with _build_once(lock, step="key"):
                        entry = aug_cache.get(enc_sig)
                        if entry is None:
                            entry = (self._augment_keys(build, build_keys,
                                                        enc_keys), {})
                            aug_cache[enc_sig] = entry
                        if wkey not in entry[1]:
                            (build_aug, build_ords), _ = entry
                            entry[1][wkey] = J.build_side(build_aug,
                                                          build_ords, pk)
                (build_aug, build_ords), built_by_widths = entry
                built = built_by_widths[wkey]
                lo, counts, offsets, total = J._probe_ranges(pk, built)
                # the gathers a probe row made to find its range
                add_count("probe_gather_rounds", J.PROBE_GATHER_ROUNDS)
                spec = speculation.active()
                sized = probe_aug.bucket > COL.SIZED_MIN_BUCKET
                if spec is not None and not sized:
                    # optimistic OUTPUT table = probe bucket (exact for
                    # the FK->PK joins that dominate star schemas: <=1
                    # build match per probe row), but candidates are
                    # expanded + verified over a HEADROOM window first:
                    # the candidates verification rejects must not flag
                    # overflow (they used to trigger a silent full-query
                    # exact replay).  Those are null keys and the other
                    # live build rows of a probe row's table slot: the
                    # latter raise ``total`` by at most probe rows /
                    # J._TABLE_LOAD on average (additive: a slot holds
                    # live build rows / slots <= 1 / _TABLE_LOAD rows
                    # whatever the join selects), an eighth of the probe
                    # bucket where the window leaves a whole one.
                    # Overflow is decided on the POST-VERIFY pair count
                    # against the probe bucket (below, after compact);
                    # only a candidate total beyond even the headroom
                    # window — unverifiable without a sizing sync —
                    # forces the replay directly
                    out_bucket = probe_aug.bucket
                    verify_bucket = out_bucket * SPECULATIVE_PAIR_HEADROOM
                    spec.add(total > verify_bucket)
                else:
                    # the per-join sizing sync: the pair table and the
                    # batch handed on hold every candidate, so nothing
                    # can overflow and nothing is truncated below.  A
                    # large probe's table never goes under the floor;
                    # the replay of a small one is exact
                    total = TR.sync_int(total, site="join-size")
                    add_count("sized_joins", 1)
                    out_bucket = J.bucket_rows(max(total, 1))
                    if sized:
                        out_bucket = max(out_bucket, COL.SIZED_MIN_BUCKET)
                    verify_bucket = out_bucket
                # the rows of the pair table the device expands and
                # verifies, whatever the join selects
                add_count("pair_rows_padded", verify_bucket)
                l_idx, r_idx, keep, pair_bucket = J._expand_verify(
                    probe_aug, probe_ords, built, self.null_safe, lo,
                    offsets, total, verify_bucket)
            else:
                l_idx, r_idx, keep, pair_bucket = J.cross_pairs(probe, build)
            probe_pay = probe
            build_pay = build
            if self.condition is not None:
                keep = self._condition_keep(probe_pay, build_pay, l_idx,
                                            r_idx, keep, pair_bucket)
            if jt in (J.RIGHT_OUTER, J.FULL_OUTER):
                bm = J.matched_flags(r_idx, keep, build.bucket)
                build_matched = bm if build_matched is None \
                    else build_matched | bm
            if semi_anti:
                flags = J.matched_flags(l_idx, keep, probe.bucket)
                if jt == J.LEFT_ANTI:
                    rows, n = J.unmatched_positions(flags, probe.row_count)
                else:
                    rows, n = J.unmatched_positions(~flags, probe.row_count)
                yield J.gather_join_output(probe_pay, empty_right,
                                           rows, None, n, names,
                                           out_bucket=probe.bucket)
                continue
            l, r, n = J.compact_pairs(l_idx, r_idx, keep)
            if use_hash and pair_bucket > out_bucket:
                # speculative sizing only (a table sized by its fetched
                # total IS its output bucket).  The post-verify overflow
                # check: only REAL pairs (after key verification AND the
                # non-equi condition) must fit the optimistic output
                # bucket; the verified headroom window then truncates
                # back so output batches keep the probe-bucket footprint
                from spark_rapids_tpu.columnar.column import (
                    DeferredCount as _DC, rc_traceable as _rt)
                from spark_rapids_tpu.columnar.column import _jnp as _j
                jnp = _j()
                nt = jnp.asarray(_rt(n))
                spec.add(nt > out_bucket)
                l, r = l[:out_bucket], r[:out_bucket]
                n = _DC(jnp.minimum(nt, out_bucket))
                pair_bucket = out_bucket
            if jt in (J.LEFT_OUTER, J.FULL_OUTER):
                flags = J.matched_flags(l_idx, keep, probe.bucket)
                ul, un = J.unmatched_positions(flags, probe.row_count)
                lmap, rmap, total_out, ob = J.concat_matched_unmatched(
                    l, r, n, ul, un)
                yield J.gather_join_output(probe_pay, build_pay, lmap, rmap,
                                           total_out, names, out_bucket=ob)
            elif swapped:
                # emit left-then-right: build side IS the left child here
                yield J.gather_join_output(build_pay, probe_pay, r, l, n,
                                           names, out_bucket=pair_bucket)
            else:
                yield J.gather_join_output(probe_pay, build_pay, l, r, n,
                                           names, out_bucket=pair_bucket)
        # outer-join: unmatched build rows after the probe stream drains
        if jt in (J.RIGHT_OUTER, J.FULL_OUTER):
            if build_matched is None:
                from spark_rapids_tpu.columnar.column import _jnp
                jnp = _jnp()
                build_matched = jnp.zeros(build.bucket, dtype=bool)
            ub, un = J.unmatched_positions(build_matched, build.row_count)
            probe_empty = _empty_device(ls)
            yield J.gather_join_output(probe_empty, build, None, ub, un,
                                       names, out_bucket=build.bucket)


# ---------------------------------------------------------------------------
# Concrete execs
# ---------------------------------------------------------------------------

def _check_copartitioned(join) -> None:
    """Partition i of the left side pairs with partition i of the right:
    the contract every producer of a shuffled join upholds — the eager
    exchanges, AQE's COORDINATED readers, and the distribution pass's
    elision (which only removes an exchange whose child provably
    delivers the same placement).  A count mismatch here means a pass
    broke that contract; failing loudly beats joining partition i
    against an unrelated partition i and returning silently wrong
    rows (plan/verify.py's distribution-consistency check is the
    observe-only twin of this guard)."""
    ln, rn = join.left.num_partitions, join.right.num_partitions
    if ln != rn:
        raise ValueError(
            f"{join.name} sides are not co-partitioned: left has {ln} "
            f"partition(s), right has {rn} — partition pairing would "
            "silently drop or mis-match rows")


class CpuShuffledHashJoinExec(_CpuJoinCore):
    """Both children hash-partitioned by the join keys; joins partition-wise
    (reference: GpuShuffledHashJoinExec)."""

    @property
    def num_partitions(self):
        return self.left.num_partitions

    def execute_partition(self, pidx):
        _check_copartitioned(self)
        left = _concat_or_empty(list(self.left.execute_partition(pidx)),
                                self.left.schema)
        right = _concat_or_empty(list(self.right.execute_partition(pidx)),
                                 self.right.schema)
        out = self._join_host(left, right)
        if out.row_count:
            yield out


class TpuShuffledHashJoinExec(_TpuJoinCore):
    @property
    def num_partitions(self):
        return self.left.num_partitions

    def _maybe_swapped(self, pidx):
        build = list(self.right.execute_partition(pidx))
        return self._maybe_swapped_with(build, pidx)

    def _maybe_swapped_with(self, build, pidx):
        """Runtime build-side choice for inner equi-joins: build on the
        smaller side (reference: GpuShuffledHashJoinExec's build side is
        planner-chosen by size; our SQL planner joins in source order,
        which would build on the FACT side in star queries — wrong both
        for memory and for the speculative pair sizing)."""
        bb = sum(b.nbytes() for b in build)
        if self.swap_enabled() and self.join_type == J.INNER and \
                self.condition is None and \
                self.left_keys and bb <= self.swap_max_bytes():
            # first-batch sampling: probe batches are pulled only until
            # their running bytes EXCEED the build side (probe provably
            # bigger -> no swap) or the stream ends first (whole probe is
            # smaller -> build on it).  Weighing the swap materializes at
            # most ~build-side bytes (itself <= buildSideSwap.maxBytes),
            # never the whole probe partition
            it = self.left.execute_partition(pidx)
            sampled = []
            pb = 0
            for b in it:
                sampled.append(b)
                pb += b.nbytes()
                if pb > bb:
                    break
            if pb <= bb:      # stream drained: full probe is the smaller side
                return iter(build), sampled, True
            return _chain_then_close(sampled, it), build, False
        return self.left.execute_partition(pidx), build, False

    def swap_enabled(self) -> bool:
        bs = self.build_swap_enabled
        return BUILD_SWAP_ENABLED if bs is None else bs

    def swap_max_bytes(self) -> int:
        mb = self.build_swap_max_bytes
        return BUILD_SWAP_MAX_BYTES if mb is None else mb

    def execute_partition(self, pidx):
        _check_copartitioned(self)
        probe, build, swapped = self._maybe_swapped(pidx)
        yield from self._join_device(probe, build, swapped=swapped)


class CpuBroadcastHashJoinExec(_CpuJoinCore):
    """Build side = every partition of the right child, materialized once
    (reference: GpuBroadcastHashJoinExecBase; the broadcast is a no-op
    in-process).  Right/full outer are not planned broadcast (the build side
    match flags would span probe partitions), matching Spark's rule that the
    broadcast side must not be the outer side."""

    @property
    def num_partitions(self):
        return self.left.num_partitions

    def _build_all(self):
        if getattr(self, "_built_host", None) is None:
            # concurrent probe tasks must not double-build; drop device
            # admission before blocking on the lock (the builder may need it)
            with _build_once(self._exec_lock, step="pull",
                             partitions=self.right.num_partitions):
                if getattr(self, "_built_host", None) is None:
                    bs = []
                    for p in range(self.right.num_partitions):
                        bs.extend(self.right.execute_partition(p))
                    self._built_host = _concat_or_empty(bs,
                                                        self.right.schema)
                    add_count("broadcast_builds", 1)
        return self._built_host

    def execute_partition(self, pidx):
        left = _concat_or_empty(list(self.left.execute_partition(pidx)),
                                self.left.schema)
        out = self._join_host(left, self._build_all())
        if out.row_count:
            yield out


class TpuBroadcastHashJoinExec(_TpuJoinCore):
    @property
    def num_partitions(self):
        return self.left.num_partitions

    def execute_partition(self, pidx):
        # the build cache persists across probe partitions: the broadcast
        # side is pulled and concatenated exactly once, by the first probe
        # task to arrive, and keyed and hash-sorted once for each key
        # layout a probe presents (``_join_device``), both under the
        # join's lock: sibling tasks that arrive together wait for the one
        # build (admission dropped first so the builder can acquire it)
        cache = getattr(self, "_build_cache", None)
        if cache is None or "build" not in cache:
            with _build_once(self._exec_lock, step="pull",
                             partitions=self.right.num_partitions):
                cache = getattr(self, "_build_cache", None)
                if cache is None:
                    cache = self._build_cache = {"lock": self._exec_lock}
                if "build" not in cache:
                    bs = []
                    for p in range(self.right.num_partitions):
                        bs.extend(self.right.execute_partition(p))
                    cache["build"] = self._concat_build(bs,
                                                        self.right.schema)
                    add_count("broadcast_builds", 1)
        yield from self._join_device(self.left.execute_partition(pidx),
                                     [], cache)


class CpuBroadcastNestedLoopJoinExec(CpuBroadcastHashJoinExec):
    """Condition-only / cross joins (reference:
    GpuBroadcastNestedLoopJoinExecBase): no keys, every pair considered."""


class TpuBroadcastNestedLoopJoinExec(TpuBroadcastHashJoinExec):
    pass


# plan-rewrite registration (reference: GpuOverrides BroadcastHashJoinExec /
# ShuffledHashJoinExec / BroadcastNestedLoopJoinExec rules :4117-4260)
from spark_rapids_tpu.plan.overrides import register_exec  # noqa: E402


def _join_exprs(p: _JoinBase):
    out = list(p.left_keys) + list(p.right_keys)
    if p.condition is not None:
        out.append(p.condition)
    return out


from spark_rapids_tpu.plan import typechecks as TS  # noqa: E402


def _tag_join_keys(m):
    TS.no_array_keys(list(m.plan.left_keys) + list(m.plan.right_keys), m,
                     "join key")


def _reg(cpu_cls, tpu_cls, desc):
    register_exec(
        cpu_cls,
        convert=lambda p, m: tpu_cls(p.left_keys, p.right_keys, p.join_type,
                                     p.condition, p.children[0],
                                     p.children[1], p.null_safe),
        sig=TS.BASIC_WITH_ARRAYS,
        exprs_of=_join_exprs,
        extra_tag=_tag_join_keys,
        desc=desc)


def _convert_shuffled(p, m):
    """Shuffled joins convert to the sub-partition-capable device join;
    below the size threshold it behaves exactly like the plain one."""
    from spark_rapids_tpu import config as C
    out = TpuSubPartitionHashJoinExec(p.left_keys, p.right_keys,
                                      p.join_type, p.condition,
                                      p.children[0], p.children[1],
                                      p.null_safe)
    out.subpartition_threshold = C.parse_bytes(
        m.conf.get(C.JOIN_SUBPARTITION_THRESHOLD.key))
    out.num_subpartitions = int(m.conf.get(C.JOIN_NUM_SUBPARTITIONS.key))
    # round-5 behavior knobs ride the INSTANCE (set from meta.conf at
    # convert time) — concurrent sessions must not race module globals
    out.build_swap_enabled = bool(
        m.conf.get(C.JOIN_BUILD_SWAP_ENABLED.key))
    out.build_swap_max_bytes = C.parse_bytes(
        m.conf.get(C.JOIN_BUILD_SWAP_MAX_BYTES.key))
    return out


register_exec(CpuShuffledHashJoinExec, convert=_convert_shuffled,
              sig=TS.BASIC_WITH_ARRAYS,
              exprs_of=_join_exprs,
              extra_tag=_tag_join_keys,
              desc="hash join over shuffled children (size-adaptive "
                   "sub-partitioning)")
_reg(CpuBroadcastHashJoinExec, TpuBroadcastHashJoinExec,
     "broadcast hash join")
_reg(CpuBroadcastNestedLoopJoinExec, TpuBroadcastNestedLoopJoinExec,
     "broadcast nested loop join")


# ---------------------------------------------------------------------------
# sub-partitioned join for oversized inputs (reference:
# GpuSubPartitionHashJoin.scala — when the build side cannot fit the memory
# budget, re-hash BOTH sides with a fresh seed into buckets and join each
# bucket pair independently; rows of one key land in exactly one bucket)
# ---------------------------------------------------------------------------

_SUBPART_SEED = 1999


def _subpartition_ids_device(batch, keys, k):
    from spark_rapids_tpu.columnar.column import _jnp
    from spark_rapids_tpu.expressions.evaluator import device_batch_tcols
    from spark_rapids_tpu.expressions.hashing import Murmur3Hash
    jnp = _jnp()
    ctx = EvalContext(device_batch_tcols(batch), "tpu", batch.bucket)
    h = Murmur3Hash(*keys, seed=_SUBPART_SEED).eval_tpu(ctx)
    r = h.data.astype(np.int32) % np.int32(k)
    return jnp.where(r < 0, r + k, r)


def _subpartition_device(batches, keys, k):
    """Splits device batches into k bucket lists by re-hash of the keys."""
    from spark_rapids_tpu.columnar.column import _jnp
    from spark_rapids_tpu.ops.batch_ops import compact_batch
    jnp = _jnp()
    buckets = [[] for _ in range(k)]
    for b in batches:
        pids = _subpartition_ids_device(b, keys, k)
        live = jnp.arange(b.bucket) < b.row_count
        for i in range(k):
            sub = compact_batch(b, (pids == i) & live)
            buckets[i].append(sub)
    return buckets


def _subpartition_host(batches, keys, k, schema):
    import pyarrow as pa
    from spark_rapids_tpu.columnar.batch import batch_from_arrow
    from spark_rapids_tpu.expressions.evaluator import host_batch_tcols
    from spark_rapids_tpu.expressions.hashing import Murmur3Hash
    buckets = [[] for _ in range(k)]
    for hb in batches:
        ctx = EvalContext(host_batch_tcols(hb), "cpu", hb.row_count)
        h = Murmur3Hash(*keys, seed=_SUBPART_SEED).eval_cpu(ctx)
        pids = np.mod(h.data.astype(np.int64), k).astype(np.int64)
        tab = pa.Table.from_batches([hb.to_arrow()])
        for i in range(k):
            idx = np.flatnonzero(pids == i)
            if len(idx):
                buckets[i].append(
                    batch_from_arrow(tab.take(pa.array(idx))))
    return buckets


class _SubPartitionMixin:
    """Adds size-gated sub-partitioning to the shuffled joins."""

    subpartition_threshold: int = 1 << 30
    num_subpartitions: int = 16

    def _build_oversized(self, build_batches) -> bool:
        total = sum(b.nbytes() if hasattr(b, "nbytes") else 0
                    for b in build_batches)
        return total > self.subpartition_threshold


class CpuSubPartitionHashJoinExec(_SubPartitionMixin, CpuShuffledHashJoinExec):
    """Host variant (oracle): always joins through the bucket machinery."""

    def execute_partition(self, pidx):
        _check_copartitioned(self)
        left = list(self.left.execute_partition(pidx))
        right = list(self.right.execute_partition(pidx))
        if not self._build_oversized(right):
            lb = _concat_or_empty(left, self.left.schema)
            rb = _concat_or_empty(right, self.right.schema)
            out = self._join_host(lb, rb)
            if out.row_count:
                yield out
            return
        k = self.num_subpartitions
        lbuckets = _subpartition_host(left, self.left_keys, k,
                                      self.left.schema)
        rbuckets = _subpartition_host(right, self.right_keys, k,
                                      self.right.schema)
        for i in range(k):
            lb = _concat_or_empty(lbuckets[i], self.left.schema)
            rb = _concat_or_empty(rbuckets[i], self.right.schema)
            if lb.row_count == 0 and rb.row_count == 0:
                continue
            out = self._join_host(lb, rb)
            if out.row_count:
                yield out


class TpuSubPartitionHashJoinExec(_SubPartitionMixin, TpuShuffledHashJoinExec):
    def execute_partition(self, pidx):
        _check_copartitioned(self)
        build = list(self.right.execute_partition(pidx))
        if not self._build_oversized(build):
            probe, build, swapped = self._maybe_swapped_with(build, pidx)
            yield from self._join_device(probe, build, swapped=swapped)
            return
        k = self.num_subpartitions
        probe = list(self.left.execute_partition(pidx))
        lbuckets = _subpartition_device(probe, self.left_keys, k)
        rbuckets = _subpartition_device(build, self.right_keys, k)
        for i in range(k):
            yield from self._join_device(iter(lbuckets[i]), rbuckets[i])


register_exec(CpuSubPartitionHashJoinExec, convert=_convert_shuffled,
              sig=TS.BASIC_WITH_ARRAYS,
              exprs_of=_join_exprs,
              extra_tag=_tag_join_keys,
              desc="explicit sub-partitioned hash join")
