"""Whole-stage fusion execs.

One jitted XLA program per (stage signature, input shapes) covering a
maximal chain of device-side narrow ops — filters and projections — plus,
when the stage feeds a hash aggregate, the aggregate's per-batch update
pass.  Inside a fused stage filters never compact: they AND into a
selection mask that the terminal consumes (reductions mask by it; the
compact terminal sorts the row positions once and gathers by them).  This
removes whole kernel dispatches and all intermediate HBM materialization.

Sizing of the batch a stage with a filter hands on, by what the stage can
see of its input (``TpuFusedStageExec``):
  batch bucket > SIZED_MIN_BUCKET (``columnar/column.py``): by the
                filter's live count, fetched (one counted scalar sync a
                batch, site ``stage-size``, counter ``sized_stages``).
                ``fused.stage`` runs the chain over the input's bucket and
                returns its planes uncompacted, the compaction's
                permutation and the count; ``fused.compact`` then gathers
                every plane at ``max(bucket_rows(count), floor)`` rows: a
                gathered row is what the terminal costs, the fetch
                milliseconds, and every operator above (a join's build
                side, a roll-up's fan-out) runs at the size of what the
                filter kept.  The row count handed on is known
  batch bucket <= the floor: one program with the compact terminal
                inside, no sync, a deferred count, the input's bucket:
                there the padding is cheap and the round trip is not
A stage of projections alone keeps every row and is never sized.

The reference dispatches one cuDF kernel per operator and cannot do this
(GpuProjectExec -> columnarEval chains, basicPhysicalOperators.scala:350);
whole-stage fusion is the structural advantage of tracing compilation, and
is this engine's analog of Spark's whole-stage codegen (which the
reference explicitly replaces with columnar execution).
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import column as COL
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import (DeferredCount, DeviceColumn,
                                              rc_traceable)
from spark_rapids_tpu.expressions.base import EvalContext, Expression, TCol, \
    expr_key, valid_array
from spark_rapids_tpu.plan.base import Exec, UnaryExec, closing_source


def _jx():
    from spark_rapids_tpu.columnar.column import _jnp
    return _jnp()


#: ops are ('filter', condition) or ('project', [exprs])
StageOp = Tuple[str, object]


def _ops_signature(ops: Sequence[StageOp]) -> Tuple:
    sig = []
    for kind, payload in ops:
        if kind == "filter":
            sig.append(("F",) + expr_key(payload))
        else:
            sig.append(("P", tuple(expr_key(e) for e in payload)))
    return tuple(sig)


def _lits_desc(promoted) -> str:
    """Explain-only rendering of promoted-literal slot VALUES: the ops'
    sql() shows value-independent ``$litN`` placeholders (they key the
    program cache), so the concrete bindings surface here."""
    if not promoted:
        return ""
    return " lits[" + \
        ", ".join(f"$lit{p.slot}={p.value!r}" for p in promoted) + "]"


def _batch_signature(batch: ColumnarBatch) -> Tuple:
    from spark_rapids_tpu.columnar.encoding import (DictionaryColumn,
                                                    RleColumn)
    sig = []
    for c in batch.columns:
        enc = None
        if isinstance(c, DictionaryColumn):
            # codes plane, value-plane shapes ride the dictionary args;
            # the FINGERPRINT stays out — one executable per table/plane
            # SHAPE serves every dictionary and literal value
            enc = "dict"
        elif isinstance(c, RleColumn):
            enc = ("rle", c.logical_bucket)
        sig.append((str(c.data_type), tuple(c.data.shape),
                    c.lengths is not None, c.elem_valid is not None, enc))
    return tuple(sig)


def _trace_chain(ops, cols: List[TCol], sel, bucket, jnp, lit_args=None,
                 enc_tables=None):
    """Applies the filter/project chain to (cols, sel) in-trace.
    ``lit_args`` carries the runtime values of PromotedLiteral slots
    (plan/stages.py) so one compiled program serves every literal;
    ``enc_tables`` the dictionary lookup tables of code-space
    predicates (columnar/encoding.py DictContains)."""
    from spark_rapids_tpu.expressions.evaluator import tcol_to_device_column
    for kind, payload in ops:
        ctx = EvalContext(cols, "tpu", bucket)
        ctx.literal_args = lit_args
        ctx.enc_tables = enc_tables
        if kind == "filter":
            pred = payload.eval_tpu(ctx)
            keep = valid_array(pred, ctx)
            if not pred.is_scalar:
                keep = keep & pred.data
            else:
                keep = keep & jnp.asarray(pred.data).astype(bool)
            sel = sel & keep
        else:
            outs = []
            for e in payload:
                tc = e.eval_tpu(ctx)
                dc = tcol_to_device_column(tc, 0, bucket, jnp)
                outs.append(TCol(dc.data, dc.validity, e.data_type,
                                 lengths=dc.lengths,
                                 elem_valid=dc.elem_valid))
            cols = outs
    return cols, sel


def _cols_to_arrs(batch: ColumnarBatch):
    return [(c.data, c.validity, c.lengths, c.elem_valid)
            for c in batch.columns]


def _arrs_to_tcols(arrs, dtypes):
    return [TCol(d, v, dt, lengths=ln, elem_valid=ev)
            for (d, v, ln, ev), dt in zip(arrs, dtypes)]


class _PromotedLiteralsMixin:
    """Promoted-literal plumbing shared by the fused execs: slot values
    bind as runtime args of the compiled program (``_lit_args``) while
    plan-identity keys still carry the VALUES (``lit_key`` — two stages
    sharing one program are still different pipelines)."""

    def _init_promoted(self, promoted) -> None:
        #: PromotedLiteral slots in order (plan/stages.py); their values
        #: are runtime args of the compiled program, not part of its key
        self.promoted = list(promoted)
        self._lits = None

    def _lit_args(self) -> Tuple:
        if self._lits is None:
            from spark_rapids_tpu.plan.stages import physical_literal
            self._lits = tuple(physical_literal(p.value, p.data_type)
                               for p in self.promoted)
        return self._lits

    def lit_key(self) -> Tuple:
        return tuple((p.slot, repr(p.value)) for p in self.promoted)


class TpuFusedStageExec(UnaryExec, _PromotedLiteralsMixin):
    """Fused [Filter|Project]+ chain with a compact terminal."""

    is_device = True

    def __init__(self, ops: Sequence[StageOp], child: Exec, promoted=()):
        super().__init__(child)
        self.ops = list(ops)
        self._init_promoted(promoted)
        #: per-(batch encodings) translated op chains (encoding.py)
        self._enc_cache: dict = {}

    @property
    def schema(self) -> T.StructType:
        s = self.child.schema
        for kind, payload in self.ops:
            if kind == "project":
                from spark_rapids_tpu.exec.basic import _project_schema
                s = _project_schema(payload)
        return s

    def _out_names(self):
        from spark_rapids_tpu.expressions.evaluator import _out_names
        names = None
        for kind, payload in self.ops:
            if kind == "project":
                names = _out_names(payload)
        return names

    def execute_partition(self, pidx):
        from spark_rapids_tpu.exec import stage_compiler as SC
        pending = None
        with closing_source(self.child.execute_partition(pidx)) as it:
            for b in it:
                step = self._program(b)
                prog, args = step[:2]
                if SC.ASYNC_COMPILE and prog.needs_compile():
                    # background lower+compile; the one-batch look-ahead
                    # below overlaps it with the previous batch's
                    # downstream compute
                    prog.warm_async(*args)
                if pending is not None:
                    yield self._finish(*pending)
                    pending = None
                # defer only while a background compile is actually in
                # flight: in the steady state (program warm) an
                # unconditional hold would add a batch of latency and pin
                # an extra batch's device arrays per fused stage for zero
                # overlap benefit
                if prog.compiling():
                    pending = step
                else:
                    yield self._finish(*step)
        if pending is not None:
            yield self._finish(*pending)

    def _program(self, b):
        """The stage program for ``b``, its arguments, the encoding plan
        and whether the batch is sized (the module's header): ``_finish``
        takes the four."""
        from spark_rapids_tpu.columnar import encoding as ENC
        from spark_rapids_tpu.ops.batch_ops import (compact_planes,
                                                    compaction_perm)
        jnp = _jx()
        enc = ENC.plan_fused_stage(self.ops, b, cache=self._enc_cache)
        ops = self.ops if enc is None else enc.ops
        sized = b.bucket > COL.SIZED_MIN_BUCKET and \
            any(kind == "filter" for kind, _ in self.ops)
        key = (_ops_signature(self.ops), _batch_signature(b), b.bucket,
               None if enc is None else enc.sig)

        def build():
            bucket = b.bucket
            dtypes = [c.data_type for c in b.columns]
            plan = enc

            def run(arrs, rc, lits, enc_args):
                cols = _arrs_to_tcols(arrs, dtypes)
                if plan is not None:
                    cols = plan.prepare_cols(cols, enc_args, jnp)
                sel = jnp.arange(bucket, dtype=np.int32) < rc
                cols, sel = _trace_chain(ops, cols, sel, bucket, jnp,
                                         lits,
                                         None if plan is None
                                         else enc_args[0])
                planes = [(c.data, c.valid, c.lengths,
                           getattr(c, "elem_valid", None)) for c in cols]
                if sized:
                    # the terminal's gathers wait for the count
                    # (``_compact_sized``): the planes leave as they are
                    return planes, compaction_perm(sel, jnp), jnp.sum(sel)
                # compact terminal: kept rows to the front
                return compact_planes(planes, sel, jnp)

            return run
        from spark_rapids_tpu.exec.stage_compiler import get_or_build
        prog = get_or_build("fused.stage", key, build)
        # validity inside the trace comes from TCol.valid; bind real
        # planes (and the promoted literal values) here
        args = (_cols_to_arrs(b), rc_traceable(b.row_count),
                self._lit_args(),
                () if enc is None else enc.runtime_args(b))
        return prog, args, enc, sized

    @staticmethod
    def _compact_sized(planes, perm, cnt):
        """The compact terminal of a sized batch: fetches the live count
        (one counted scalar sync, site ``stage-size``) and gathers every
        plane at ``max(bucket_rows(count), floor)`` rows, or at the
        input's bucket where that is no smaller (a filter that keeps most
        of a large batch pays the fetch, milliseconds, and shrinks
        nothing).  Returns the planes and the count, an ``int``."""
        from spark_rapids_tpu.aux import transitions as TR
        from spark_rapids_tpu.aux.tracing import add_count
        from spark_rapids_tpu.exec.stage_compiler import get_or_build
        from spark_rapids_tpu.ops.batch_ops import take_front_planes
        jnp = _jx()
        n = TR.sync_int(cnt, site="stage-size")
        add_count("sized_stages", 1)
        out_bucket = min(perm.shape[0],
                         max(COL.bucket_rows(n), COL.SIZED_MIN_BUCKET))
        key = (tuple(tuple(None if p is None else (tuple(p.shape),
                                                   str(p.dtype))
                           for p in col) for col in planes), out_bucket)

        def build():
            def run(arrs, front_first, live):
                return take_front_planes(arrs, front_first, live,
                                         out_bucket, jnp)

            return run
        return get_or_build("fused.compact", key, build)(planes, perm,
                                                         n), n

    def _finish(self, prog, args, enc=None, sized=False):
        if sized:
            outs, rc = self._compact_sized(*prog(*args))
        else:
            outs, cnt = prog(*args)
            rc = DeferredCount(cnt)
        fields = self.schema.fields
        cols = []
        for i, ((d, v, ln, ev), f) in enumerate(zip(outs, fields)):
            dic = None if enc is None else enc.final_dicts[i]
            if dic is not None:
                # kept codes survived the compacting filter: decode is
                # deferred until (and unless) something needs values
                from spark_rapids_tpu.columnar.encoding import \
                    DictionaryColumn
                cols.append(DictionaryColumn(d, v, rc, f.data_type,
                                             None, None, dictionary=dic))
            else:
                cols.append(DeviceColumn(d, v, rc, f.data_type, ln, ev))
        return ColumnarBatch(cols, rc, self._out_names() or
                             [f.name for f in fields])

    def node_desc(self):
        parts = []
        for kind, payload in self.ops:
            if kind == "filter":
                parts.append(f"F[{payload.sql()}]")
            else:
                parts.append(f"P[{', '.join(e.sql() for e in payload)}]")
        return "TpuFusedStage(" + " -> ".join(parts) + ")" \
            + _lits_desc(self.promoted)


class TpuFusedAggExec(UnaryExec, _PromotedLiteralsMixin):
    """Fused [Filter|Project]* chain + hash-aggregate update pass.

    The chain and the aggregate's first (update) pass over each input batch
    run as ONE jit; filters contribute a selection mask consumed directly
    by the reductions — no compaction, no intermediate batches.  Merge and
    final passes reuse segmented_aggregate (tiny inputs).
    """

    is_device = True

    def __init__(self, ops: Sequence[StageOp], layout, mode, child: Exec,
                 promoted=()):
        super().__init__(child)
        self.ops = list(ops)
        self.layout = layout
        self.mode = mode
        self._init_promoted(promoted)
        #: per-(batch encodings) translated op chains (encoding.py)
        self._enc_cache: dict = {}

    @property
    def schema(self):
        from spark_rapids_tpu.exec.aggregate import PARTIAL
        return self.layout.buffer_schema if self.mode == PARTIAL else \
            self.layout.result_schema

    def _fused_update(self, b: ColumnarBatch) -> ColumnarBatch:
        from spark_rapids_tpu.columnar import encoding as ENC
        jnp = _jx()
        lay = self.layout
        nk0 = lay.num_keys
        all_upd = list(lay.update_input_exprs())
        enc = ENC.plan_fused_stage(self.ops, b, key_exprs=all_upd[:nk0],
                                   other_exprs=all_upd[nk0:],
                                   cache=self._enc_cache)
        ops = self.ops if enc is None else enc.ops
        # per-key Dictionary when the group key is a kept (code-space)
        # column; dictionary IDENTITY joins the program key — grouped
        # code outputs are only meaningful against their dictionary
        key_dicts = self._key_dicts(enc, all_upd[:nk0])
        key = (_ops_signature(self.ops), _batch_signature(b), b.bucket,
               tuple(expr_key(e) for e in lay.update_input_exprs()),
               tuple((o, k, cv, str(dt))
                     for o, k, cv, dt in lay.update_specs()),
               lay.num_keys,
               None if enc is None else enc.sig,
               tuple(None if d is None else d.fingerprint
                     for d in key_dicts))
        def build():
            import jax
            from spark_rapids_tpu.expressions.evaluator import \
                tcol_to_device_column
            from spark_rapids_tpu.ops.agg_ops import (_GLOBAL_OUT_BUCKET,
                                                      global_agg_trace,
                                                      keyed_agg_trace)
            bucket = b.bucket
            dtypes = [c.data_type for c in b.columns]
            upd_exprs = list(lay.update_input_exprs())
            upd_specs = list(lay.update_specs())
            nk = lay.num_keys
            plan = enc
            kdicts = key_dicts

            def run(arrs, rc, lits, enc_args):
                # named scopes: the engine's names for the program's
                # phases in every XLA op's op_name (metadata only)
                with jax.named_scope("keys"):
                    upd_cols, sel = update_inputs(arrs, rc, lits, enc_args)
                with jax.named_scope("update"):
                    if nk == 0:
                        outs = global_agg_trace(upd_cols, sel, upd_specs,
                                                jnp)
                        return outs, None
                    return keyed_agg_trace(upd_cols, sel, nk, upd_specs,
                                           bucket, jnp)

            def update_inputs(arrs, rc, lits, enc_args):
                """The chain's filters and projections, then the group
                keys and the aggregates' inputs."""
                cols = _arrs_to_tcols(arrs, dtypes)
                if plan is not None:
                    cols = plan.prepare_cols(cols, enc_args, jnp)
                sel = jnp.arange(bucket, dtype=np.int32) < rc
                cols, sel = _trace_chain(ops, cols, sel, bucket, jnp,
                                         lits,
                                         None if plan is None
                                         else enc_args[0])
                ctx = EvalContext(cols, "tpu", bucket)
                upd_cols = []
                for ki, e in enumerate(upd_exprs):
                    if ki < nk and kdicts[ki] is not None:
                        # kept dictionary key: GROUP BY THE CODES — an
                        # int32 plane instead of string word planes
                        from spark_rapids_tpu.columnar.encoding import \
                            _strip_alias
                        base = _strip_alias(e)
                        tc = cols[base.ordinal]
                        upd_cols.append(DeviceColumn(
                            tc.data.astype(np.int32), tc.valid, bucket,
                            T.INT))
                        continue
                    tc = e.eval_tpu(ctx)
                    dc = tcol_to_device_column(tc, 0, bucket, jnp)
                    upd_cols.append(DeviceColumn(dc.data, dc.validity,
                                                 bucket, e.data_type,
                                                 dc.lengths))
                return upd_cols, sel

            return run
        from spark_rapids_tpu.exec.stage_compiler import get_or_build
        fn = get_or_build("fused.agg_update", key, build)

        arrs = _cols_to_arrs(b)
        outs, ng = fn(arrs, rc_traceable(b.row_count), self._lit_args(),
                      () if enc is None else enc.runtime_args(b))
        lay = self.layout
        nk = lay.num_keys
        n = 1 if nk == 0 else DeferredCount(ng)
        names = [lay.key_name(i) for i in range(nk)] + \
            [lay.buffer_name(j) for j in range(len(lay.flat))]
        cols = []
        upd_exprs = list(lay.update_input_exprs())
        upd_specs = list(lay.update_specs())
        for j, (d, v, ln) in enumerate(outs):
            if j < nk:
                dt = upd_exprs[j].data_type
                if key_dicts[j] is not None:
                    from spark_rapids_tpu.columnar.encoding import \
                        DictionaryColumn
                    cols.append(DictionaryColumn(
                        d, v, n, dt, None, None,
                        dictionary=key_dicts[j]))
                    continue
            else:
                dt = upd_specs[j - nk][3]
                if ln is None and dt.np_dtype is not None and \
                        d.dtype != np.dtype(dt.np_dtype):
                    d = d.astype(dt.np_dtype)
            cols.append(DeviceColumn(d, v, n, dt, ln))
        return ColumnarBatch(cols, n, names)

    @staticmethod
    def _key_dicts(enc, key_exprs):
        """Per grouping key: the Dictionary when the key rides codes."""
        from spark_rapids_tpu.columnar.encoding import _strip_alias
        from spark_rapids_tpu.expressions.base import BoundReference
        out = []
        for e in key_exprs:
            dic = None
            if enc is not None:
                base = _strip_alias(e)
                if isinstance(base, BoundReference) and \
                        base.ordinal < len(enc.final_dicts):
                    dic = enc.final_dicts[base.ordinal]
            out.append(dic)
        return out

    def _merge_final_eligible(self, partials: List[ColumnarBatch]) -> bool:
        """The single-jit merge+final path needs in-trace concat: every
        partial must share one plane layout (same 2-D widths, no nested
        element-validity planes)."""
        sig0 = _batch_signature(partials[0])
        for b in partials[1:]:
            if _batch_signature(b) != sig0:
                return False
        return all(c.elem_valid is None
                   for b in partials for c in b.columns)

    def _merge_final_fused(self, partials: List[ColumnarBatch]):
        """ONE jit for the whole reduce side: in-trace concat of the
        partial buffers -> merge pass -> final expression eval.  Collapses
        three sequential dispatches (concat_batches, segmented_aggregate,
        final project) into one, off the critical path of every
        aggregate query's last mile."""
        from spark_rapids_tpu.columnar.encoding import DictionaryColumn
        jnp = _jx()
        lay = self.layout
        nk = lay.num_keys
        merge_specs = list(lay.merge_specs())
        final_exprs = list(lay.final_exprs())
        # encoded key columns merge as code planes; align_batches already
        # guaranteed one fingerprint per position, and that IDENTITY is
        # part of the program key (grouped codes mean nothing without
        # their dictionary)
        enc_dicts = [c.dictionary if isinstance(c, DictionaryColumn)
                     else None for c in partials[0].columns]
        key = ("mergefinal", tuple(_batch_signature(b) for b in partials),
               tuple(b.bucket for b in partials), nk,
               tuple((o, k, cv, str(dt)) for o, k, cv, dt in merge_specs),
               tuple(expr_key(e) for e in final_exprs),
               tuple(None if d is None else d.fingerprint
                     for d in enc_dicts))
        def build():
            from spark_rapids_tpu.columnar.column import DeviceColumn
            from spark_rapids_tpu.expressions.evaluator import \
                tcol_to_device_column
            from spark_rapids_tpu.ops.agg_ops import (_GLOBAL_OUT_BUCKET,
                                                      global_agg_trace,
                                                      keyed_agg_trace)
            buckets = [b.bucket for b in partials]
            total = sum(buckets)
            # inside the trace encoded key columns are their int32 code
            # planes (the group/hash machinery must not see the logical
            # string type)
            in_dtypes = [T.INT if enc_dicts[ci] is not None
                         else c.data_type
                         for ci, c in enumerate(partials[0].columns)]

            def run(arrs_list, rcs):
                sel = jnp.concatenate(
                    [jnp.arange(bk, dtype=np.int32) < rcs[pi]
                     for pi, bk in enumerate(buckets)])
                cols = []
                for ci, dt in enumerate(in_dtypes):
                    d = jnp.concatenate(
                        [arrs_list[pi][ci][0] for pi in range(len(buckets))],
                        axis=0)
                    v = jnp.concatenate(
                        [arrs_list[pi][ci][1] for pi in range(len(buckets))])
                    lns = [arrs_list[pi][ci][2] for pi in range(len(buckets))]
                    ln = None if lns[0] is None else jnp.concatenate(lns)
                    cols.append(DeviceColumn(d, v, total, dt, ln))
                if nk == 0:
                    outs = global_agg_trace(cols, sel, merge_specs, jnp)
                    ng = None
                    out_bucket = _GLOBAL_OUT_BUCKET
                else:
                    outs, ng = keyed_agg_trace(cols, sel, nk, merge_specs,
                                               total, jnp)
                    out_bucket = total
                tcols = []
                for j, (d, v, ln) in enumerate(outs):
                    dt = in_dtypes[j] if j < nk else merge_specs[j - nk][3]
                    if ln is None and dt.np_dtype is not None and \
                            d.dtype != np.dtype(dt.np_dtype):
                        d = d.astype(dt.np_dtype)
                    tcols.append(TCol(d, v, dt, lengths=ln))
                ctx = EvalContext(tcols, "tpu", out_bucket)
                fouts = []
                for e in final_exprs:
                    tc = e.eval_tpu(ctx)
                    dc = tcol_to_device_column(tc, 0, out_bucket, jnp)
                    fouts.append((dc.data, dc.validity, dc.lengths,
                                  dc.elem_valid))
                return fouts, ng

            return run
        from spark_rapids_tpu.exec.stage_compiler import get_or_build
        fn = get_or_build("fused.agg_merge_final", key, build)

        arrs_list = [[(c.data, c.validity, c.lengths) for c in b.columns]
                     for b in partials]
        rcs = [rc_traceable(b.row_count) for b in partials]
        fouts, ng = fn(arrs_list, rcs)
        n = 1 if nk == 0 else DeferredCount(ng)
        from spark_rapids_tpu.expressions.evaluator import _out_names
        fields = self.layout.result_schema.fields
        cols = []
        for i, ((d, v, ln, ev), f) in enumerate(zip(fouts, fields)):
            if i < nk and enc_dicts[i] is not None:
                cols.append(DictionaryColumn(d, v, n, f.data_type,
                                             None, None,
                                             dictionary=enc_dicts[i]))
            else:
                cols.append(DeviceColumn(d, v, n, f.data_type, ln, ev))
        return ColumnarBatch(cols, n, _out_names(final_exprs) or
                             [f.name for f in fields])

    def execute_partition(self, pidx):
        from spark_rapids_tpu.exec.aggregate import COMPLETE, FINAL, PARTIAL
        from spark_rapids_tpu.expressions.evaluator import eval_exprs_tpu
        from spark_rapids_tpu.memory.retry import with_retry_no_split
        from spark_rapids_tpu.columnar import encoding as ENC
        lay = self.layout
        partials: List[ColumnarBatch] = []
        with closing_source(self.child.execute_partition(pidx)) as it:
            for b in it:
                partials.append(with_retry_no_split(
                    None, lambda: self._fused_update(b)))
        if len(partials) > 1 and any(ENC.batch_has_encoded(p)
                                     for p in partials):
            # grouped codes only combine against ONE dictionary per key
            # column; mismatched fingerprints decode before merging
            partials = ENC.align_batches(partials, site="agg-merge")
        if not partials:
            if lay.num_keys == 0 and self.mode in (COMPLETE, FINAL) and \
                    self.child.num_partitions == 1:
                from spark_rapids_tpu.exec.aggregate import \
                    CpuHashAggregateExec
                yield CpuHashAggregateExec(
                    lay.grouping, lay.aggs, self.mode,
                    self.child)._empty_reduction().to_device()
            return
        needs_merge = len(partials) > 1 or self.mode == FINAL
        if not needs_merge:
            merged_iter = iter(partials)
        else:
            from spark_rapids_tpu.exec.aggregate import \
                merge_partials_out_of_core
            from spark_rapids_tpu.memory.device_manager import \
                free_device_headroom
            from spark_rapids_tpu.memory.retry import (SplitAndRetryOOM,
                                                       maybe_inject_oom)
            from spark_rapids_tpu.memory.spillable import \
                SpillableColumnarBatch
            import spark_rapids_tpu.exec.aggregate as A
            eligible = self.mode != PARTIAL and \
                A.FORCE_REPARTITION_BELOW_DEPTH == 0 and \
                self._merge_final_eligible(partials)
            too_big = False
            if lay.num_keys > 0:
                budget = free_device_headroom(2)
                if budget is not None:
                    est = sum(p.sized_nbytes() for p in partials)
                    too_big = est > budget
            if (not eligible or too_big) and \
                    any(ENC.batch_has_encoded(p) for p in partials):
                # the out-of-core merge walks host tiers and the CPU
                # repartitioner: it needs values, not codes
                partials = [ENC.materialize_batch(p, site="agg-merge")
                            for p in partials]
            spills = [SpillableColumnarBatch.from_device(p)
                      for p in partials]
            partials = None  # only the spillable handles keep them alive
            if eligible and not too_big:
                def attempt():
                    maybe_inject_oom()
                    return self._merge_final_fused(
                        [sb.get_batch() for sb in spills])
                try:
                    out = with_retry_no_split(None, attempt)
                    for sb in spills:
                        sb.close()
                    yield out
                    return
                except SplitAndRetryOOM:
                    if lay.num_keys == 0:
                        raise
            merged_iter = merge_partials_out_of_core(lay, spills)
        names = [lay.key_name(i) for i in range(lay.num_keys)] + \
            [lay.buffer_name(j) for j in range(len(lay.flat))]
        for merged in merged_iter:
            if self.mode == PARTIAL:
                merged.names = list(names)
                yield merged
            elif lay.num_keys == 0 and merged.row_count == 0:
                from spark_rapids_tpu.exec.aggregate import \
                    CpuHashAggregateExec
                yield CpuHashAggregateExec(
                    lay.grouping, lay.aggs, self.mode,
                    self.child)._empty_reduction().to_device()
            else:
                # grouped dictionary keys pass through STILL ENCODED
                yield ENC.eval_exprs_keep_encoded(lay.final_exprs(),
                                                  merged)

    def node_desc(self):
        chain = "+".join("F" if k == "filter" else "P"
                         for k, _ in self.ops) or "-"
        return f"TpuFusedAgg[{chain}, keys={self.layout.num_keys}, " \
               f"mode={self.mode}]" + _lits_desc(self.promoted)
