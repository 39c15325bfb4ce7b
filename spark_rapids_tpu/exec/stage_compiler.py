"""StageCompiler: the process-wide executable cache behind every jitted
stage program.

The engine's end-to-end deficit lives in the query path around the
kernels, not in the kernels (ROADMAP item 1): per-operator dispatch and —
worse — re-tracing/re-compiling programs the process has already built.
Every jitted stage program (fused filter/project/agg chains, join
build/probe/pair phases, sort permutations, window frames, transfer
pack/unpack...) is obtained through ONE helper here, keyed by its
(op-signature, batch schema, row bucket) and backed by a two-tier cache:

- **tier 1 — process executable cache**: a bounded LRU of jitted
  callables with hit/miss/evict/trace counters.  The python trace
  function of every program is wrapped with a trace counter, so "the
  second run of an identical query performs zero new traces" is an
  assertable fact, not a hope.
- **tier 2 — JAX persistent compilation cache** (conf
  ``spark.rapids.sql.compile.cacheDir``): compiled XLA executables
  survive process restarts; a cold process re-traces (cheap) but loads
  machine code from disk instead of re-compiling (expensive: seconds
  to tens of seconds per program, ``scripts/tpu_rehearsal.py``).

Optional background compilation (conf ``spark.rapids.sql.compile.async``):
``warm_async`` lowers + compiles a program on a daemon pool thread while
the caller overlaps other work (the fused stage exec runs a one-batch
look-ahead so a new program's compile overlaps the previous batch's
compute), mirroring the PR-4 pipeline's producer/consumer overlap at the
compiler layer.

Reference analog: the reference pays JIT cost in cuDF kernel launches and
avoids it via pre-built kernels; a tracing-compiler engine must instead
manage program identity explicitly — this module is that manager.
"""

from __future__ import annotations

import collections
import hashlib
import os
import re
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from spark_rapids_tpu.aux import events as EV
from spark_rapids_tpu.aux.tracing import annotation, span

__all__ = ["get_or_build", "stats", "reset_stats", "clear",
           "set_max_programs", "set_persistent_cache_dir", "StageProgram",
           "jaxpr_signatures"]

#: synced from ``spark.rapids.sql.compile.async`` by the planner
ASYNC_COMPILE = False

#: synced from ``spark.rapids.audit.ledger`` by the planner: record a
#: per-program audit ledger row (``stageProgram`` event, schema v3) at
#: every build, so the offline auditor (tools/audit) sees every cached
#: executable.  The row carries signatures/shapes/fingerprints ONLY —
#: never jaxpr objects or buffers, so audit state pins no device memory.
#: Recording only happens when a sink that will STORE the row is live
#: (the query's event-log file sink, or a process-global sink) — the
#: audit is an offline tool over event logs, and paying the per-build
#: analysis for a row that dies in the per-query ring buffer would tax
#: every sink-less session (~10% on compile-heavy suites) for nothing.
AUDIT_LEDGER = True

#: consts at or under this many bytes get a content fingerprint (one
#: host read at build time); larger consts record shape/dtype only —
#: the auditor treats any large const as promotion-suspect on its own
CONST_FP_MAX_BYTES = 1 << 20

#: cache keys recorded into the ledger are capped at this many repr
#: chars (key provenance is for storm diagnosis, not reconstruction)
KEY_REPR_MAX = 600

_LOCK = threading.RLock()
_PROGRAMS: "collections.OrderedDict[Tuple, StageProgram]" = \
    collections.OrderedDict()
_MAX_PROGRAMS = 4096

_STATS = {
    "hits": 0,          # tier-1 lookups that found a live program
    "misses": 0,        # lookups that had to build a new program
    "evictions": 0,     # programs dropped by the LRU bound
    "traces": 0,        # python trace-function executions (per jax trace)
    "compiles": 0,      # first dispatches that built a new executable
    "async_compiles": 0,  # programs compiled on the background pool
    "async_failures": 0,  # background compiles that raised (jit fallback)
    "compile_s": 0.0,   # seconds spent in first-dispatch trace+compile
    "ledger_rows": 0,   # stageProgram audit rows emitted
    "ledger_errors": 0,  # ledger recordings that raised (audit never
                         # fails the query; nonzero = blind audit spots)
    "dispatches": 0,    # steady-path calls of an already-built program
    "dispatch_s": 0.0,  # host seconds inside those calls
}
#: last background-compile error (stats(); None = healthy)
_ASYNC_ERROR = [None]
_TRACES_BY_KIND: Dict[str, int] = {}
_DISPATCHES_BY_KIND: Dict[str, int] = {}

#: persistent (tier-2) cache state; dir None = disabled
_DISK = {"dir": None, "error": None}

_POOL = None
_POOL_LOCK = threading.Lock()


class _DaemonPool:
    """Two daemon worker threads + a queue.  NOT a ThreadPoolExecutor:
    since 3.9 its (non-daemon) workers are joined at interpreter exit, so
    an in-flight XLA compile — tens of seconds, or forever on a hung
    device — would block shutdown.  A background compile is disposable;
    daemon threads let the process exit mid-compile."""

    def __init__(self, workers: int = 2):
        import queue
        self._q = queue.Queue()
        for i in range(workers):
            threading.Thread(target=self._loop, daemon=True,
                             name=f"tpu-compile-{i}").start()

    def _loop(self):
        while True:
            fut, fn = self._q.get()
            if not fut.set_running_or_notify_cancel():
                continue
            try:
                fut.set_result(fn())
            except BaseException as e:  # noqa: BLE001 — delivered to the
                fut.set_exception(e)     # joining __call__, never lost

    def submit(self, fn):
        from concurrent.futures import Future
        fut = Future()
        self._q.put((fut, fn))
        return fut


def _compile_pool():
    global _POOL
    with _POOL_LOCK:
        if _POOL is None:
            _POOL = _DaemonPool()
        return _POOL


def _key_hash(key) -> str:
    return hashlib.sha1(repr(key).encode()).hexdigest()[:12]


class StageProgram:
    """One cached jitted program.  Callable; measures its first dispatch
    (trace + compile + first execution) and emits a ``stageCompile``
    event so the profiler can attribute compilation separately from
    steady-state compute."""

    __slots__ = ("kind", "key_hash", "key_repr", "_fn", "_lock",
                 "_dispatched", "_warm_future", "_compiled", "_drifted")

    def __init__(self, kind: str, key, fn):
        self.kind = kind
        self.key_hash = _key_hash(key)
        #: key provenance for the audit ledger (bounded repr: enough to
        #: diagnose which component over-discriminates in a recompile
        #: storm, never the whole structure)
        self.key_repr = repr(key)[:KEY_REPR_MAX]
        self._fn = fn
        self._lock = threading.Lock()
        self._dispatched = False
        self._warm_future = None
        self._compiled = None
        self._drifted = False

    # -- async (AOT) path ----------------------------------------------------
    def needs_compile(self) -> bool:
        return not (self._dispatched or self._compiled is not None
                    or self._warm_future is not None)

    def compiling(self) -> bool:
        """True while a background compile is in flight (cleared when a
        ``__call__`` joins it)."""
        return self._warm_future is not None

    def warm_async(self, *args) -> bool:
        """Lower + compile off the critical path on the daemon pool.  The
        next ``__call__`` joins the in-flight future, so foreground work
        never duplicates the compile.  Returns True if a warm was
        scheduled."""
        with self._lock:
            if not self.needs_compile():
                return False

            def work():
                with span("compile.build", kind=self.kind, tier="aot"):
                    t0 = time.perf_counter()
                    traced = self._fn.trace(*args)
                    lowered = traced.lower()
                    compiled = lowered.compile()
                    dt = time.perf_counter() - t0
                self._note_compiled(dt, tier="aot")
                with _LOCK:
                    _STATS["async_compiles"] += 1
                _record_ledger(self, traced, lowered)
                return compiled

            # the pool's daemon threads carry no contextvars: run the
            # work inside a COPY of the caller's context (the spool
            # pattern) so the stageCompile/stageProgram events route to
            # the caller's query sinks — without it every async-built
            # program would silently vanish from the audit ledger
            import contextvars
            ctx = contextvars.copy_context()
            self._warm_future = _compile_pool().submit(
                lambda: ctx.run(work))
            return True

    def _note_compiled(self, dt: float, tier: str) -> None:
        with _LOCK:
            _STATS["compiles"] += 1
            _STATS["compile_s"] += dt
        q = EV.active_query()
        if q is not None:
            q.note_compiled(dt)
        from spark_rapids_tpu.aux.events import emit
        emit("stageCompile", stage_kind=self.kind, key=self.key_hash,
             duration_s=round(dt, 6), tier=tier,
             disk_cache=_DISK["dir"] is not None)

    # -- dispatch ------------------------------------------------------------
    def _dispatch(self, fn, args):
        """The steady path: a call of a program that is already built.
        One ``srt.dispatch`` annotation (no Span object: a query may make
        thousands) and two clock reads; counted once it has returned, in
        the process's totals and in the query that made the call."""
        q = EV.active_query()
        t0 = time.perf_counter()
        with annotation("dispatch", q, EV.current_span_id(),
                        kind=self.kind):
            out = fn(*args)
        dt = time.perf_counter() - t0
        with _LOCK:
            _STATS["dispatches"] += 1
            _STATS["dispatch_s"] += dt
            _DISPATCHES_BY_KIND[self.kind] = \
                _DISPATCHES_BY_KIND.get(self.kind, 0) + 1
        if q is not None:
            q.note_dispatch(self.kind, dt)
        return out

    def __call__(self, *args):
        fut = self._warm_future
        if fut is not None:
            try:
                compiled = fut.result()
            except Exception as e:  # noqa: BLE001 — AOT is an optimization;
                compiled = None      # the jit path below is always correct,
                # but a silently-failing async tier must be visible in
                # stats(), or async=true degrades to sync with no evidence
                with _LOCK:
                    _STATS["async_failures"] += 1
                    _ASYNC_ERROR[0] = f"{type(e).__name__}: {e}"[:160]
            with self._lock:
                self._warm_future = None
                if compiled is not None:
                    self._compiled = compiled
                    self._dispatched = True
                # on a failed background compile, first-dispatch stays
                # unclaimed: the fallback jit compile below must be timed
                # and counted like any cold compile, not happen invisibly
        if self._compiled is not None:
            try:
                return self._dispatch(self._compiled, args)
            except (TypeError, ValueError):
                # arg-signature drift only (an int row count where the
                # lowering saw a device scalar): route THIS call through
                # the jit dispatcher, which traces and caches the
                # variant.  The compiled executable is KEPT — exact-
                # signature calls stay on it, and dropping it would make
                # jit re-compile the original signature from scratch the
                # next time it recurs (one full wasted compile per
                # drifting program).  The first drift is timed and
                # counted like any cold compile so it can't leak into
                # steady-state metrics.  Genuine runtime errors (device
                # OOM...) must propagate to retry/arbitration, not
                # silently re-execute the program.
                t0 = time.perf_counter()
                out = self._fn(*args)
                with self._lock:
                    first_drift = not self._drifted
                    self._drifted = True
                if first_drift:
                    self._note_compiled(time.perf_counter() - t0,
                                        tier="jit")
                return out
        first = False
        if not self._dispatched:
            # claim first-dispatch under the lock: concurrent partitions
            # hitting a fresh program must produce ONE compile record
            with self._lock:
                if not self._dispatched:
                    self._dispatched = True
                    first = True
        if first:
            with span("compile.build", kind=self.kind, tier="jit"):
                return self._first_dispatch(args)
        return self._dispatch(self._fn, args)

    def _first_dispatch(self, args):
        """Trace + compile + first execution, timed as one compile."""
        t0 = time.perf_counter()
        traced = lowered = compiled = None
        if _ledger_active():
            # first dispatch goes through the AOT pipeline so the
            # audit ledger sees the jaxpr + cost analysis of the
            # exact program being cached, with ONE trace (the same
            # count the jit dispatch would pay) and no duplicate
            # compile.  Any AOT-surface failure falls back to the
            # plain jit dispatch, which is always correct.
            try:
                traced = self._fn.trace(*args)
                lowered = traced.lower()
                compiled = lowered.compile()
            except Exception:  # noqa: BLE001 — audit is best-effort
                traced = lowered = compiled = None
                with _LOCK:
                    _STATS["ledger_errors"] += 1
        if compiled is not None:
            self._compiled = compiled
            out = compiled(*args)
            self._note_compiled(time.perf_counter() - t0, tier="jit")
            _record_ledger(self, traced, lowered)
            return out
        out = self._fn(*args)
        self._note_compiled(time.perf_counter() - t0, tier="jit")
        return out


def _counting(kind: str, fn: Callable) -> Callable:
    """Wraps a trace function so every ACTUAL jax trace (including
    signature-variant retraces inside one jit wrapper) counts.

    The name it gives the function is a contract: ``run[<kind>]``,
    whatever the build function called it, so jit names the program
    ``jit_run_<kind>`` (the brackets fall to XLA's module naming).  That
    is what the profiler's trace shows for it and what
    ``benchmark/trace/reduce.py`` ``short_program`` shortens to the kind.
    ``tests/test_spans.py`` pins it through an xplane."""
    def traced(*args, **kwargs):
        with _LOCK:
            _STATS["traces"] += 1
            _TRACES_BY_KIND[kind] = _TRACES_BY_KIND.get(kind, 0) + 1
        return fn(*args, **kwargs)
    traced.__name__ = f"run[{kind}]"
    return traced


# ---------------------------------------------------------------------------
# audit ledger (schema v3 ``stageProgram`` rows; consumed by tools/audit)
# ---------------------------------------------------------------------------

#: memory addresses inside param reprs (callables, array views) would
#: make structural signatures unstable across processes
_ADDR_RE = re.compile(r"0x[0-9a-fA-F]+")


def _ledger_active() -> bool:
    """True when a recorded row would actually be STORED: the active
    query carries a durable (file) sink, or process-global sinks exist
    (out-of-query builds route there).  A query's ring buffer alone
    does not count — it is discarded at query end."""
    if not AUDIT_LEDGER:
        return False
    from spark_rapids_tpu.aux import events as EV
    q = EV.active_query()
    if q is not None:
        return bool(getattr(q, "_sinks", None))
    return bool(EV._GLOBAL_SINKS)


def _sub_jaxprs(val) -> List:
    """Open jaxprs nested inside an eqn param (pjit's ``jaxpr``, scan's
    branches...), whatever container they arrive in."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    if isinstance(val, Jaxpr):
        return [val]
    if isinstance(val, ClosedJaxpr):
        return [val.jaxpr]
    if isinstance(val, (tuple, list)):
        out = []
        for v in val:
            out.extend(_sub_jaxprs(v))
        return out
    return []


def _walk_eqns(jaxpr, exact: List, norm: List, prims: set) -> None:
    from jax.extend.core import Literal
    for eqn in jaxpr.eqns:
        prims.add(eqn.primitive.name)
        ins_exact, ins_norm = [], []
        for v in eqn.invars:
            short = v.aval.str_short()
            if isinstance(v, Literal):
                # the exact signature keeps the baked value, the
                # normalized one keeps only its type: N keys collapsing
                # onto one normalized signature while their exact
                # signatures differ IS the missed-literal-promotion
                # storm the auditor hunts
                ins_exact.append(f"lit({v.val!r}):{short}")
                ins_norm.append(f"lit:{short}")
            else:
                ins_exact.append(short)
                ins_norm.append(short)
        params = []
        for k in sorted(eqn.params):
            val = eqn.params[k]
            subs = _sub_jaxprs(val)
            if subs:
                for sj in subs:
                    _walk_eqns(sj, exact, norm, prims)
                params.append((k, "<jaxpr>"))
            else:
                params.append((k, _ADDR_RE.sub("0x", repr(val))))
        rec = (eqn.primitive.name, tuple(params),
               tuple(o.aval.str_short() for o in eqn.outvars))
        exact.append((rec, tuple(ins_exact)))
        norm.append((rec, tuple(ins_norm)))


def jaxpr_signatures(jaxpr) -> Tuple[str, str, List[str], int]:
    """(struct_sig, norm_sig, primitives, eqn count) of an OPEN jaxpr.

    ``struct_sig`` hashes the full structure including inline literal
    VALUES; ``norm_sig`` replaces every literal value with its type, so
    programs differing only in baked scalars collapse onto one
    signature — the clustering key of the auditor's recompile-storm and
    baked-constant passes.  Const buffers never participate: constvars
    contribute only their avals."""
    exact: List = []
    norm: List = []
    prims: set = set()
    _walk_eqns(jaxpr, exact, norm, prims)
    frame = (tuple(v.aval.str_short() for v in jaxpr.invars),
             tuple(v.aval.str_short() for v in jaxpr.constvars),
             tuple(v.aval.str_short() for v in jaxpr.outvars))

    def h(parts) -> str:
        return hashlib.sha1(repr((frame, parts)).encode()).hexdigest()[:16]

    return h(exact), h(norm), sorted(prims), len(exact)


def _const_records(consts) -> List[Dict]:
    """Shape/dtype/nbytes + content fingerprint per jaxpr const.  The
    fingerprint is a hash of the VALUE (one bounded host read at build
    time) so the auditor can tell 'same table baked everywhere' from
    'a different table baked per key'; the buffer itself is read and
    immediately dropped — ledger rows hold primitives only."""
    import numpy as np
    out = []
    for c in consts:
        shape = tuple(getattr(c, "shape", ()))
        dtype = str(getattr(c, "dtype", type(c).__name__))
        # a numpy const arrives as jax's TypedNdArray, which has a shape
        # and a dtype but no nbytes
        nbytes = int(np.prod(shape)) * np.dtype(c.dtype).itemsize \
            if hasattr(c, "dtype") else 0
        if 0 < nbytes <= CONST_FP_MAX_BYTES:
            try:
                fp = hashlib.sha1(
                    np.asarray(c).tobytes()).hexdigest()[:16]
            except Exception:  # noqa: BLE001 — unreadable const: shape-only
                fp = "unreadable"
        else:
            fp = "large"
        out.append({"shape": list(shape), "dtype": dtype,
                    "nbytes": nbytes, "fp": fp})
    return out


def _record_ledger(prog: StageProgram, traced, lowered) -> None:
    """Emits the program's ``stageProgram`` audit row.  Never raises —
    a failed recording counts in ``ledger_errors`` (a blind audit spot
    must be visible in stats, not silent)."""
    if traced is None or not _ledger_active():
        return
    try:
        closed = traced.jaxpr
        struct_sig, norm_sig, prims, n_eqns = jaxpr_signatures(closed.jaxpr)
        in_avals = [v.aval for v in closed.jaxpr.invars]
        out_avals = [v.aval for v in closed.jaxpr.outvars]
        flops = bytes_accessed = None
        try:
            ca = lowered.cost_analysis()
            if isinstance(ca, dict):
                if ca.get("flops") is not None:
                    flops = float(ca["flops"])
                if ca.get("bytes accessed") is not None:
                    bytes_accessed = float(ca["bytes accessed"])
        except Exception:  # noqa: BLE001 — cost analysis is best-effort
            pass
        args_sig = [a.str_short() for a in in_avals]
        payload = {
            "stage_kind": prog.kind,
            "key": prog.key_hash,
            "key_repr": prog.key_repr,
            "struct_sig": struct_sig,
            "norm_sig": norm_sig,
            "primitives": prims,
            "eqns": n_eqns,
            "consts": _const_records(closed.consts),
            "n_args": len(args_sig),
            "args": args_sig[:64],
            "in_dtypes": sorted({str(getattr(a, "dtype", "?"))
                                 for a in in_avals}),
            "out_dtypes": sorted({str(getattr(a, "dtype", "?"))
                                  for a in out_avals}),
            "flops": flops,
            "bytes_accessed": bytes_accessed,
        }
        from spark_rapids_tpu.aux.events import emit
        emit("stageProgram", **payload)
        with _LOCK:
            _STATS["ledger_rows"] += 1
    except Exception:  # noqa: BLE001 — audit must never fail the query
        with _LOCK:
            _STATS["ledger_errors"] += 1


def get_or_build(kind: str, key: Tuple,
                 build: Callable[[], Callable]) -> StageProgram:
    """THE lookup every jit site uses.  ``build()`` runs only on a miss
    and returns the raw python trace function; this helper owns jitting,
    trace counting, LRU bounding and the program wrapper."""
    full_key = (kind, key)
    with _LOCK:
        prog = _PROGRAMS.get(full_key)
        if prog is not None:
            _STATS["hits"] += 1
            _PROGRAMS.move_to_end(full_key)
            return prog
        _STATS["misses"] += 1
    # build outside the lock: expression tree walks can be slow and must
    # not serialize unrelated task threads; a racing double-build is
    # harmless (the FIRST insert wins, the loser's wrapper is discarded,
    # both programs are correct)
    import jax
    prog = StageProgram(kind, full_key, jax.jit(_counting(kind, build())))
    with _LOCK:
        existing = _PROGRAMS.get(full_key)
        if existing is not None:
            # the race loser's lookup was really a hit: reclassify its
            # recorded miss so hits+misses stays equal to lookups
            _STATS["misses"] -= 1
            _STATS["hits"] += 1
            return existing
        _PROGRAMS[full_key] = prog
        while len(_PROGRAMS) > _MAX_PROGRAMS:
            _PROGRAMS.popitem(last=False)
            _STATS["evictions"] += 1
    return prog


# ---------------------------------------------------------------------------
# stats / maintenance
# ---------------------------------------------------------------------------

def stats() -> Dict:
    with _LOCK:
        out = dict(_STATS)
        out["programs"] = len(_PROGRAMS)
        out["max_programs"] = _MAX_PROGRAMS
        out["traces_by_kind"] = dict(_TRACES_BY_KIND)
        out["dispatches_by_kind"] = dict(_DISPATCHES_BY_KIND)
        out["disk_cache_dir"] = _DISK["dir"]
        out["disk_cache_error"] = _DISK["error"]
        out["async_error"] = _ASYNC_ERROR[0]
        return out


def reset_stats() -> None:
    """Zeroes the counters (tests / bench phase boundaries); live
    programs stay cached."""
    with _LOCK:
        for k in _STATS:
            _STATS[k] = 0.0 if k in ("compile_s", "dispatch_s") else 0
        _TRACES_BY_KIND.clear()
        _DISPATCHES_BY_KIND.clear()
        _ASYNC_ERROR[0] = None


def clear() -> None:
    """Drops every cached program (tests; also releases the compiled
    executables' device handles)."""
    with _LOCK:
        _PROGRAMS.clear()


def set_max_programs(n: int) -> None:
    global _MAX_PROGRAMS
    with _LOCK:
        _MAX_PROGRAMS = max(1, int(n))
        while len(_PROGRAMS) > _MAX_PROGRAMS:
            _PROGRAMS.popitem(last=False)
            _STATS["evictions"] += 1


def set_persistent_cache_dir(path: Optional[str]) -> None:
    """Tier 2: point JAX's persistent compilation cache at ``path`` so
    compiled executables survive across queries AND sessions (conf
    ``spark.rapids.sql.compile.cacheDir``).  Thresholds drop to zero so
    every stage program persists.  Empty/None disables.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache was placed from
    outside: JAX reads the variable itself, and this function stands
    aside (the conf is ignored, nothing is assigned)."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        _DISK["dir"] = env_dir
        return
    path = (path or "").strip() or None
    if path == _DISK["dir"]:
        return
    import jax
    try:
        jax.config.update("jax_compilation_cache_dir", path)
        if path is not None:
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        _DISK["dir"] = path
        _DISK["error"] = None
    except Exception as e:  # noqa: BLE001 — the disk tier is optional;
        # a bad dir must not fail the query path
        _DISK["error"] = f"{type(e).__name__}: {e}"[:160]
