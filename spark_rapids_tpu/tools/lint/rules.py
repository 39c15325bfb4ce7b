"""The engine invariant rules.

Each rule guards an invariant a previous PR established by convention;
see docs/lint.md for the full table (id, invariant, rationale, how to
suppress).  Suppression: ``# lint: ok=<rule-id>`` on the flagged line or
the one above, or a baseline entry (core.py).
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set

from spark_rapids_tpu.tools.lint import lockgraph
from spark_rapids_tpu.tools.lint.core import LintContext, ParsedFile, Rule
from spark_rapids_tpu.tools.lint.facts import DYNAMIC_CONF_PREFIXES


def _call_name(node: ast.Call) -> Optional[str]:
    fn = node.func
    if isinstance(fn, ast.Name):
        return fn.id
    if isinstance(fn, ast.Attribute):
        return fn.attr
    return None


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted rendering of a Name/Attribute chain."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    elif isinstance(node, ast.Call):
        parts.append(_dotted(node.func) + "()")
    return ".".join(reversed(parts))


# ---------------------------------------------------------------------------
# jit-site
# ---------------------------------------------------------------------------

class JitSiteRule(Rule):
    """PR 8 migrated ~25 per-module jit caches to ONE entry point; a bare
    jit re-introduces uncounted traces and per-module cache lifetimes."""

    id = "jit-site"
    invariant = ("jax.jit / jax.pmap only inside exec/stage_compiler.py; "
                 "every jitted program goes through get_or_build")
    rationale = ("the executable cache's hit/trace counters (and the "
                 "'zero new traces on a warm run' guarantee) only hold "
                 "if nothing compiles around it")
    hint = ("obtain the program via exec.stage_compiler.get_or_build("
            "kind, key, build) so it is cached, trace-counted and "
            "persisted; or annotate '# lint: ok=jit-site' with a reason")

    ALLOWED_FILES = ("exec/stage_compiler.py",)
    _BANNED_ATTRS = frozenset({"jit", "pmap"})

    def check_file(self, ctx: LintContext, pf: ParsedFile) -> None:
        if pf.rel in self.ALLOWED_FILES:
            return
        # names imported straight off jax ('from jax import jit')
        jax_imported: Set[str] = set()
        for node in pf.nodes:
            if isinstance(node, ast.ImportFrom) and node.module == "jax":
                for alias in node.names:
                    if alias.name in self._BANNED_ATTRS:
                        jax_imported.add(alias.asname or alias.name)
        for node in pf.nodes:
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            bad = None
            if isinstance(fn, ast.Attribute) and \
                    fn.attr in self._BANNED_ATTRS and \
                    isinstance(fn.value, ast.Name) and fn.value.id == "jax":
                bad = f"jax.{fn.attr}"
            elif isinstance(fn, ast.Name) and fn.id in jax_imported:
                bad = f"jax {fn.id}"
            if bad:
                self.report(ctx, pf.rel, node.lineno,
                            f"bare {bad}(...) outside the stage compiler")


# ---------------------------------------------------------------------------
# aot-site
# ---------------------------------------------------------------------------

class AotSiteRule(Rule):
    """The compiled-program audit ledger (stageProgram rows) is recorded
    where programs are built — exec/stage_compiler.py.  An AOT
    ``.lower(...)/.compile()`` pipeline anywhere else produces an
    executable the ledger never sees, so the auditor's 'every cached
    program is audited' guarantee silently stops holding."""

    id = "aot-site"
    invariant = (".lower(args)/.compile() AOT compilation on jit "
                 "objects only inside exec/stage_compiler.py; every "
                 "program reaches the audit ledger")
    rationale = ("the auditor (tools audit) can only vouch for "
                 "programs whose build ran through the stage "
                 "compiler's ledger recorder; an out-of-band AOT "
                 "compile is an unaudited executable")
    hint = ("obtain the program via exec.stage_compiler.get_or_build "
            "(it owns AOT lowering AND ledger recording), or annotate "
            "'# lint: ok=aot-site' with a reason")

    ALLOWED_FILES = ("exec/stage_compiler.py",)

    #: the jax AOT entry points: ``jitted.lower(args)`` and
    #: ``jitted.trace(args)``.  Both take the program's example
    #: arguments, which is what separates them statically from
    #: ``str.lower()`` / attribute look-alikes (argless)
    _ENTRY_ATTRS = frozenset({"lower", "trace"})

    def check_file(self, ctx: LintContext, pf: ParsedFile) -> None:
        if pf.rel in self.ALLOWED_FILES:
            return
        # names bound from an AOT pipeline stage: entry calls
        # ('traced = f.trace(x)', 'lowered = f.lower(x)') and argless
        # '.lower()' on an already-tracked name ('lowered =
        # traced.lower()') — fixpoint over assignment order
        tracked: Set[str] = set()
        grew = True
        while grew:
            grew = False
            for node in pf.nodes:
                if not isinstance(node, ast.Assign):
                    continue
                if self._is_aot_stage(node.value, tracked):
                    for t in node.targets:
                        if isinstance(t, ast.Name) and \
                                t.id not in tracked:
                            tracked.add(t.id)
                            grew = True
        for node in pf.nodes:
            if not isinstance(node, ast.Call) or \
                    not isinstance(node.func, ast.Attribute):
                continue
            if self._is_entry_call(node):
                self.report(ctx, pf.rel, node.lineno,
                            f".{node.func.attr}(...) AOT "
                            "trace/lowering outside the stage compiler")
            elif node.func.attr == "compile":
                recv = node.func.value
                chained = isinstance(recv, ast.Call) and \
                    self._is_aot_stage(recv, tracked)
                from_tracked = isinstance(recv, ast.Name) and \
                    recv.id in tracked
                if chained or from_tracked:
                    self.report(ctx, pf.rel, node.lineno,
                                ".compile() of a traced/lowered "
                                "program outside the stage compiler")

    @classmethod
    def _is_entry_call(cls, node: ast.AST) -> bool:
        return (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in cls._ENTRY_ATTRS
                and bool(node.args or node.keywords))

    @classmethod
    def _is_aot_stage(cls, node: ast.AST, tracked: Set[str]) -> bool:
        """An expression yielding a Traced/Lowered: an entry call, or
        an argless ``.lower()`` whose receiver is itself a stage or a
        tracked name (``jitted.trace(x).lower()``)."""
        if cls._is_entry_call(node):
            return True
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr == "lower" and \
                not node.args and not node.keywords:
            recv = node.func.value
            if isinstance(recv, ast.Name) and recv.id in tracked:
                return True
            return cls._is_aot_stage(recv, tracked)
        return False


# ---------------------------------------------------------------------------
# sync-site
# ---------------------------------------------------------------------------

class SyncSiteRule(Rule):
    """The host-transition ledger (aux/transitions.py) can only claim
    'every blocking device sync is counted' if no code syncs around it.
    A raw ``block_until_ready`` / ``jax.device_get`` is a sync the
    ledger, tools profile and tools trace never see."""

    id = "sync-site"
    invariant = ("block_until_ready / jax.device_get only inside "
                 "aux/transitions.py; every blocking device sync "
                 "routes through the instrumented gateway")
    rationale = ("the transition ledger's per-query sync counts/seconds "
                 "(and tools profile's transitions/sync buckets) are "
                 "only trustworthy if the gateway sees every sync; a "
                 "raw sync is invisible latency")
    hint = ("sync through aux.transitions — block_until_ready(x, site), "
            "device_get(x, site), fetch(arr, site) or sync_int(x, site) "
            "— so it is timed, counted and attributed; or annotate "
            "'# lint: ok=sync-site' with a reason")

    ALLOWED_FILES = ("aux/transitions.py",)
    _BANNED = frozenset({"block_until_ready", "device_get"})

    def check_file(self, ctx: LintContext, pf: ParsedFile) -> None:
        if pf.rel in self.ALLOWED_FILES:
            return
        # names imported straight off jax ('from jax import device_get')
        jax_imported: Set[str] = set()
        for node in pf.nodes:
            if isinstance(node, ast.ImportFrom) and node.module == "jax":
                for alias in node.names:
                    if alias.name in self._BANNED:
                        jax_imported.add(alias.asname or alias.name)
        for node in pf.nodes:
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            bad = None
            if isinstance(fn, ast.Attribute) and \
                    fn.attr == "block_until_ready":
                # method form (arr.block_until_ready()) and module form
                # (jax.block_until_ready(x)) are both raw syncs; the
                # gateway's own wrapper is a same-named attribute on the
                # transitions module alias — not a sync at the call site
                recv = fn.value
                if not (isinstance(recv, ast.Name)
                        and recv.id in ("TR", "transitions")):
                    bad = "block_until_ready"
            elif isinstance(fn, ast.Attribute) and \
                    fn.attr == "device_get" and \
                    isinstance(fn.value, ast.Name) and fn.value.id == "jax":
                bad = "jax.device_get"
            elif isinstance(fn, ast.Name) and fn.id in jax_imported:
                bad = f"jax {fn.id}"
            if bad:
                self.report(ctx, pf.rel, node.lineno,
                            f"raw {bad}(...) outside the transition "
                            "gateway")


# ---------------------------------------------------------------------------
# conf-registry
# ---------------------------------------------------------------------------

_CONF_KEY = re.compile(r"^spark\.rapids\.[A-Za-z0-9_.]*[A-Za-z0-9_]$")


class ConfRegistryRule(Rule):
    """config.py's ConfEntry registry + generated docs/configs.md are the
    closed conf vocabulary (reference RapidsConf + generated docs)."""

    id = "conf-registry"
    invariant = ("every spark.rapids.* key literal resolves to a "
                 "registered ConfEntry AND a docs/configs.md row; every "
                 "registered key is documented and referenced somewhere")
    rationale = ("an unregistered key silently no-ops (no validation, "
                 "no default); an undocumented or dead key is drift "
                 "users hit")
    hint = ("register the key in config.py and regenerate docs "
            "(python -m spark_rapids_tpu.testing.docsgen), or delete "
            "the stale literal/entry")

    def __init__(self):
        #: names/strings read anywhere (for the dead-key direction)
        self._loaded_names: Set[str] = set()
        self._literals: Set[str] = set()
        #: key-prefix literals ("spark.rapids.chaos.") seen in source,
        #: plus every other string literal: a key counts as used when
        #: BOTH a prefix and its exact remainder exist as literals —
        #: evidence of prefix+suffix key construction
        #: (aux/faults.arm_from_conf), without a bare "spark.rapids."
        #: crediting everything
        self._prefix_literals: Set[str] = set()
        self._all_strings: Set[str] = set()

    def check_file(self, ctx: LintContext, pf: ParsedFile) -> None:
        is_config = pf.rel == "config.py"
        registered = ctx.facts.conf_registered
        # skip the registration's OWN key literal (its Constant line, not
        # the call line — they differ on multi-line registrations) so a
        # key only its registration mentions still reads as dead
        reg_lines = {(info.key, info.key_line)
                     for info in registered.values()} if is_config else ()
        for node in pf.nodes:
            if isinstance(node, ast.Name) and \
                    isinstance(node.ctx, ast.Load):
                self._loaded_names.add(node.id)
            elif isinstance(node, ast.Attribute):
                self._loaded_names.add(node.attr)
            elif isinstance(node, ast.Constant) and \
                    isinstance(node.value, str):
                s = node.value
                self._all_strings.add(s)
                if s.startswith("spark.rapids.") and s.endswith("."):
                    self._prefix_literals.add(s)
                if not _CONF_KEY.match(s):
                    continue
                if is_config and (s, node.lineno) in reg_lines:
                    continue        # the registration itself
                self._literals.add(s)
                if s in registered or \
                        s.startswith(DYNAMIC_CONF_PREFIXES):
                    if s not in ctx.facts.conf_doc_keys and \
                            ctx.facts.conf_doc_keys:
                        self.report(
                            ctx, pf.rel, node.lineno,
                            f"conf key {s!r} missing from "
                            "docs/configs.md (stale generated docs?)")
                    continue
                self.report(ctx, pf.rel, node.lineno,
                            f"conf key {s!r} is not a registered "
                            "ConfEntry")

    def finalize(self, ctx: LintContext) -> None:
        config_pf = ctx.file("config.py")
        if config_pf is None:
            return      # linting a fixture tree: no registry to audit
        for key, info in sorted(ctx.facts.conf_registered.items()):
            if key not in ctx.facts.conf_doc_keys and \
                    ctx.facts.conf_doc_keys:
                self.report(ctx, "config.py", info.line,
                            f"registered key {key!r} has no "
                            "docs/configs.md row (regenerate docs)")
            used = key in self._literals or (
                info.const_name is not None
                and info.const_name in self._loaded_names) or \
                any(key.startswith(p)
                    and key[len(p):] in self._all_strings
                    for p in self._prefix_literals)
            if not used:
                self.report(ctx, "config.py", info.line,
                            f"registered key {key!r} is dead: neither "
                            "the literal nor its ConfEntry constant is "
                            "read anywhere in the package")


# ---------------------------------------------------------------------------
# event-catalog
# ---------------------------------------------------------------------------

class EventCatalogRule(Rule):
    """aux/events.py EVENT_KINDS is the closed event vocabulary the
    offline reader relies on (migrated from the two ad-hoc ast tests in
    tests/test_tools.py)."""

    id = "event-catalog"
    invariant = ("every emit()/record_event kind literal is in "
                 "EVENT_KINDS, and every cataloged kind is referenced "
                 "outside the catalog")
    rationale = ("the offline tools (reader/profiler) key schemas off a "
                 "closed vocabulary; a dead kind is doc rot")
    hint = ("add the kind to aux/events.py EVENT_KINDS (grouped by "
            "emitter) or fix the call-site literal; delete kinds "
            "nothing emits")

    _CATALOG_FILE = "aux/events.py"

    def __init__(self):
        self._referenced: Set[str] = set()
        self._saw_catalog_file = False

    def check_file(self, ctx: LintContext, pf: ParsedFile) -> None:
        kinds = ctx.facts.event_kinds
        in_catalog = pf.rel == self._CATALOG_FILE
        if in_catalog:
            self._saw_catalog_file = True
        for node in pf.nodes:
            if isinstance(node, ast.Constant) and \
                    isinstance(node.value, str) and \
                    node.value in kinds and not in_catalog:
                self._referenced.add(node.value)
            if not isinstance(node, ast.Call):
                continue
            if _call_name(node) not in ("emit", "record_event"):
                continue
            if not node.args:
                continue
            first = node.args[0]
            if isinstance(first, ast.Constant) and \
                    isinstance(first.value, str) and \
                    first.value not in kinds:
                self.report(ctx, pf.rel, node.lineno,
                            f"event kind {first.value!r} is not in "
                            "EVENT_KINDS")

    def finalize(self, ctx: LintContext) -> None:
        if not self._saw_catalog_file:
            return      # fixture tree without the catalog module
        dead = ctx.facts.event_kinds - self._referenced
        for kind in sorted(dead):
            self.report(ctx, self._CATALOG_FILE,
                        ctx.facts.event_kinds_line,
                        f"cataloged event kind {kind!r} is never "
                        "referenced outside the catalog")


# ---------------------------------------------------------------------------
# traced-purity
# ---------------------------------------------------------------------------

class TracedPurityRule(Rule):
    """PR 8 caches compiled programs under value-independent keys; an
    impure traced function bakes one observation into every future run —
    a silent wrong-results bug only static analysis catches (Flare's
    whole-query-compilation purity argument, PAPERS.md)."""

    id = "traced-purity"
    invariant = ("functions passed to get_or_build must not read time/"
                 "randomness or force host syncs inside the trace")
    rationale = ("the cached executable replays forever under a "
                 "value-independent key: impurity at trace time is "
                 "baked in; host syncs serialize every dispatch")
    hint = ("hoist the impure read out of the build/run function and "
            "pass it as a runtime argument (literal promotion), or "
            "annotate '# lint: ok=traced-purity' with a reason")

    _TIME_MODULES = frozenset({"time"})
    _RANDOM_ROOTS = frozenset({"random"})
    _SYNC_CALLS = frozenset({"asarray", "device_get"})
    _DT_IMPURE = frozenset({"now", "utcnow", "today"})

    def check_file(self, ctx: LintContext, pf: ParsedFile) -> None:
        funcs_above: List[ast.FunctionDef] = [
            n for n in pf.nodes
            if isinstance(n, ast.FunctionDef)]
        for node in pf.nodes:
            if not (isinstance(node, ast.Call)
                    and _call_name(node) == "get_or_build"):
                continue
            build = None
            if len(node.args) >= 3:
                build = node.args[2]
            else:
                for kw in node.keywords:
                    if kw.arg == "build":
                        build = kw.value
            if build is None:
                continue
            target: Optional[ast.AST] = None
            if isinstance(build, ast.Lambda):
                target = build
            elif isinstance(build, ast.Name):
                # the `def build():` defined nearest above the call
                cands = [f for f in funcs_above
                         if f.name == build.id and f.lineno < node.lineno]
                if cands:
                    target = max(cands, key=lambda f: f.lineno)
            if target is None:
                continue
            for impure, line in self._impure_calls(target):
                self.report(ctx, pf.rel, line,
                            f"{impure} inside the traced build function "
                            f"passed to get_or_build at line "
                            f"{node.lineno}")

    def _impure_calls(self, fn: ast.AST):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not isinstance(f, ast.Attribute):
                continue
            dotted = _dotted(f)
            root = dotted.split(".")[0]
            parts = dotted.split(".")
            if root in self._TIME_MODULES and len(parts) > 1:
                yield f"{dotted}()", node.lineno
            elif root in self._RANDOM_ROOTS or "random" in parts[:-1]:
                # random.x(), np.random.x(), jax.random.x()
                yield f"{dotted}()", node.lineno
            elif f.attr in self._DT_IMPURE and "datetime" in parts:
                yield f"{dotted}()", node.lineno
            elif f.attr == "item" and not node.args and not node.keywords:
                yield "host sync .item()", node.lineno
            elif f.attr == "block_until_ready":
                yield "host sync .block_until_ready()", node.lineno
            elif f.attr in self._SYNC_CALLS and root in ("np", "numpy",
                                                         "jax"):
                yield f"host transfer {dotted}()", node.lineno


# ---------------------------------------------------------------------------
# spillable-close
# ---------------------------------------------------------------------------

class SpillableCloseRule(Rule):
    """PR 4's deterministic close discipline: a generator that pulls a
    child's execute_partition stream must propagate close on early exit,
    else queued spillables / producer threads leak until GC."""

    id = "spillable-close"
    invariant = ("a generator iterating child.execute_partition(...) "
                 "routes teardown through closing_source / close_iter")
    rationale = ("abandoning a suspended generator leaves prefetch "
                 "spools and catalog-registered spillables to "
                 "non-deterministic GC; limits/early-exit paths leak")
    hint = ("wrap the stream: 'with closing_source(child."
            "execute_partition(p)) as it:' (plan/base.py), close it in "
            "a finally via close_iter, or annotate "
            "'# lint: ok=spillable-close' with why leak-free")

    def check_file(self, ctx: LintContext, pf: ParsedFile) -> None:
        for fn in self._generator_functions(pf.tree):
            self._check_generator(ctx, pf, fn)

    @staticmethod
    def _generator_functions(tree: ast.Module) -> List[ast.FunctionDef]:
        """FunctionDefs whose OWN body yields (one ownership pass: a
        yield inside a nested def belongs to the nested def)."""
        out: List[ast.FunctionDef] = []
        seen: Set[int] = set()

        def descend(node, current):
            for child in ast.iter_child_nodes(node):
                nxt = current
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef, ast.Lambda)):
                    nxt = child
                elif isinstance(child, (ast.Yield, ast.YieldFrom)) and \
                        isinstance(current, ast.FunctionDef) and \
                        id(current) not in seen:
                    seen.add(id(current))
                    out.append(current)
                descend(child, nxt)

        descend(tree, None)
        return out

    #: wrappers that keep the inner iterator LAZY (abandoning the wrapper
    #: abandons the stream) — seen through when matching loop iterables;
    #: eager consumers (list, sorted, ...) exhaust-and-finish and are safe
    _LAZY_WRAPPERS = frozenset({"enumerate", "zip", "iter", "map",
                                "filter", "islice", "chain"})

    @classmethod
    def _is_exec_part_call(cls, node: ast.AST) -> bool:
        if not isinstance(node, ast.Call):
            return False
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr == "execute_partition":
            return True
        name = _call_name(node)
        if name in cls._LAZY_WRAPPERS:
            return any(cls._is_exec_part_call(a) for a in node.args)
        return False

    def _check_generator(self, ctx: LintContext, pf: ParsedFile,
                         fn: ast.FunctionDef) -> None:
        # names the function closes explicitly / passes to close helpers
        closed_names: Set[str] = set()
        uses_close_helper = False
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node)
            if name in ("close_iter", "closing_source"):
                uses_close_helper = True
                for arg in node.args:
                    if isinstance(arg, ast.Name):
                        closed_names.add(arg.id)
            elif isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "close" and \
                    isinstance(node.func.value, ast.Name):
                closed_names.add(node.func.value.id)
        # names assigned from execute_partition calls
        iter_names: Dict[str, int] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and \
                    self._is_exec_part_call(node.value):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        iter_names[t.id] = node.lineno
        self._walk(ctx, pf, fn, fn.body, protected=False,
                   closed_names=closed_names, iter_names=iter_names,
                   uses_close_helper=uses_close_helper)

    def _walk(self, ctx, pf, fn, body, protected, closed_names,
              iter_names, uses_close_helper) -> None:
        for node in body:
            prot = protected
            if isinstance(node, ast.With):
                if any(_call_name(item.context_expr) == "closing_source"
                       for item in node.items
                       if isinstance(item.context_expr, ast.Call)):
                    prot = True
                self._walk(ctx, pf, fn, node.body, prot, closed_names,
                           iter_names, uses_close_helper)
                continue
            if isinstance(node, ast.Try):
                fin_prot = prot or any(
                    isinstance(sub, ast.Call)
                    and _call_name(sub) == "close_iter"
                    for stmt in node.finalbody
                    for sub in ast.walk(stmt))
                for sub_body in (node.body, node.orelse):
                    self._walk(ctx, pf, fn, sub_body, fin_prot,
                               closed_names, iter_names,
                               uses_close_helper)
                for handler in node.handlers:
                    self._walk(ctx, pf, fn, handler.body, fin_prot,
                               closed_names, iter_names,
                               uses_close_helper)
                self._walk(ctx, pf, fn, node.finalbody, prot,
                           closed_names, iter_names, uses_close_helper)
                continue
            if isinstance(node, ast.For):
                self._check_loop(ctx, pf, node, prot, closed_names,
                                 iter_names)
                self._walk(ctx, pf, fn, node.body + node.orelse, prot,
                           closed_names, iter_names, uses_close_helper)
                continue
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue        # nested defs are their own generators
            # other compound statements: descend into their bodies
            for attr in ("body", "orelse"):
                sub = getattr(node, attr, None)
                if isinstance(sub, list):
                    self._walk(ctx, pf, fn, sub, prot, closed_names,
                               iter_names, uses_close_helper)

    def _check_loop(self, ctx, pf, node: ast.For, protected,
                    closed_names, iter_names) -> None:
        if protected:
            return
        it = node.iter
        if self._is_exec_part_call(it):
            self.report(ctx, pf.rel, node.lineno,
                        "generator iterates a child execute_partition "
                        "stream without close propagation")
        elif isinstance(it, ast.Name) and it.id in iter_names and \
                it.id not in closed_names:
            self.report(ctx, pf.rel, node.lineno,
                        f"generator iterates {it.id!r} (an "
                        "execute_partition stream) without close "
                        "propagation")


# ---------------------------------------------------------------------------
# fault-point
# ---------------------------------------------------------------------------

class FaultPointRule(Rule):
    """aux/faults.py CHAOS_POINTS is the closed chaos vocabulary; a typo'd
    point name arms nothing and the chaos test silently tests nothing."""

    id = "fault-point"
    invariant = ("maybe_fire()/arm_fault() names match the registered "
                 "CHAOS_POINTS catalog")
    rationale = ("an uncataloged point can never be armed from conf — "
                 "the call site is dead chaos coverage")
    hint = ("add the point to aux/faults.py CHAOS_POINTS (with its conf "
            "key and exception factory) or fix the name")

    def visit(self, ctx: LintContext, pf: ParsedFile,
              node: ast.AST) -> None:
        if not (isinstance(node, ast.Call)
                and _call_name(node) in ("maybe_fire", "arm_fault")):
            return
        if not node.args:
            return
        first = node.args[0]
        if isinstance(first, ast.Constant) and \
                isinstance(first.value, str) and \
                ctx.facts.fault_points and \
                first.value not in ctx.facts.fault_points:
            self.report(ctx, pf.rel, node.lineno,
                        f"fault point {first.value!r} is not in the "
                        "CHAOS_POINTS catalog")


# ---------------------------------------------------------------------------
# retry-frame
# ---------------------------------------------------------------------------

class RetryFrameRule(Rule):
    """Tracked allocation points outside memory/ must sit inside a
    function handed to a with_retry frame — an unframed RetryOOM escapes
    as a hard query error instead of spill/split recovery."""

    id = "retry-frame"
    invariant = ("catalog .reserve()/maybe_inject_oom() call sites "
                 "outside memory/ are reachable only through a "
                 "with_retry* frame")
    rationale = ("RetryOOM/SplitAndRetryOOM are recovery signals; a "
                 "call site no frame absorbs turns memory pressure "
                 "into query failure")
    hint = ("wrap the work: fn passed to with_retry/with_retry_no_split"
            "/drain_with_retry (memory/retry.py), allocate through "
            "SpillableColumnarBatch/add_device_batch, or annotate "
            "'# lint: ok=retry-frame' with why it cannot OOM")

    _RETRY_WRAPPERS = frozenset({"with_retry", "with_retry_no_split",
                                 "drain_with_retry"})
    _TRACKED = frozenset({"reserve", "maybe_inject_oom"})

    def check_file(self, ctx: LintContext, pf: ParsedFile) -> None:
        if pf.rel.startswith("memory/"):
            return
        # function names passed (as Name args) into retry wrappers
        framed: Set[str] = set()
        for node in ast.walk(pf.tree):
            if isinstance(node, ast.Call) and \
                    _call_name(node) in self._RETRY_WRAPPERS:
                for arg in list(node.args) + \
                        [kw.value for kw in node.keywords]:
                    if isinstance(arg, ast.Name):
                        framed.add(arg.id)
        self._descend(ctx, pf, pf.tree, [], framed)

    def _descend(self, ctx, pf, node, fstack: List[str],
                 framed: Set[str]) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._descend(ctx, pf, child, fstack + [child.name],
                              framed)
                continue
            if isinstance(child, ast.Call):
                name = _call_name(child)
                if name in self._TRACKED and \
                        not any(f in framed for f in fstack):
                    # .reserve on non-catalog receivers is out of scope:
                    # only flag attribute calls that look like catalog
                    # admission or the bare injection hook
                    if name == "reserve" and not isinstance(
                            child.func, ast.Attribute):
                        pass
                    else:
                        self.report(
                            ctx, pf.rel, child.lineno,
                            f"tracked allocation point {name}() outside "
                            "any with_retry frame")
            self._descend(ctx, pf, child, fstack, framed)


# ---------------------------------------------------------------------------
# encoded-materialize
# ---------------------------------------------------------------------------

class EncodedMaterializeRule(Rule):
    """The encoded-execution PR keeps dictionary/RLE columns alive past
    the scan; decode is only correct (and only counted — decoded bytes,
    fallback events, AutoTuner evidence) through the sanctioned
    ``materialize*`` helpers.  A stray decode primitive silently
    re-materializes what the scan kept encoded AND dodges the ledger."""

    id = "encoded-materialize"
    invariant = ("the decode primitives (decode_dictionary / decode_rle "
                 "/ arrow .dictionary_decode) are called only inside "
                 "columnar/encoding.py; operators decode via the "
                 "materialize*/host_decoded helpers")
    rationale = ("every decode must flow through the one module that "
                 "counts decoded bytes and emits encodingFallback "
                 "events — an uncounted decode both wastes the encoding "
                 "and blinds the AutoTuner's fallback rule")
    hint = ("call encoding.materialize()/materialize_batch()/"
            "materialize_rle_batch() (device) or encoding.host_decoded() "
            "(arrow), or annotate '# lint: ok=encoded-materialize' with "
            "a reason")

    ALLOWED_FILES = ("columnar/encoding.py",)
    _DECODE_NAMES = frozenset({"decode_dictionary", "decode_rle",
                               "dictionary_decode"})

    def visit(self, ctx: LintContext, pf: ParsedFile,
              node: ast.AST) -> None:
        if pf.rel in self.ALLOWED_FILES:
            return
        if not isinstance(node, ast.Call):
            return
        name = _call_name(node)
        if name in self._DECODE_NAMES:
            self.report(ctx, pf.rel, node.lineno,
                        f"raw decode primitive {name}() outside "
                        "columnar/encoding.py")


# ---------------------------------------------------------------------------
# collective-site
# ---------------------------------------------------------------------------

class CollectiveSiteRule(Rule):
    """The SPMD PR concentrates every mesh collective in ``parallel/``:
    the in-mesh exchange (spmd.py) and the fused all-to-all shuffle
    (collective.py) own the shard_map programs, their HBM guards, their
    chaos point, and their host-staged fallback.  A collective primitive
    anywhere else is an unguarded whole-mesh synchronization point — no
    fallback, no iciExchange accounting, and a lost chip fails the query
    instead of degrading."""

    id = "collective-site"
    invariant = ("JAX collective primitives (shard_map, psum, "
                 "all_to_all, ppermute, axis_index) only inside "
                 "parallel/")
    rationale = ("collectives synchronize the whole mesh: the parallel/ "
                 "modules wrap them in the chaos point, the HBM guard "
                 "and the host-staged fallback; a stray collective has "
                 "none of those and turns one lost chip into a failed "
                 "query")
    hint = ("route mesh data movement through parallel/spmd.py / "
            "parallel/collective.py, or annotate "
            "'# lint: ok=collective-site' with a reason")

    ALLOWED_DIRS = ("parallel/",)
    _BANNED = frozenset({"shard_map", "psum", "all_to_all", "ppermute",
                         "axis_index"})

    def check_file(self, ctx: LintContext, pf: ParsedFile) -> None:
        if pf.rel.startswith(self.ALLOWED_DIRS):
            return
        # names imported straight from jax modules
        # ('from jax import shard_map',
        #  'from jax.lax import all_to_all')
        imported: Set[str] = set()
        for node in pf.nodes:
            if isinstance(node, ast.ImportFrom) and node.module and \
                    node.module.split(".")[0] == "jax":
                for alias in node.names:
                    if alias.name in self._BANNED:
                        imported.add(alias.asname or alias.name)
        for node in pf.nodes:
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            bad = None
            if isinstance(fn, ast.Attribute) and fn.attr in self._BANNED:
                root = _dotted(fn)
                # attribute calls count only when rooted in a jax
                # module path (jax.lax.psum, lax.all_to_all) — a
                # method named .psum on an engine object is not a
                # collective
                if root.split(".")[0] in ("jax", "lax"):
                    bad = root
            elif isinstance(fn, ast.Name) and fn.id in imported:
                bad = fn.id
            if bad:
                self.report(ctx, pf.rel, node.lineno,
                            f"mesh collective {bad}(...) outside "
                            "parallel/")


# ---------------------------------------------------------------------------
# lock-order
# ---------------------------------------------------------------------------

class LockOrderRule(Rule):
    """Static half of the lock-order cross-check (runtime half:
    aux/lockorder.py under spark.rapids.debug.lockOrder)."""

    id = "lock-order"
    invariant = ("the static lock-acquisition graph over the tracked "
                 "catalog/arbiter/semaphore/spool locks only has edges "
                 "that go FORWARD in CANONICAL_LOCK_ORDER")
    rationale = ("a backward edge is a lock-inversion deadlock waiting "
                 "for the right interleaving; the arbiter must stay "
                 "the innermost rendezvous")
    hint = ("move the cross-lock call outside the with block (snapshot "
            "under the lock, call after), or re-declare the canonical "
            "order in aux/lockorder.py if the hierarchy legitimately "
            "changed — static rule and runtime validator share it")

    def finalize(self, ctx: LintContext) -> None:
        graph = lockgraph.analyze(ctx.files)
        order = ctx.facts.canonical_lock_order
        rank = {n: i for i, n in enumerate(order)}
        ctx.extras["lock_order"] = list(order)
        ctx.extras["lock_edges"] = {
            (e.held, e.acquired, e.file, e.line) for e in graph.edges}
        ctx.extras["locks_found"] = sorted(graph.locks)
        for e in sorted(graph.edges,
                        key=lambda e: (e.file, e.line, e.acquired)):
            if e.held not in rank or e.acquired not in rank:
                self.report(ctx, e.file, e.line,
                            f"lock {e.held!r} or {e.acquired!r} missing "
                            "from CANONICAL_LOCK_ORDER "
                            "(aux/lockorder.py)")
            elif rank[e.acquired] <= rank[e.held]:
                self.report(ctx, e.file, e.line,
                            f"acquires {e.acquired!r} while holding "
                            f"{e.held!r}: backward against the "
                            f"canonical order {'<'.join(order)}")


class ConfModuleGlobalRule(Rule):
    """PR 15 prerequisite: per-query conf must travel WITH the plan.  A
    conf value copied into a module global at plan time is process-wide
    state — two concurrent sessions planning with different confs race
    each other's values, which the serving layer (N admitted queries at
    once) turns from a theoretical hazard into a daily one."""

    id = "conf-module-global"
    invariant = ("no NEW conf-driven module-global assignments: a conf "
                 "value read at plan time rides the converted plan/exec "
                 "instance (or a call argument), never a module "
                 "attribute")
    rationale = ("module globals are shared by every session in the "
                 "process; concurrent queries with different confs "
                 "(admission-time autotune deltas, per-tenant settings) "
                 "would race each other's behavior knobs")
    hint = ("set the value on the converted exec instance at convert "
            "time (see exec/joins.py build_swap_* or exec/exchange.py "
            "shrink_threshold_bytes) or thread it as an argument; "
            "'# lint: ok=conf-module-global' is reserved for the frozen "
            "legacy set below")

    #: the pre-PR-15 legacy assignments in plan/overrides.apply — this
    #: set may only SHRINK (migrate a knob onto its instances, then
    #: delete its name here); adding a name defeats the rule
    LEGACY = frozenset({
        "FORCE_REPARTITION_BELOW_DEPTH", "FORCE_OUT_OF_CORE_SORT",
        "FORCE_RUNNING_WINDOW", "FORCE_BOUNDED_WINDOW",
        "BOUNDED_WINDOW_MAX_SPAN", "PIPELINE_ENABLED", "PIPELINE_DEPTH",
        "PIPELINE_MAX_BYTES", "ARBITRATION_ENABLED", "MAX_BLOCK_MS",
        "ASYNC_COMPILE", "AUDIT_LEDGER", "LITERAL_PROMOTION",
        "ENCODING_ENABLED", "LATE_MATERIALIZATION",
        "MAX_DICTIONARY_SIZE", "RLE_ENABLED", "SPILL_CODEC",
    })

    @staticmethod
    def _module_aliases(pf: ParsedFile) -> Set[str]:
        """Names bound to modules in this file (``import m``,
        ``import a.b as m`` — and ``from pkg import mod`` heuristically:
        lowercase names from a package import)."""
        out: Set[str] = set()
        for node in pf.nodes:
            if isinstance(node, ast.Import):
                for a in node.names:
                    out.add(a.asname or a.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    name = a.asname or a.name
                    if name.islower():
                        out.add(name)
        return out

    @staticmethod
    def _conf_derived(value: ast.AST) -> bool:
        """The assigned expression reads a conf (conf.get / m.conf.get /
        a bare ``conf`` name feeding a converter)."""
        for n in ast.walk(value):
            if isinstance(n, ast.Call) and \
                    isinstance(n.func, ast.Attribute) and \
                    n.func.attr == "get":
                d = _dotted(n.func.value)
                if d == "conf" or d.endswith(".conf"):
                    return True
            if isinstance(n, ast.Name) and n.id == "conf":
                return True
        return False

    def check_file(self, ctx: LintContext, pf: ParsedFile) -> None:
        aliases = None
        for node in pf.nodes:
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            t = node.targets[0]
            if not (isinstance(t, ast.Attribute) and
                    isinstance(t.value, ast.Name)):
                continue
            if not t.attr.isupper():
                continue        # instance/field assignment, not a knob
            if not self._conf_derived(node.value):
                continue
            if aliases is None:
                aliases = self._module_aliases(pf)
            if t.value.id not in aliases:
                continue        # attribute on an object, not a module
            if t.attr in self.LEGACY and pf.rel == "plan/overrides.py":
                continue
            self.report(ctx, pf.rel, node.lineno,
                        f"conf-driven module global "
                        f"{t.value.id}.{t.attr}: per-query conf must "
                        "ride the plan instance, not process state")


def default_rules() -> List[Rule]:
    """Fresh rule instances (rules keep per-run state)."""
    return [
        JitSiteRule(),
        AotSiteRule(),
        SyncSiteRule(),
        ConfRegistryRule(),
        EventCatalogRule(),
        TracedPurityRule(),
        SpillableCloseRule(),
        FaultPointRule(),
        RetryFrameRule(),
        EncodedMaterializeRule(),
        CollectiveSiteRule(),
        LockOrderRule(),
        ConfModuleGlobalRule(),
    ]
