"""Engine invariant linter tests: the tier-1 repo gate (zero findings
over spark_rapids_tpu/), one violating + one clean fixture per rule,
baseline/inline suppression semantics, JSON output schema, the CLI
subcommand, the static lock graph, and the static<->runtime lock-order
cross-check (reference: the plugin's api_validation module + the
GpuOverrides tagging discipline, applied to our own source)."""

import json
import os
import subprocess
import sys
import textwrap

from spark_rapids_tpu.tools.lint import (load_facts, render_text,
                                         run_lint, write_baseline)
from spark_rapids_tpu.tools.lint.rules import (ConfRegistryRule,
                                               EventCatalogRule,
                                               FaultPointRule, JitSiteRule,
                                               LockOrderRule,
                                               RetryFrameRule,
                                               SpillableCloseRule,
                                               TracedPurityRule)


def _lint_snippet(tmp_path, source, rules, name="snippet.py"):
    # each snippet lints from its own root so bad/clean pairs in one
    # test never see each other
    root = tmp_path / name.replace(".py", "")
    root.mkdir()
    (root / name).write_text(textwrap.dedent(source))
    return run_lint(root=str(root), rules=rules, baseline_path="")


def _findings(report, rule_id):
    return [f for f in report.active if f.rule == rule_id]


# ---------------------------------------------------------------------------
# the repo gate
# ---------------------------------------------------------------------------

def test_repo_is_clean():
    """THE acceptance gate: the full linter over spark_rapids_tpu/ has
    zero non-baselined findings and stays inside the 10s budget."""
    report = run_lint()
    assert not report.fact_errors, report.fact_errors
    msgs = [f"{f.location}: {f.rule}: {f.message}"
            for f in report.active]
    assert not msgs, "lint findings on the repo:\n" + "\n".join(msgs)
    assert report.files_scanned > 100
    assert report.elapsed_s < 10.0


def test_repo_rules_actually_scanned_their_surfaces():
    """Zero findings must mean 'checked and clean', not 'saw nothing':
    the facts and the analyzed surfaces are non-trivially populated."""
    facts = load_facts()
    assert len(facts.event_kinds) >= 25
    assert len(facts.fault_points) >= 8
    assert len(facts.conf_registered) >= 80
    assert len(facts.conf_doc_keys) >= 100
    assert facts.canonical_lock_order == (
        "spool", "catalog", "semaphore", "arbiter")
    report = run_lint(rules=[LockOrderRule()], baseline_path="")
    assert report.extras["locks_found"] == [
        "arbiter", "catalog", "semaphore", "spool"]
    # the engine's real cross-lock call sites resolve statically
    edges = {(h, a) for (h, a, _f, _l) in report.extras["lock_edges"]}
    assert ("catalog", "arbiter") in edges
    assert ("semaphore", "arbiter") in edges
    assert ("spool", "arbiter") in edges
    assert ("spool", "semaphore") in edges


# ---------------------------------------------------------------------------
# per-rule fixtures: one violating + one clean snippet each
# ---------------------------------------------------------------------------

def test_jit_site_rule(tmp_path):
    bad = """
        import jax
        from jax import jit

        _CACHE = {}

        def compile_me(fn):
            return jax.jit(fn)

        def also_bad(fn):
            return jit(fn)
    """
    report = _lint_snippet(tmp_path, bad, [JitSiteRule()])
    assert len(_findings(report, "jit-site")) == 2
    clean = """
        from spark_rapids_tpu.exec.stage_compiler import get_or_build

        def compile_me(key, build):
            return get_or_build("my.kind", key, build)
    """
    report = _lint_snippet(tmp_path, clean, [JitSiteRule()],
                           name="clean.py")
    assert not _findings(report, "jit-site")


def test_aot_site_rule(tmp_path):
    from spark_rapids_tpu.tools.lint.rules import AotSiteRule
    bad = """
        def warm(jitted, x):
            lowered = jitted.lower(x)
            return lowered.compile()

        def chained(jitted, x):
            return jitted.lower(x).compile()

        def trace_style(jitted, x):
            traced = jitted.trace(x)
            lowered2 = traced.lower()
            return lowered2.compile()
    """
    report = _lint_snippet(tmp_path, bad, [AotSiteRule()])
    finds = _findings(report, "aot-site")
    # two .lower( + one .trace( entries, three .compile() sinks (bound,
    # chained, and via the argless traced.lower() hop)
    assert len(finds) == 6, [f.message for f in finds]
    assert any(".trace(" in f.message for f in finds)
    clean = """
        import re

        def fine(s, params, compiler_cls):
            pat = re.compile(s.lower())          # str.lower(): no args
            return compiler_cls(pat, params).compile()   # not a Lowered
    """
    report = _lint_snippet(tmp_path, clean, [AotSiteRule()],
                           name="clean.py")
    assert not _findings(report, "aot-site")


def test_conf_registry_rule(tmp_path):
    bad = """
        def read(conf):
            return conf.get("spark.rapids.sql.notARegisteredKey")
    """
    report = _lint_snippet(tmp_path, bad, [ConfRegistryRule()])
    finds = _findings(report, "conf-registry")
    assert len(finds) == 1 and "notARegisteredKey" in finds[0].message
    clean = """
        def read(conf):
            # registered + documented; prefix literals are builders
            base = "spark.rapids.chaos."
            return conf.get("spark.rapids.sql.batchSizeBytes")
    """
    report = _lint_snippet(tmp_path, clean, [ConfRegistryRule()],
                           name="clean.py")
    assert not _findings(report, "conf-registry")


def test_event_catalog_rule(tmp_path):
    bad = """
        from spark_rapids_tpu.aux.events import emit

        def notify():
            emit("definitelyNotAKind", x=1)
    """
    report = _lint_snippet(tmp_path, bad, [EventCatalogRule()])
    assert len(_findings(report, "event-catalog")) == 1
    clean = """
        from spark_rapids_tpu.aux.events import emit

        def notify():
            emit("spill", bytes=1)
    """
    report = _lint_snippet(tmp_path, clean, [EventCatalogRule()],
                           name="clean.py")
    assert not _findings(report, "event-catalog")


def test_traced_purity_rule(tmp_path):
    bad = """
        import time
        import numpy as np
        from spark_rapids_tpu.exec.stage_compiler import get_or_build

        def make(key):
            def build():
                def run(x):
                    t = time.monotonic()
                    y = np.asarray(x)
                    return y.item()
                return run
            return get_or_build("k", key, build)
    """
    report = _lint_snippet(tmp_path, bad, [TracedPurityRule()])
    msgs = [f.message for f in _findings(report, "traced-purity")]
    assert len(msgs) == 3, msgs
    assert any("time.monotonic" in m for m in msgs)
    assert any("np.asarray" in m for m in msgs)
    assert any(".item()" in m for m in msgs)
    clean = """
        import numpy as np
        from spark_rapids_tpu.exec.stage_compiler import get_or_build

        def make(key, shape):
            def build():
                size = int(np.prod(shape))   # static, trace-time constant

                def run(x):
                    return x.reshape((size,))
                return run
            return get_or_build("k", key, build)
    """
    report = _lint_snippet(tmp_path, clean, [TracedPurityRule()],
                           name="clean.py")
    assert not _findings(report, "traced-purity")


def test_spillable_close_rule(tmp_path):
    bad = """
        class MyExec:
            def execute_partition(self, pidx):
                for b in self.child.execute_partition(pidx):
                    yield transform(b)
    """
    report = _lint_snippet(tmp_path, bad, [SpillableCloseRule()])
    assert len(_findings(report, "spillable-close")) == 1
    clean = """
        from spark_rapids_tpu.plan.base import close_iter, closing_source

        class WithExec:
            def execute_partition(self, pidx):
                with closing_source(
                        self.child.execute_partition(pidx)) as it:
                    for b in it:
                        yield transform(b)

        class FinallyExec:
            def execute_partition(self, pidx):
                it = self.child.execute_partition(pidx)
                try:
                    for b in it:
                        yield transform(b)
                finally:
                    close_iter(it)

        class DelegatingExec:
            def execute_partition(self, pidx):
                # yield from propagates close() natively
                yield from self.child.execute_partition(pidx)
    """
    report = _lint_snippet(tmp_path, clean, [SpillableCloseRule()],
                           name="clean.py")
    assert not _findings(report, "spillable-close")


def test_spillable_close_rule_sees_through_lazy_wrappers(tmp_path):
    """enumerate/zip keep the stream lazy: abandoning the wrapper
    abandons the generator — the exact pre-PR TpuSampleExec pattern."""
    bad = """
        class MyExec:
            def execute_partition(self, pidx):
                for i, b in enumerate(
                        self.child.execute_partition(pidx)):
                    yield transform(b)
    """
    report = _lint_snippet(tmp_path, bad, [SpillableCloseRule()])
    assert len(_findings(report, "spillable-close")) == 1
    clean = """
        class EagerExec:
            def execute_partition(self, pidx):
                # list() exhausts the stream: exhaustion IS teardown
                for b in list(self.child.execute_partition(pidx)):
                    yield transform(b)
    """
    report = _lint_snippet(tmp_path, clean, [SpillableCloseRule()],
                           name="clean.py")
    assert not _findings(report, "spillable-close")


def test_conf_registry_dead_key_direction_fires(tmp_path):
    """A registered key nothing reads IS flagged (multi-line
    registrations put the key literal below the call line — the
    registration's own literal must not count as a use)."""
    src = """
        from spark_rapids_tpu.config import conf_bool

        DEAD = conf_bool(
            "spark.rapids.totally.deadKey",
            "nothing ever reads this",
            True)
        LIVE = conf_bool(
            "spark.rapids.totally.liveKey",
            "read below",
            True)

        def read(conf):
            return conf.get(LIVE.key)
    """
    root = tmp_path / "deadcfg"
    root.mkdir()
    (root / "config.py").write_text(textwrap.dedent(src))
    # facts from the FIXTURE tree: its config.py is the registry under
    # audit (the real package's registry would shadow it)
    report = run_lint(root=str(root), rules=[ConfRegistryRule()],
                      baseline_path="",
                      facts=load_facts(package_root=str(root)))
    dead = [f for f in _findings(report, "conf-registry")
            if "is dead" in f.message]
    assert len(dead) == 1 and "deadKey" in dead[0].message, \
        [f.message for f in _findings(report, "conf-registry")]


def test_fault_point_rule(tmp_path):
    bad = """
        from spark_rapids_tpu.aux.faults import maybe_fire

        def work():
            maybe_fire("shuffle.fletch")   # typo: never armable
    """
    report = _lint_snippet(tmp_path, bad, [FaultPointRule()])
    assert len(_findings(report, "fault-point")) == 1
    clean = """
        from spark_rapids_tpu.aux.faults import maybe_fire

        def work():
            maybe_fire("shuffle.fetch")
    """
    report = _lint_snippet(tmp_path, clean, [FaultPointRule()],
                           name="clean.py")
    assert not _findings(report, "fault-point")


def test_collective_site_rule(tmp_path):
    from spark_rapids_tpu.tools.lint.rules import CollectiveSiteRule
    bad = """
        import jax
        from jax import shard_map
        from jax import lax

        def my_exchange(fn, mesh, x):
            prog = shard_map(fn, mesh=mesh)          # imported name
            total = jax.lax.psum(x, "data")          # jax.lax attr
            moved = lax.all_to_all(x, "data", 0, 0)  # lax attr
            return prog, total, moved
    """
    report = _lint_snippet(tmp_path, bad, [CollectiveSiteRule()])
    finds = _findings(report, "collective-site")
    assert len(finds) == 3, [f.message for f in finds]
    # a file under parallel/ is the sanctioned home
    root = tmp_path / "pkg"
    (root / "parallel").mkdir(parents=True)
    (root / "parallel" / "spmd2.py").write_text(textwrap.dedent(bad))
    from spark_rapids_tpu.tools.lint import run_lint
    report = run_lint(root=str(root), rules=[CollectiveSiteRule()],
                      baseline_path="")
    assert not _findings(report, "collective-site")
    # method look-alikes on engine objects are not collectives
    clean = """
        def fine(store, x):
            return store.psum(x) + x.all_to_all()
    """
    report = _lint_snippet(tmp_path, clean, [CollectiveSiteRule()],
                           name="clean.py")
    assert not _findings(report, "collective-site")


def test_sync_site_rule(tmp_path):
    from spark_rapids_tpu.tools.lint.rules import SyncSiteRule
    bad = """
        import jax
        from jax import device_get as dget

        def raw_syncs(arr, x):
            arr.block_until_ready()          # method form
            jax.block_until_ready(x)         # module form
            y = jax.device_get(x)            # attr form
            return dget(y)                   # from-import alias
    """
    report = _lint_snippet(tmp_path, bad, [SyncSiteRule()])
    finds = _findings(report, "sync-site")
    assert len(finds) == 4, [f.message for f in finds]
    # the gateway itself is the sanctioned home
    root = tmp_path / "pkg"
    (root / "aux").mkdir(parents=True)
    (root / "aux" / "transitions.py").write_text(textwrap.dedent(bad))
    from spark_rapids_tpu.tools.lint import run_lint
    report = run_lint(root=str(root), rules=[SyncSiteRule()],
                      baseline_path="")
    assert not _findings(report, "sync-site")
    # gateway wrappers at the call site are not raw syncs
    clean = """
        from spark_rapids_tpu.aux import transitions as TR

        def fine(arr, x):
            TR.block_until_ready(arr, site="dispatch")
            return TR.device_get(x, site="test")
    """
    report = _lint_snippet(tmp_path, clean, [SyncSiteRule()],
                           name="clean.py")
    assert not _findings(report, "sync-site")


def test_encoded_materialize_rule(tmp_path):
    from spark_rapids_tpu.tools.lint.rules import EncodedMaterializeRule
    bad = """
        from spark_rapids_tpu.columnar.encoding import decode_dictionary

        def sneak(col, jnp):
            data, v, ln = decode_dictionary(col.data, col.validity,
                                            planes, jnp)
            return col.arrow.dictionary_decode()
    """
    report = _lint_snippet(tmp_path, bad, [EncodedMaterializeRule()])
    assert len(_findings(report, "encoded-materialize")) == 2
    clean = """
        from spark_rapids_tpu.columnar.encoding import (host_decoded,
                                                        materialize_batch)

        def sanctioned(batch, arr):
            return materialize_batch(batch), host_decoded(arr)
    """
    report = _lint_snippet(tmp_path, clean, [EncodedMaterializeRule()],
                           name="clean.py")
    assert not _findings(report, "encoded-materialize")


def test_retry_frame_rule(tmp_path):
    bad = """
        from spark_rapids_tpu.memory.retry import maybe_inject_oom

        def stage_batch(catalog, nbytes):
            catalog.reserve(nbytes)
            maybe_inject_oom()
    """
    report = _lint_snippet(tmp_path, bad, [RetryFrameRule()])
    assert len(_findings(report, "retry-frame")) == 2
    clean = """
        from spark_rapids_tpu.memory.retry import (maybe_inject_oom,
                                                   with_retry_no_split)

        def stage_batch(catalog, nbytes):
            def attempt():
                maybe_inject_oom()
                catalog.reserve(nbytes)
            return with_retry_no_split(None, attempt)
    """
    report = _lint_snippet(tmp_path, clean, [RetryFrameRule()],
                           name="clean.py")
    assert not _findings(report, "retry-frame")


def test_lock_order_rule(tmp_path):
    bad = """
        from spark_rapids_tpu.aux.lockorder import tracked_condition

        class Inner:
            def __init__(self):
                self._cond = tracked_condition("arbiter")

            def poke(self, outer):
                with self._cond:
                    outer.touch()   # arbiter -> semaphore: backward

        class Outer:
            def __init__(self):
                self._cond = tracked_condition("semaphore")

            def touch(self):
                with self._cond:
                    pass
    """
    report = _lint_snippet(tmp_path, bad, [LockOrderRule()])
    finds = _findings(report, "lock-order")
    assert len(finds) == 1 and "backward" in finds[0].message
    clean = """
        from spark_rapids_tpu.aux.lockorder import tracked_condition

        class Inner:
            def __init__(self):
                self._cond = tracked_condition("semaphore")

            def poke(self, inner):
                with self._cond:
                    inner.touch()   # semaphore -> arbiter: forward

        class Innermost:
            def __init__(self):
                self._cond = tracked_condition("arbiter")

            def touch(self):
                with self._cond:
                    pass
    """
    report = _lint_snippet(tmp_path, clean, [LockOrderRule()],
                           name="clean.py")
    assert not _findings(report, "lock-order")


# ---------------------------------------------------------------------------
# suppression semantics
# ---------------------------------------------------------------------------

def test_inline_annotation_suppresses(tmp_path):
    src = """
        import jax

        def a(fn):
            return jax.jit(fn)   # lint: ok=jit-site -- fixture

        def b(fn):
            # lint: ok=jit-site -- annotation on the line above
            return jax.jit(fn)

        def c(fn):
            return jax.jit(fn)   # lint: ok=other-rule (does NOT match)
    """
    report = _lint_snippet(tmp_path, src, [JitSiteRule()])
    active = _findings(report, "jit-site")
    suppressed = [f for f in report.findings
                  if f.rule == "jit-site" and f.suppressed == "inline"]
    assert len(active) == 1
    assert len(suppressed) == 2


def test_baseline_suppresses_and_invalidates_on_change(tmp_path):
    src = """
        import jax

        def a(fn):
            return jax.jit(fn)
    """
    (tmp_path / "mod.py").write_text(textwrap.dedent(src))
    base = tmp_path / "baseline.json"
    # grandfather the current finding
    report = run_lint(root=str(tmp_path), rules=[JitSiteRule()],
                      baseline_path="")
    assert len(report.active) == 1
    n = write_baseline(str(base), report)
    assert n == 1
    report2 = run_lint(root=str(tmp_path), rules=[JitSiteRule()],
                       baseline_path=str(base))
    assert not report2.active
    assert [f.suppressed for f in report2.findings] == ["baseline"]
    assert report2.exit_code == 0
    # idempotent re-write: --write-baseline twice must not wipe the
    # entries the first run grandfathered
    assert write_baseline(str(base), report2) == 1
    report2b = run_lint(root=str(tmp_path), rules=[JitSiteRule()],
                        baseline_path=str(base))
    assert not report2b.active and report2b.exit_code == 0
    # the flagged LINE changing invalidates the entry
    (tmp_path / "mod.py").write_text(textwrap.dedent(src).replace(
        "jax.jit(fn)", "jax.jit(fn )"))
    report3 = run_lint(root=str(tmp_path), rules=[JitSiteRule()],
                       baseline_path=str(base))
    assert len(report3.active) == 1
    assert report3.exit_code == 1


# ---------------------------------------------------------------------------
# output schema + CLI
# ---------------------------------------------------------------------------

def test_json_schema(tmp_path):
    src = """
        import jax

        def a(fn):
            return jax.jit(fn)
    """
    (tmp_path / "mod.py").write_text(textwrap.dedent(src))
    report = run_lint(root=str(tmp_path), baseline_path="")
    d = report.to_json()
    assert d["version"] == 1
    assert d["files_scanned"] == 1
    assert {r["id"] for r in d["rules"]} == {
        "jit-site", "aot-site", "sync-site", "conf-registry",
        "event-catalog", "traced-purity", "spillable-close",
        "fault-point", "retry-frame", "encoded-materialize",
        "collective-site", "lock-order", "conf-module-global"}
    (f,) = [f for f in d["findings"] if f["rule"] == "jit-site"]
    assert set(f) == {"rule", "severity", "file", "line", "message",
                      "hint", "suppressed"}
    assert f["file"] == "mod.py" and f["severity"] == "error"
    assert d["summary"]["active_errors"] >= 1
    # round-trips through json
    json.loads(json.dumps(d))


def test_cli_lint_subcommand(tmp_path):
    (tmp_path / "mod.py").write_text("import jax\nx = jax.jit(len)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.tools", "lint",
         str(tmp_path), "--format", "json"],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 1, out.stderr
    d = json.loads(out.stdout)
    assert any(f["rule"] == "jit-site" for f in d["findings"])
    # single-rule selection + clean tree exits 0
    (tmp_path / "mod.py").write_text("x = 1\n")
    out = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.tools", "lint",
         str(tmp_path), "--rule", "jit-site"],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0 finding(s)" in out.stdout


def test_render_text_lists_findings(tmp_path):
    (tmp_path / "mod.py").write_text("import jax\nx = jax.jit(len)\n")
    report = run_lint(root=str(tmp_path), rules=[JitSiteRule()],
                      baseline_path="")
    text = render_text(report)
    assert "mod.py:2" in text and "jit-site" in text and "FAIL" in text


# ---------------------------------------------------------------------------
# static <-> runtime lock-order cross-check
# ---------------------------------------------------------------------------

def test_runtime_edges_subset_of_static_graph():
    """Every edge the RUNTIME validator observes under a real contended
    workload must already be predicted by the STATIC graph — the two
    halves describe one hierarchy."""
    import numpy as np

    from spark_rapids_tpu.aux import lockorder
    from spark_rapids_tpu.session import TpuSession

    report = run_lint(rules=[LockOrderRule()], baseline_path="")
    static_edges = {(h, a)
                    for (h, a, _f, _l) in report.extras["lock_edges"]}
    order = tuple(report.extras["lock_order"])
    rank = {n: i for i, n in enumerate(order)}

    lockorder.reset_observations()
    s = TpuSession({"spark.rapids.debug.lockOrder": "true",
                    "spark.rapids.sql.test.enabled": False,
                    "spark.rapids.tpu.taskParallelism": 3})
    try:
        n = 20_000
        df = s.create_dataframe(
            {"k": (np.arange(n) % 7).astype(np.int64),
             "v": np.arange(n, dtype=np.int64)}, num_partitions=3)
        assert df.group_by("k").count().count() == 7
        observed = lockorder.observed_edges()
        assert lockorder.violations_total() == 0
        assert observed <= static_edges, (
            f"runtime edges {observed - static_edges} not predicted "
            "statically")
        for held, acquired in observed:
            assert rank[acquired] > rank[held]
    finally:
        lockorder.set_enabled(False)
        lockorder.reset_observations()


def test_runtime_validator_counts_backward_acquisition():
    from spark_rapids_tpu.aux import lockorder

    lockorder.reset_observations()
    lockorder.set_enabled(True)
    try:
        a = lockorder.tracked_condition("arbiter")
        b = lockorder.tracked_condition("semaphore")
        with a:
            with b:     # arbiter held, semaphore acquired: backward
                pass
        assert lockorder.violations_total() == 1
        assert ("arbiter", "semaphore") in lockorder.violation_pairs()
        # forward edges record but do not count as violations
        with b:
            with a:
                pass
        assert lockorder.violations_total() == 1
        assert ("semaphore", "arbiter") in lockorder.observed_edges()
    finally:
        lockorder.set_enabled(False)
        lockorder.reset_observations()


def test_force_enabled_survives_default_conf_session():
    """A TpuSession built with default conf syncs the validator OFF;
    force_enabled pins it on across incidental session construction
    (the arbiter suite's fixture depends on this)."""
    from spark_rapids_tpu.aux import lockorder
    from spark_rapids_tpu.session import TpuSession

    try:
        lockorder.force_enabled(True)
        TpuSession({"spark.rapids.sql.enabled": "false"},
                   init_device=False)
        assert lockorder.is_enabled(), \
            "default-conf session must not disarm a forced validator"
        # plain set_enabled(True) WOULD be disarmed the same way
        lockorder.force_enabled(None)
        lockorder.set_enabled(True)
        TpuSession({"spark.rapids.sql.enabled": "false"},
                   init_device=False)
        assert not lockorder.is_enabled()
    finally:
        lockorder.force_enabled(None)
        lockorder.set_enabled(False)
        lockorder.reset_observations()


def test_disarm_mid_hold_leaves_no_stale_stack():
    """Disarming while a tracked lock is held (what a default-conf
    session construction does implicitly) must still pop the held stack
    on release, or a later re-arm sees phantom backward edges."""
    from spark_rapids_tpu.aux import lockorder

    lockorder.reset_observations()
    lockorder.set_enabled(True)
    try:
        arb = lockorder.tracked_condition("arbiter")
        spool = lockorder.tracked_condition("spool")
        with arb:
            lockorder.set_enabled(False)    # disarmed mid-hold
        # re-arm: 'arbiter' must NOT linger as held on this thread
        lockorder.set_enabled(True)
        with spool:
            pass
        assert lockorder.violations_total() == 0, \
            lockorder.violation_pairs()
    finally:
        lockorder.set_enabled(False)
        lockorder.reset_observations()


def test_lock_order_violation_event_and_prometheus(tmp_path):
    from spark_rapids_tpu.aux import events as EV
    from spark_rapids_tpu.aux import lockorder

    lockorder.reset_observations()
    ring = EV.RingBufferSink()
    EV.add_global_sink(ring)
    lockorder.set_enabled(True)
    try:
        a = lockorder.tracked_condition("arbiter")
        c = lockorder.tracked_condition("catalog")
        with a:
            with c:
                pass
        kinds = [e.kind for e in ring.events()]
        assert kinds.count("lockOrderViolation") == 1
        (ev,) = [e for e in ring.events()
                 if e.kind == "lockOrderViolation"]
        assert ev.payload["held"] == "arbiter"
        assert ev.payload["acquiring"] == "catalog"
        assert "lockOrderViolation" in EV.EVENT_KINDS
    finally:
        lockorder.set_enabled(False)
        EV.remove_global_sink(ring)
    text = EV.render_prometheus()
    assert "spark_rapids_tpu_lock_order_violations_total" in text
    line = [ln for ln in text.splitlines()
            if ln.startswith("spark_rapids_tpu_lock_order_violations_total ")]
    assert float(line[0].split()[-1]) >= 1
    lockorder.reset_observations()
