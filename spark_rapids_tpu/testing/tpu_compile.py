"""Compiling the engine's stage programs for a TPU that is described, not
attached (``on-chip-measurement`` guide, section 2.3): shared by
``scripts/tpu_rehearsal.py`` and ``tests/test_tpu_compile.py``.

Nothing here touches libtpu at import.  ``describe_v5e()`` loads it: call
it from a script's ``main`` or a test fixture, never while a module is
imported, and from one process only (libtpu's lock admits one at a time).
"""

from __future__ import annotations

import contextlib
import os


class Captured(Exception):
    """Raised in place of executing a program in capture-only mode."""


def describe_v5e():
    """The ``v5e:2x2`` topology description (four described chips)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


def _spec_of(a):
    import jax
    if isinstance(a, jax.ShapeDtypeStruct):
        return a
    if hasattr(a, "shape") and hasattr(a, "dtype"):
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype, weak_type=bool(getattr(a, "weak_type", False)),
            sharding=getattr(a, "sharding", None))
    return a     # python scalar: rides as itself (weakly typed)


class ProgramRecorder:
    """While installed, notes the ``StageProgram`` and the argument shapes
    of every distinct program call; with ``capture_only`` the program is
    not run and the call raises ``Captured`` instead."""

    def __init__(self, capture_only: bool = False):
        self.calls: dict = {}
        self.capture_only = capture_only
        self.phase = ""

    @contextlib.contextmanager
    def installed(self):
        import jax
        from spark_rapids_tpu.exec import stage_compiler as SC
        original = SC.StageProgram.__call__

        def call(prog, *args):
            specs = jax.tree.map(_spec_of, args)
            ident = (prog.kind, prog.key_hash, str(jax.tree.map(
                lambda s: (s.shape, str(s.dtype), s.weak_type)
                if isinstance(s, jax.ShapeDtypeStruct)
                else type(s).__name__, specs)))
            self.calls.setdefault(ident, (prog, specs, self.phase))
            if self.capture_only:
                raise Captured()
            return original(prog, *args)

        SC.StageProgram.__call__ = call
        try:
            yield self
        finally:
            SC.StageProgram.__call__ = original

    def capture(self, fn, *args, **kwargs):
        """Runs ``fn`` up to its first program call, which is captured
        instead of executed; returns that ``(program, specs)``."""
        before = set(self.calls)
        was, self.capture_only = self.capture_only, True
        try:
            with self.installed():
                try:
                    fn(*args, **kwargs)
                except Captured:
                    pass
        finally:
            self.capture_only = was
        new = [v for k, v in self.calls.items() if k not in before]
        if len(new) != 1:
            raise AssertionError(f"expected one new program call, got "
                                 f"{len(new)}")
        prog, specs, _phase = new[0]
        return prog, specs


def chip_args(specs, topo):
    """``specs`` with every array placed on the described topology: an
    array sharded over a (virtual CPU) mesh keeps its spec on a mesh of as
    many described chips, everything else goes to the first chip."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])

    def place(s):
        if not isinstance(s, jax.ShapeDtypeStruct):
            return s
        sharding = s.sharding
        if isinstance(sharding, NamedSharding) and sharding.mesh.size > 1:
            mesh = sharding.mesh
            if mesh.devices.flat[0].platform != "tpu":
                mesh = Mesh(np.asarray(topo.devices[:mesh.size]).reshape(
                    mesh.devices.shape), mesh.axis_names)
            sharding = NamedSharding(mesh, sharding.spec)
        else:
            sharding = one_chip
        return jax.ShapeDtypeStruct(s.shape, s.dtype,
                                    weak_type=s.weak_type, sharding=sharding)

    return jax.tree.map(place, specs)


def compile_for_chip(prog, specs, topo):
    """Lowers and compiles one recorded program for the described chip;
    raises what the chip's compiler would raise."""
    # never dispatched and never cached: an executable for a chip that is
    # not attached has no place in the audit ledger
    lowered = prog._fn.lower(  # lint: ok=aot-site (described chip)
        *chip_args(specs, topo))
    return lowered.compile()  # lint: ok=aot-site (described chip)


def _equations(jaxpr, scopes=()):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it, each
    with the names of the ``jax.named_scope``s around it (a nested jaxpr's
    own name stack starts empty: the caller's scopes are carried in)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for eqn in jaxpr.eqns:
        names = scopes + tuple(
            n for n in str(eqn.source_info.name_stack).split("/") if n)
        yield eqn, names
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                if isinstance(sub, ClosedJaxpr):
                    yield from _equations(sub.jaxpr, names)
                elif isinstance(sub, Jaxpr):
                    yield from _equations(sub, names)


def sort_operand_counts(prog, specs) -> list:
    """Operand count of every ``sort`` in the program's jaxpr (nested
    jaxprs included): a count, not a clock."""
    traced = prog._fn.trace(*specs)  # lint: ok=aot-site (jaxpr only)
    return [len(eqn.invars) for eqn, _names in _equations(traced.jaxpr.jaxpr)
            if eqn.primitive.name == "sort"]


def primitive_counts_under_scope(fn, specs, scope: str):
    """``Counter`` of the primitives that the jitted ``fn`` traces inside
    the ``jax.named_scope`` called ``scope``, nested jaxprs included."""
    import collections
    traced = fn.trace(*specs)  # lint: ok=aot-site (jaxpr only)
    return collections.Counter(
        eqn.primitive.name
        for eqn, names in _equations(traced.jaxpr.jaxpr) if scope in names)


def primitives_under_scope(fn, specs, scope: str) -> set:
    """Names of the primitives under ``scope``
    (:func:`primitive_counts_under_scope`)."""
    return set(primitive_counts_under_scope(fn, specs, scope))
