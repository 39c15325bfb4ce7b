"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh (the reference tests multi-node
shuffle by mocking the transport SPI — tests/.../shuffle/ — we test multi-chip
sharding by forcing XLA's host platform to expose 8 virtual devices).
"""

import os
import re

# Must be set before jax import anywhere in the test process.
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_X64", "true")

import jax  # noqa: E402

# Force the pure-CPU backend whatever the environment selected: tests must
# be hermetic and run on the virtual 8-device CPU mesh.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def conf():
    from spark_rapids_tpu.config import TpuConf
    return TpuConf()


@pytest.fixture(autouse=True)
def _no_prefetch_thread_leaks():
    """Pipelining leak guard (exec/pipeline.py): every prefetch producer /
    shuffle-warm thread must be gone after the test that spawned it —
    early-exit paths (limits, abandoned fetches) included.  A short grace
    covers producers mid-pull at teardown; anything still alive after it
    is a stranded thread and fails the test."""
    yield
    import threading
    import time

    def stray():
        return [t for t in threading.enumerate()
                if t.is_alive() and t.name.startswith("tpu-prefetch")]

    leaked = stray()
    deadline = time.monotonic() + 5.0
    while leaked and time.monotonic() < deadline:
        time.sleep(0.02)
        leaked = stray()
    assert not leaked, \
        f"leaked prefetch threads: {[t.name for t in leaked]}"


@pytest.fixture(autouse=True)
def _no_arbiter_registry_leaks():
    """Arbitration leak guard (memory/arbiter.py): every task registered
    with the resource arbiter must deregister by task end — early-exit
    paths (limits, retries, cancellations) included.  A short grace
    covers tasks finishing at teardown; anything registered after it is
    a leaked registry entry and fails the test."""
    yield
    import time
    from spark_rapids_tpu.memory.arbiter import get_arbiter
    arb = get_arbiter()
    deadline = time.monotonic() + 5.0
    while arb.stats()["tasks"] and time.monotonic() < deadline:
        time.sleep(0.02)
    leaked = arb.stats()["tasks"]
    if leaked:
        arb._reset_for_tests()      # don't poison every later test
    assert not leaked, f"leaked arbiter task registrations: {leaked}"


@pytest.fixture(autouse=True)
def _bound_process_memory(request):
    """Drop the jit caches after every case of the TPC-DS shard files
    (tests/test_tpcds_<k>.py) and of test_harnesses.py.  Safety code of the
    test process: without the clear, the 60 differential queries in one
    process died with SIGSEGV at the 26th case, inside the CPU compiler
    (XLA's backend_compile_and_load on a tpu-prefetch producer thread), at a
    peak RSS of 5.7 GB on a 125 GB machine.  So what the clear bounds is the
    compiler's accumulated programs, not the machine's memory; one xdist
    worker may be handed several shard files in a row.  CPU recompiles are
    cheap and the correctness signal is unchanged."""
    yield
    name = os.path.basename(str(request.fspath))
    if re.fullmatch(r"test_tpcds_\d+\.py", name) \
            or name == "test_harnesses.py":
        import gc
        jax.clear_caches()
        gc.collect()
