"""Real-accelerator differential test tier.

The reference runs its ScalaTest tier against real GPUs
(/root/reference/tests/README.md:8-21); this directory is the analog: the
platform is left UNforced so the engine runs on the actual TPU chip, while
the CPU oracle stays host-side numpy/arrow.  Run with:

    python -m pytest tests_tpu -q

The whole tier skips when no accelerator backend is present, so it is
safe to invoke unconditionally; `tests/` (forced-CPU, virtual 8-device
mesh) remains the breadth tier.

TPU float64 caveat (documented in docs/compatibility.md): XLA:TPU
emulates f64 as two f32s — ~49-bit precision, f32 exponent range.  Data
generators here keep doubles within +/-1e30 and comparisons use the
relative tolerance already built into tests/asserts.py.
"""

import os
import sys

# ensure `tests.asserts` resolves when running `pytest tests_tpu` alone
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _needs_accelerator():
    """Asks for the backend when the first test starts, never while
    pytest collects: initializing it takes the chip, and a collection
    hook runs in every worker."""
    if jax.default_backend() == "cpu":
        pytest.skip("no accelerator backend; the real-TPU tier needs a TPU "
                    "device")


@pytest.fixture
def rng():
    return np.random.default_rng(7)
