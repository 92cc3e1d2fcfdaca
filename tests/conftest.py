"""Test configuration: run on a virtual 8-device CPU mesh.

Mirrors the reference's "same tests, more ranks" methodology (SURVEY.md §4):
the suite runs unchanged whether amplitudes live on one device or are
sharded over the fake 8-device host mesh (the analogue of `mpirun -np 8`).
Environment variables must be set before jax is imported.
"""

import os

os.environ["JAX_PLATFORMS"] = os.environ.get("QUEST_TEST_PLATFORM", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np
import pytest

import jax  # noqa: E402  (after env setup: jax reads JAX_PLATFORMS then)

jax.config.update("jax_enable_x64", True)
# persistent compile cache: the suite is compile-dominated (hundreds of
# distinct (gate, targets, n) programs); repeated runs hit the disk cache.
# min_compile_secs=0.1: the eager per-gate programs (test_unitaries'
# 568 sweeps) compile in 0.1-0.5 s each — above the old 0.5 s threshold
# they were recompiled EVERY run, which alone pushed the tier-1 suite
# against its 870 s budget (measured PR 3)
from quest_tpu.precision import enable_compile_cache
enable_compile_cache(min_compile_secs=0.1)


NUM_QUBITS = 5  # matches the reference's test scale (tests/utilities.hpp:36)


@pytest.fixture(params=["complex64", "complex128"])
def dtype(request):
    return np.dtype(request.param)


@pytest.fixture
def tol(dtype):
    # reference REAL_EPS per precision; density tests widen ~10x like the
    # reference does (test_unitaries.cpp:70)
    return 2e-5 if dtype == np.dtype("complex64") else 1e-12


@pytest.fixture
def rng():
    return np.random.default_rng(20260729)


@pytest.fixture
def compile_auditor():
    """A fresh CompileAuditor (quest_tpu.analysis.audit): enter it
    around a code block to count jit traces/compiles, then
    `assert_no_retrace()` to pin that warm reruns compile nothing —
    the mechanical guard against unstable compiled-program cache keys
    (docs/ANALYSIS.md)."""
    from quest_tpu.analysis.audit import CompileAuditor
    return CompileAuditor()
