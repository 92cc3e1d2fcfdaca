"""Tests for the profiling hooks and batched shot sampling (TPU-native
capabilities beyond the reference — SURVEY.md §5 lists tracing as absent
there)."""

import jax
import numpy as np
import pytest

import quest_tpu as qt
from quest_tpu import measurement as meas
from quest_tpu import profiling
from quest_tpu.ops import gates as G
from quest_tpu.state import init_state_from_amps
from quest_tpu.validation import QuESTError

from . import oracle


def test_sample_distribution(rng):
    v = oracle.random_statevector(4, rng)
    q = init_state_from_amps(qt.create_qureg(4, dtype=np.complex128),
                             v.real, v.imag)
    shots = 20000
    samples = np.asarray(meas.sample(q, shots, jax.random.PRNGKey(0)))
    assert samples.shape == (shots,)
    freqs = np.bincount(samples, minlength=16) / shots
    np.testing.assert_allclose(freqs, np.abs(v) ** 2, atol=0.02)


def test_sample_density(rng):
    rho = oracle.random_density(3, rng)
    flat = rho.reshape(-1, order="F")
    q = init_state_from_amps(qt.create_density_qureg(3, dtype=np.complex128),
                             flat.real, flat.imag)
    samples = np.asarray(meas.sample(q, 20000, jax.random.PRNGKey(1)))
    freqs = np.bincount(samples, minlength=8) / 20000
    np.testing.assert_allclose(freqs, np.diagonal(rho).real, atol=0.02)


def test_sample_deterministic_state():
    q = qt.init_classical_state(qt.create_qureg(3), 5)
    samples = np.asarray(meas.sample(q, 100, jax.random.PRNGKey(2)))
    assert np.all(samples == 5)


def test_sample_validation():
    q = qt.create_qureg(2)
    with pytest.raises(QuESTError, match="shots"):
        meas.sample(q, 0, jax.random.PRNGKey(0))


@pytest.mark.slow          # ~29 s: the heaviest single test on this
                           # host — tier-1 budget discipline (runs in
                           # the full CI suite step)
def test_annotate_and_trace(tmp_path):
    with profiling.annotate("test-region"):
        _ = qt.create_qureg(4)
    with profiling.trace(str(tmp_path / "trace")):
        q = qt.create_qureg(4)
        q = G.hadamard(q, 0)
    # trace directory was written
    import os
    assert any(os.scandir(str(tmp_path / "trace")))


def test_sweep_dma_report_smoke():
    """The per-sweep DMA-vs-compute split (profiling.sweep_dma_report,
    ISSUE 11 hook) runs end-to-end off-chip: interpreter-mode kernels,
    one stage-free copy launch as the DMA floor, per-sweep adders
    reported. The record must carry the split keys the chip run
    attributes stall time with."""
    import io

    buf = io.StringIO()
    rec = profiling.sweep_dma_report(n=10, reps=1, out=buf)
    assert rec["n"] == 10 and rec["dma_ms"] >= 0
    kernels = [s for s in rec["sweeps"] if s["kind"] == "kernel"]
    assert kernels, rec
    for s in kernels:
        assert set(s) >= {"total_ms", "compute_adder_ms", "stages",
                          "dma_bound"}
        assert s["compute_adder_ms"] >= 0
    text = buf.getvalue()
    assert "DMA floor" in text
    # off-chip the report must caution that times are interpreter ones
    assert "INTERPRETER" in text


def test_decoupled_kernel_wraps_dma_waits_in_named_scopes():
    """The in-kernel trace labels the chip profile attributes stall
    time with: the decoupled driver must wrap its in/out DMA waits and
    the stage chain in the documented named scopes (a rename would
    silently orphan the docs/SWEEPS.md profiling recipe)."""
    import inspect

    from quest_tpu.ops import pallas_band as PB

    src = inspect.getsource(PB._decoupled_kernel)
    for label in ("quest:dma_in_wait", "quest:dma_out_wait",
                  "quest:stages"):
        assert label in src, label


def test_linear_xeb(rng):
    """Samples drawn from the state give F_XEB near the theoretical value;
    uniform samples give ~0."""
    from quest_tpu import calculations as C
    from quest_tpu.circuit import random_circuit

    n = 8
    circ = random_circuit(n, depth=8, seed=3)
    q = circ.apply(qt.create_qureg(n, dtype=np.complex128))
    key = jax.random.PRNGKey(7)
    samples = meas.sample(q, 4000, key)
    probs = np.abs(np.asarray(
        qt.state.to_dense(q))) ** 2
    # ideal sampler: E[F_XEB] = 2^n * sum p^2 - 1
    ideal = (1 << n) * float(np.sum(probs ** 2)) - 1.0
    got = C.calc_linear_xeb(q, samples)
    assert got == pytest.approx(ideal, abs=0.35)

    uniform = jax.random.randint(key, (4000,), 0, 1 << n)
    assert C.calc_linear_xeb(q, uniform) == pytest.approx(0.0, abs=0.35)


def test_linear_xeb_validation():
    from quest_tpu import calculations as C
    rho = qt.create_density_qureg(2)
    with pytest.raises(QuESTError, match="state-vector"):
        C.calc_linear_xeb(rho, np.array([0]))


# -- memory-discipline regression nets ---------------------------------------
# Round 1's headline failure was an OOM from per-gate full-state HLO
# temporaries (VERDICT: bench rc=1 at 26-28q, dozens of live full-state
# temps). These tests pin the compiled engines' PEAK temp allocation to a
# small multiple of the state size so a regression to copy-heavy programs
# fails in CI, on CPU, at test size.


def _temp_bytes(fn, *args):
    comp = jax.jit(fn).lower(*args).compile()
    try:
        return comp.memory_analysis().temp_size_in_bytes
    except Exception:
        return None


@pytest.mark.parametrize("engine", ["banded", "pergate"])
def test_engine_peak_temp_bounded(engine):
    import jax.numpy as jnp
    from quest_tpu.circuit import Circuit

    n = 16
    rng = np.random.default_rng(3)
    c = Circuit(n)
    for i in range(16):
        c.rx(1 + i % (n - 1), float(rng.uniform(0, 2 * np.pi)))
    amps = jnp.zeros((2, 1 << n), dtype=jnp.float32).at[0, 0].set(1.0)
    fn = (lambda a: c.banded_trace(a, n, False)) if engine == "banded" \
        else (lambda a: c.trace(a, n, False))
    got = _temp_bytes(fn, amps)
    if got is None:
        pytest.skip("backend has no memory analysis")
    state = 2 * (1 << n) * 4
    # measured 2.5x (banded) / 3x (pergate) state; the round-1 failure
    # mode held tens of full-state temps simultaneously
    assert got <= 5 * state, (got, state)


def test_sample_without_key_is_seed_reproducible():
    """sample(q, shots) with no key draws its seed from the seeded host
    stream: seedQuEST makes sampling reproducible like the reference."""
    import quest_tpu as qt
    from quest_tpu import api as Q

    q = qt.init_plus_state(qt.create_qureg(4))
    Q.seedQuEST([123])
    a = np.asarray(qt.sample(q, 32))
    Q.seedQuEST([123])
    b = np.asarray(qt.sample(q, 32))
    np.testing.assert_array_equal(a, b)
    Q.seedQuEST([124])
    c = np.asarray(qt.sample(q, 32))
    assert not np.array_equal(a, c)


def test_default_sample_key_uses_the_full_rng_word():
    """The default PRNGKey seed is a FULL 32-bit word from the seeded
    stream (random_.uint32), not `int(uniform() * 2**31)` — that old
    mapping zeroed bit 31 (half the seed space unreachable) and
    collapsed distinct stream states onto one key. Pins: per-seed
    determinism of the word stream, and that the stream actually
    exercises the high bit."""
    from quest_tpu import api as Q
    from quest_tpu import random_ as R

    Q.seedQuEST([123, 456])
    words_a = [R.uint32() for _ in range(64)]
    Q.seedQuEST([123, 456])
    words_b = [R.uint32() for _ in range(64)]
    assert words_a == words_b
    assert all(0 <= w < (1 << 32) for w in words_a)
    assert any(w >= (1 << 31) for w in words_a)   # bit 31 reachable again
    Q.seedQuEST([123, 457])
    assert [R.uint32() for _ in range(64)] != words_a
