"""Real-hardware smoke tests: compiled (NOT interpreted) Pallas kernels on
the actual TPU chip, checking numerics against the XLA per-gate path and a
floor on achieved memory bandwidth.

Run with QUEST_TEST_PLATFORM=tpu, on the machine that holds the chip:
    QUEST_TEST_PLATFORM=tpu python -m pytest tests/test_tpu_smoke.py -q
Skipped on CPU (the default suite platform) — the rest of the suite covers
the kernels in interpret mode; these tests exist because interpret mode
cannot see on-device compilation issues (VMEM limits, matmul pass
precision, layout bugs: all three bit in rounds 1-2).
"""

import os
import time

import numpy as np
import pytest

import jax


pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="real-TPU smoke tests (run with QUEST_TEST_PLATFORM=tpu)")


def _state(n):
    import jax.numpy as jnp
    return jnp.zeros((2, 1 << n), dtype=jnp.float32).at[0, 0].set(1.0)


@pytest.fixture(autouse=True)
def _free_device_memory():
    """Collect dropped device buffers between tests: at the 8 GB/state
    scale two tests' worth of leaked garbage OOMs the 15.75 GiB chip
    (observed r3: one failure cascaded RESOURCE_EXHAUSTED into every
    later test via traceback-held frames)."""
    yield
    import gc
    gc.collect()


def _check_engine_matches(circ, n, atol=1e-5):
    got = np.asarray(circ.compiled_fused(n, density=False, donate=False)(
        _state(n)))
    want = np.asarray(circ.compiled(n, density=False, donate=False)(
        _state(n)))
    err = float(np.max(np.abs(got - want)))
    assert err < atol, f"fused/per-gate diverge on chip: {err}"
    norm = float(np.sum(got.astype(np.float64) ** 2))
    assert abs(norm - 1.0) < 1e-5, f"norm drifted on chip: {norm}"


def test_band_stages_compiled_on_chip():
    """One segment exercising b0 + b1 + scattered + diag + parity + masks,
    compiled for the real chip."""
    from quest_tpu.circuit import Circuit

    n = 16
    c = Circuit(n)
    for q in range(0, 7):
        c.rx(q, 0.1 * (q + 1))     # b0
    for q in range(7, 14):
        c.ry(q, 0.2 * q)           # b1
    c.h(14)                        # scattered
    c.ry(15, 0.7)                  # scattered
    c.rz(15, 0.4)
    c.cz(3, 15)
    c.s(9)
    c.x(2, 14)                     # lane target, scattered-row control
    _check_engine_matches(c, n)


def test_rcs_fused_on_chip():
    from quest_tpu.circuit import random_circuit

    _check_engine_matches(random_circuit(16, depth=4, seed=5), 16)


def test_density_channels_on_chip():
    from quest_tpu.circuit import Circuit
    import quest_tpu as qt
    from quest_tpu.state import to_dense

    c = Circuit(6)
    c.h(0)
    c.cnot(0, 4)
    c.damping(2, 0.2)
    c.depolarising(5, 0.1)
    rho1 = qt.init_debug_state(qt.create_density_qureg(6))
    want = to_dense(c.apply(rho1))
    got = to_dense(c.apply_fused(qt.init_debug_state(
        qt.create_density_qureg(6))))
    scale = max(1.0, float(np.max(np.abs(want))))
    np.testing.assert_allclose(got, want, atol=2e-5 * scale, rtol=0)


def test_full_scb_band_on_chip():
    """A d=128 scb stage (whole high band as one MXU dot over merged
    scattered axes) compiled for the real chip: numerics vs the per-gate
    path, plus cross-band couplings into and out of the band."""
    from quest_tpu.circuit import Circuit

    n = 22
    c = Circuit(n)
    for q in range(14, 21):
        c.ry(q, 0.13 * (q - 13))   # composes into one d=128 scb
    c.cz(13, 14)                   # couples sublane band to the scb band
    c.x(15, 21)                    # scb-band target, top-qubit control
    c.h(2)
    c.rz(18, 0.7)
    _check_engine_matches(c, n)


def _metric(name, **kv):
    """Record an on-chip measurement in the test log. Pytest's fd-level
    capture swallows stderr from PASSING tests, so the line is also
    appended to $QUEST_METRICS_FILE (default chiprun_out/
    tpu_smoke_metrics.log under the repo, which the chip tool brings
    back) — the file, not the captured stream, is the artifact."""
    import json
    import os
    import sys
    line = f"[smoke-metric] {json.dumps(dict(name=name, **kv))}"
    print(line, file=sys.stderr, flush=True)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.environ.get("QUEST_METRICS_FILE", os.path.join(
        repo, "chiprun_out", "tpu_smoke_metrics.log"))
    os.makedirs(os.path.dirname(path), exist_ok=True)
    try:
        with open(path, "a") as f:
            f.write(line + "\n")
    except OSError as e:
        print(f"[smoke-metric] WARNING could not append to {path}: {e}",
              file=sys.stderr, flush=True)


def _device_maxdiff(a, b):
    import jax
    import jax.numpy as jnp
    return float(jax.jit(lambda x, y: jnp.max(jnp.abs(x - y)))(a, b))


def test_peak_hbm_within_5x_state():
    """Peak HBM of a fused 26q step stays under 5x the state size
    (measured in a SUBPROCESS so earlier tests' peaks don't pollute the
    stat). Catches buffer-donation and relayout-copy regressions — the
    0f4f622 class of bug that only appears at scale."""
    import subprocess
    import sys
    code = r"""
import jax, json
import numpy as np
from quest_tpu.circuit import random_circuit
from quest_tpu.state import basis_planes, fused_state_shape
import jax.numpy as jnp
n = 26
c = random_circuit(n, depth=2, seed=3)
step = c.compiled_fused(n, density=False, donate=True)
s = basis_planes(0, n=n, rdt=jnp.float32, shape=fused_state_shape(n))
s = step(s)
np.asarray(s[0, :1])
stats = jax.local_devices()[0].memory_stats()
print(json.dumps({"peak": stats.get("peak_bytes_in_use") if stats else None,
                  "state": 2 * 4 * (1 << n)}))
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    import json
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    if rec["peak"] is None:
        pytest.skip("backend does not expose memory_stats")
    ratio = rec["peak"] / rec["state"]
    _metric("peak_hbm_26q_fused", ratio=round(ratio, 2))
    assert ratio <= 5.0, f"peak HBM {ratio:.1f}x state size"


def test_fused_vs_banded_28q_full_circuit():
    """Full-circuit engine equivalence at the 2 GB scale, compared ON
    DEVICE (fetching two 2 GB states to the host would dominate the
    test)."""
    from quest_tpu.circuit import random_circuit
    from quest_tpu.state import basis_planes, fused_state_shape

    import jax.numpy as jnp

    n = 28
    c = random_circuit(n, depth=4, seed=11)
    sf = c.compiled_fused(n, density=False, donate=False)(
        basis_planes(0, n=n, rdt=jnp.float32, shape=fused_state_shape(n)))
    sb = c.compiled_banded(n, density=False, donate=False)(
        basis_planes(0, n=n, rdt=jnp.float32, shape=(2, 1 << n)))
    err = _device_maxdiff(sf.reshape(2, -1), sb)
    _metric("fused_vs_banded_28q_maxdiff", err=err)
    assert err < 5e-6, f"engines diverge at 28q: {err}"


def test_qft_30q_on_chip():
    """QFT of a basis state at the 8 GB scale through the fused engine:
    analytically known output (uniform magnitudes 2^-15)."""
    from quest_tpu.circuit import qft_circuit
    from quest_tpu.state import basis_planes, fused_state_shape

    import jax.numpy as jnp

    n = 30
    t0 = time.perf_counter()
    step = qft_circuit(n).compiled_fused(n, density=False, donate=True)
    s = step(basis_planes(0, n=n, rdt=jnp.float32,
                          shape=fused_state_shape(n)))
    # slice the NATIVE (2, 2^(n-7), 128) layout: flat amps 0..7 live at
    # [:, 0, :8]. An out-of-jit reshape(2, -1) would relayout-copy the
    # full 8 GB state on device next to the live one -> OOM (bit in r3)
    head = np.asarray(jax.device_get(s[:, 0, :8]))
    dt = time.perf_counter() - t0
    want = 1.0 / np.sqrt(1 << n)
    np.testing.assert_allclose(head[0], want, atol=1e-7, rtol=0)
    np.testing.assert_allclose(head[1], 0.0, atol=1e-7, rtol=0)
    _metric("qft_30q_compile_plus_run_s", seconds=round(dt, 2))


def test_rcs_30q_d20_wallclock():
    """The round-2 headline workload, re-measured with the scb kernel
    generation: 30q depth-20 RCS steady-state wall-clock."""
    from quest_tpu.circuit import random_circuit
    from quest_tpu.state import basis_planes, fused_state_shape

    import jax.numpy as jnp

    n, depth = 30, 20
    c = random_circuit(n, depth=depth, seed=7, entangler="cz")
    t0 = time.perf_counter()
    step = c.compiled_fused(n, density=False, donate=True)
    s = step(basis_planes(0, n=n, rdt=jnp.float32,
                          shape=fused_state_shape(n)))
    jax.block_until_ready(s)
    compile_plus_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    s = step(s)
    jax.block_until_ready(s)
    steady = time.perf_counter() - t0
    gates = len(c.ops)
    _metric("rcs_30q_d20", compile_plus_first_s=round(compile_plus_first, 2),
            steady_state_s=round(steady, 3), gates=gates,
            gates_per_sec=round(gates / steady, 1))
    # round-2 pre-scb measured 6.76 s; regression floor at 2x that
    assert steady < 13.5, f"steady-state RCS regressed: {steady:.1f}s"


def test_sharded_engine_single_chip_mesh():
    """The shard_map engine on a 1-device mesh of the real chip: the
    collective-free degenerate case must agree with the local engine
    (pod runs reuse this exact code path with D>1)."""
    from jax.sharding import Mesh

    from quest_tpu.circuit import random_circuit
    from quest_tpu.env import AMP_AXIS
    from quest_tpu.parallel.sharded import compile_circuit_sharded

    import jax.numpy as jnp

    n = 16
    c = random_circuit(n, depth=3, seed=2)
    mesh = Mesh(np.array(jax.devices()[:1]), (AMP_AXIS,))
    s0 = _state(n)
    got = compile_circuit_sharded(c.ops, n, density=False, mesh=mesh,
                                  donate=False)(s0)
    want = c.compiled(n, density=False, donate=False)(s0)
    err = _device_maxdiff(got, want)
    assert err < 5e-6, f"sharded(1-dev) vs local diverge: {err}"


def test_f64_banded_numerics_on_chip():
    """complex128 registers on the XLA banded path: the reference's
    default-precision envelope (1e-13, QuEST_precision.h:48) at 20q on
    real hardware, plus measured f64 throughput at 26q for the precision
    policy (docs/PRECISION.md)."""
    import jax.numpy as jnp

    from quest_tpu.circuit import random_circuit

    if not jax.config.jax_enable_x64:
        pytest.skip("x64 disabled")
    n = 20
    c = random_circuit(n, depth=3, seed=4)
    s64 = jnp.zeros((2, 1 << n), dtype=jnp.float64).at[0, 0].set(1.0)
    out = c.compiled_banded(n, density=False, donate=False)(s64)
    norm = float(jnp.sum(out[0] ** 2 + out[1] ** 2))
    assert abs(norm - 1.0) < 1e-13, f"f64 norm drift: {norm}"
    # agreement with the f64 per-gate path at full double precision
    want = c.compiled(n, density=False, donate=False)(s64)
    err = _device_maxdiff(out, want)
    assert err < 1e-13, f"f64 banded vs per-gate: {err}"

    # throughput at 26q for the documented f64 policy
    n = 26
    rng = np.random.default_rng(1)
    from quest_tpu.circuit import Circuit
    c = Circuit(n)
    for i in range(16):
        c.rx(1 + i % (n - 1), float(rng.uniform(0, 2 * np.pi)))
    step = c.compiled_banded(n, density=False, donate=True, iters=4)
    s = jnp.zeros((2, 1 << n), dtype=jnp.float64).at[0, 0].set(1.0)
    s = step(s)
    jax.block_until_ready(s)
    t0 = time.perf_counter()
    s = step(s)
    jax.block_until_ready(s)
    dt = time.perf_counter() - t0
    _metric("f64_banded_26q", gates_per_sec=round(16 * 4 / dt, 1))


def test_kernel_bandwidth_floor():
    """A warmed 16-gate fused step must beat 10x the reference's measured
    single-core CPU throughput at the same size — a deliberately
    conservative floor that still catches 'kernel silently fell back to
    a per-gate path' regressions."""
    from quest_tpu.circuit import Circuit

    n = 22
    rng = np.random.default_rng(1)
    c = Circuit(n)
    for i in range(16):
        c.rx(1 + i % (n - 1), float(rng.uniform(0, 2 * np.pi)))
    step = c.compiled_fused(n, density=False, donate=True, iters=8)
    s = _state(n)
    s = step(s)
    jax.block_until_ready(s)
    t0 = time.perf_counter()
    for _ in range(3):
        s = step(s)
    jax.block_until_ready(s)
    dt = (time.perf_counter() - t0) / 3
    gates_per_sec = 16 * 8 / dt
    # reference serial CPU measured 150.6e6 amps/sec on this host
    # (benchmarks/reference_baseline.json) -> 35.9 gates/s @ 22q
    assert gates_per_sec > 359, f"only {gates_per_sec:.0f} gates/s @ {n}q"


def test_dynamic_circuit_on_chip():
    """Mid-circuit measurement + classical feedback compiled for the
    real chip: teleportation at fidelity 1 on whatever branch is drawn."""
    from examples.teleportation import teleport_circuit, THETA, PHI

    import quest_tpu as qt
    from quest_tpu.state import to_dense

    want = np.array([np.cos(THETA / 2),
                     np.sin(THETA / 2) * np.exp(1j * PHI)])
    c = teleport_circuit()
    import jax as _jax
    q, outs = c.apply_measured(qt.create_qureg(3), _jax.random.PRNGKey(5))
    o = tuple(int(x) for x in np.asarray(outs))
    v = to_dense(q).reshape(2, 2, 2)
    bob = v[:, o[1], o[0]]
    fid = abs(np.vdot(want, bob)) ** 2
    assert fid > 1 - 1e-5, (o, fid)


def test_high_precision_tier_on_chip():
    """QUEST_MATMUL_PRECISION=high (manual double-bf16 3-pass in the
    kernel): measure throughput vs the HIGHEST default at 26q and pin the
    accuracy envelope on real MXU hardware. The 3-pass scheme halves MXU
    passes on the compute-bound fused path."""
    from quest_tpu import precision as P
    from quest_tpu.circuit import Circuit
    from quest_tpu.state import basis_planes, fused_state_shape

    import jax.numpy as jnp

    n = 26
    rng = np.random.default_rng(5)
    c = Circuit(n)
    for i in range(16):
        c.rx(1 + i % (n - 1), float(rng.uniform(0, 2 * np.pi)))

    def measure(tier):
        old = P.matmul_precision()
        P.set_matmul_precision(tier)
        try:
            step = c.compiled_fused(n, density=False, donate=True, iters=8)
            s = step(basis_planes(0, n=n, rdt=jnp.float32,
                                  shape=fused_state_shape(n)))
            jax.block_until_ready(s)
            t0 = time.perf_counter()
            for _ in range(3):
                s = step(s)
            jax.block_until_ready(s)
            gps = 16 * 8 * 3 / (time.perf_counter() - t0)
            # one more application WITHOUT donation: the tiers' states
            # are compared ON DEVICE over the FULL state (a first-N-amps
            # slice inflates the metric arbitrarily — reduced precision
            # has an ABSOLUTE error floor per dot, so locally-small
            # amplitudes carry large RELATIVE error; bit in r3: the
            # slice metric read 4.3e-2 while the true full-state
            # relative error was 3.2e-5)
            one = c.compiled_fused(n, density=False, donate=False)(
                basis_planes(0, n=n, rdt=jnp.float32,
                             shape=fused_state_shape(n)))
            return gps, one
        finally:
            P.set_matmul_precision(old)

    gps_hi, out_hi = measure("highest")
    gps_h3, out_h3 = measure("high")
    err = (float(jnp.max(jnp.abs(out_h3 - out_hi)))
           / float(jnp.max(jnp.abs(out_hi))))
    _metric("precision_high_vs_highest_26q",
            gates_per_sec_highest=round(gps_hi, 1),
            gates_per_sec_high=round(gps_h3, 1),
            speedup=round(gps_h3 / gps_hi, 2), rel_err_full_state=err)
    # one application through the 3-stage fused kernel: ~1e-5/dot for
    # the double-bf16 scheme (measured 3.2e-5 at 22q/26q on chip)
    assert err < 5e-4, f"HIGH tier diverged on chip: {err}"
