"""Benchmark harness for the five BASELINE.json config scenarios.

Prints one JSON line per scenario:
  {"scenario": ..., "metric": ..., "value": N, "unit": ...}

Scenarios (BASELINE.json "configs"):
  1. tutorial   — the 3-qubit tutorial circuit, eager QuEST-compatible API
  2. rcs        — random-circuit-sampling statevector, whole circuit jitted
  3. genunitary — multi-controlled + general k-qubit ComplexMatrixN gates
  4. channels   — density-matrix decoherence (damping/depolarising/Kraus)
  5. qft        — QFT sharded over the device mesh (ppermute engine)

Sizes adapt to the platform: full scale on TPU, scaled-down on CPU so the
suite stays fast. Run: python benchmarks/run.py [scenario ...]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np


def _sync(x):
    jax.block_until_ready(x)


def _emit(scenario, metric, value, unit, **extra):
    # platform is evidence: it tells an on-chip measurement from a CPU run
    print(json.dumps({"scenario": scenario, "metric": metric,
                      "value": round(value, 3), "unit": unit,
                      "platform": jax.devices()[0].platform, **extra}))


def _on_tpu():
    return jax.devices()[0].platform == "tpu"


# -- 1. tutorial -------------------------------------------------------------


def bench_tutorial():
    from quest_tpu import api as Q

    def run_once():
        qubits = Q.createQureg(3)
        Q.hadamard(qubits, 0)
        Q.controlledNot(qubits, 0, 1)
        Q.rotateY(qubits, 2, 0.1)
        Q.multiControlledPhaseFlip(qubits, [0, 1, 2])
        u = np.array([[0.5 + 0.5j, 0.5 - 0.5j], [0.5 - 0.5j, 0.5 + 0.5j]])
        Q.unitary(qubits, 0, u)
        Q.compactUnitary(qubits, 1, 0.5 + 0.5j, 0.5 - 0.5j)
        Q.rotateAroundAxis(qubits, 2, 3.14 / 2, (1, 0, 0))
        Q.controlledCompactUnitary(qubits, 0, 1, 0.5 + 0.5j, 0.5 - 0.5j)
        Q.multiControlledUnitary(qubits, [0, 1], 2, u)
        return Q.calcProbOfOutcome(qubits, 2, 1)

    run_once()  # warmup/compile
    t0 = time.perf_counter()
    reps = 5
    for _ in range(reps):
        p = run_once()
    dt = (time.perf_counter() - t0) / reps
    assert abs(p - 0.749178) < 1e-4
    _emit("tutorial", "eager tutorial circuit wall-clock", dt * 1000, "ms/run")


# -- 2. RCS ------------------------------------------------------------------


def bench_rcs():
    from quest_tpu.circuit import random_circuit

    from quest_tpu.state import basis_planes, fused_state_shape

    n = 30 if _on_tpu() else 20
    depth = 20
    circ = random_circuit(n, depth, seed=1)
    num_gates = len(circ.ops)
    if _on_tpu():
        # fused band-segment engine with its native (2, rows, 128) state,
        # built directly in that layout (see bench.py: an out-of-jit
        # reshape or a zeros().at.set would transiently double the 8 GB
        # state at 30q)
        fn = circ.compiled_fused(n, density=False, donate=True)
        amps = basis_planes(0, n=n, rdt=jnp.float32,
                            shape=fused_state_shape(n))
    else:
        fn = circ.compiled_banded(n, density=False, donate=True)
        amps = basis_planes(0, n=n, rdt=jnp.float32)
    amps = fn(amps)
    _sync(amps)
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        amps = fn(amps)
    _sync(amps)
    dt = (time.perf_counter() - t0) / reps
    _emit("rcs", f"RCS depth-{depth} @ {n}q wall-clock", dt * 1000, "ms/run",
          gates_per_sec=round(num_gates / dt, 1))


# -- 3. general unitaries ----------------------------------------------------


def bench_general_unitaries():
    from quest_tpu.ops import gates as G
    import quest_tpu as qt

    n = 24 if _on_tpu() else 18
    rng = np.random.default_rng(5)
    q = qt.create_qureg(n)

    def rand_u(k):
        z = rng.normal(size=(1 << k, 1 << k)) + 1j * rng.normal(size=(1 << k, 1 << k))
        u, _ = np.linalg.qr(z)
        return u

    u1, u2, u3 = rand_u(1), rand_u(2), rand_u(3)
    # warmup all shapes
    q = G.multi_controlled_unitary(q, [n - 1, n - 2], 0, u1)
    q = G.two_qubit_unitary(q, 1, 5, u2)
    q = G.multi_qubit_unitary(q, [0, 3, 7], u3)
    _sync(q.amps)
    t0 = time.perf_counter()
    reps = 4
    for _ in range(reps):
        q = G.multi_controlled_unitary(q, [n - 1, n - 2], 0, u1)
        q = G.two_qubit_unitary(q, 1, 5, u2)
        q = G.multi_qubit_unitary(q, [0, 3, 7], u3)
    _sync(q.amps)
    dt = (time.perf_counter() - t0) / (3 * reps)
    _emit("genunitary", f"general k-qubit unitaries @ {n}q", dt * 1000,
          "ms/gate")


# -- 4. density channels -----------------------------------------------------


def bench_channels():
    from quest_tpu.ops import channels as ch
    import quest_tpu as qt

    n = 12 if _on_tpu() else 9
    rng = np.random.default_rng(6)
    q = qt.init_plus_state(qt.create_density_qureg(n))
    ops = None
    from tests.oracle import random_kraus_map  # reuse the CPTP generator
    ops = random_kraus_map(1, 4, rng)

    def step(q):
        q = ch.mix_damping(q, 0, 0.05)
        q = ch.mix_depolarising(q, n // 2, 0.05)
        q = ch.mix_two_qubit_dephasing(q, 1, n - 1, 0.05)
        q = ch.mix_kraus_map(q, 2, ops)
        return q

    q = step(q)
    _sync(q.amps)
    t0 = time.perf_counter()
    reps = 4
    for _ in range(reps):
        q = step(q)
    _sync(q.amps)
    dt = (time.perf_counter() - t0) / (4 * reps)
    _emit("channels", f"decoherence channels @ {n}q density", dt * 1000,
          "ms/channel")


# -- 5. distributed QFT ------------------------------------------------------


def bench_qft_sharded():
    from quest_tpu.circuit import qft_circuit
    from quest_tpu.parallel.mesh import make_amp_mesh, amp_sharding

    devices = jax.devices()
    d = 1 << (len(devices).bit_length() - 1)
    n = 26 if _on_tpu() else 20
    mesh = make_amp_mesh(d)
    from quest_tpu.state import basis_planes

    circ = qft_circuit(n)
    fn = circ.compiled_sharded(n, density=False, mesh=mesh, donate=True)
    amps = basis_planes(0, n=n, rdt=jnp.float32)
    amps = jax.device_put(amps, amp_sharding(mesh))
    amps = fn(amps)
    _sync(amps)
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        amps = fn(amps)
    _sync(amps)
    dt = (time.perf_counter() - t0) / reps
    _emit("qft", f"QFT @ {n}q over {d}-device mesh", dt * 1000, "ms/run",
          devices=d)


# -- 6. trajectory noise (beyond the BASELINE five) --------------------------


def bench_trajectories():
    """Noisy-circuit shots via stochastic Kraus unraveling, vmapped over
    a shot batch — statevector memory per shot where the reference needs
    the 4^n density register (quest_tpu/trajectories.py). Reported as
    noisy shots/sec; the density-register equivalent at this size would
    square the memory."""
    from quest_tpu import trajectories as T
    from quest_tpu.circuit import random_circuit
    from quest_tpu.state import basis_planes

    n = 20 if _on_tpu() else 12
    shots = 64 if _on_tpu() else 16
    depth = 4
    c = random_circuit(n, depth=depth, seed=13)

    def shot(key):
        amps = basis_planes(0, n=n, rdt=jnp.float32)
        amps = c.compiled(n, density=False, donate=False)(amps)
        for q in (0, n // 2, n - 1):
            amps, key, _ = T.damping(amps, key, n, q, 0.05)
        return amps[0, 0]

    run = jax.jit(jax.vmap(shot))
    keys = jax.random.split(jax.random.key(1), shots)
    out = run(keys)
    _sync(out)
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        out = run(keys)
    _sync(out)
    dt = (time.perf_counter() - t0) / reps
    _emit("trajectories", f"noisy RCS shots @ {n}q (3 damping channels)",
          shots / dt, "shots/sec", shots=shots)


ALL = {
    "tutorial": bench_tutorial,
    "rcs": bench_rcs,
    "genunitary": bench_general_unitaries,
    "channels": bench_channels,
    "qft": bench_qft_sharded,
    "trajectories": bench_trajectories,
}


def main(argv):
    names = argv or list(ALL)
    for name in names:
        ALL[name]()


if __name__ == "__main__":
    main(sys.argv[1:])
