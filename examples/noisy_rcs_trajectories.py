"""Noisy random-circuit sampling via quantum trajectories + linear XEB.

The full pipeline the reference cannot run at statevector cost: simulate
an RCS experiment with per-qubit depolarising noise using trajectory
unraveling (quest_tpu/trajectories.py — 2^n memory per shot, the whole
shot batch one vmapped program), sample a bitstring from every noisy
shot, and score the samples against the IDEAL circuit with linear
cross-entropy benchmarking (calculations.calc_linear_xeb). The measured
fidelity decays with circuit volume toward the digital-error-model
reference curve (1 - p)^{n_channels} — a lower bound at shallow depth,
where errors are not yet fully decorrelating.

Run: python examples/noisy_rcs_trajectories.py
"""

import numpy as np


import jax
import jax.numpy as jnp

import quest_tpu as qt
from quest_tpu import trajectories as T
from quest_tpu import variational as V
from quest_tpu.calculations import calc_linear_xeb
from quest_tpu.circuit import Circuit
from quest_tpu.state import basis_planes

N = 10
P_DEPOL = 0.01
SHOTS = 1024


def layers(depth, seed=3):
    """Shared gate plan: (kind, qubit, angle) rotations + CZ bricks."""
    rng = np.random.default_rng(seed)
    plan = []
    for d in range(depth):
        rots = [(int(rng.integers(0, 3)), q,
                 float(rng.uniform(0, 2 * np.pi))) for q in range(N)]
        brick = [(q, q + 1) for q in range(d % 2, N - 1, 2)]
        plan.append((rots, brick))
    return plan


def ideal_state(plan):
    c = Circuit(N)
    for rots, brick in plan:
        for kind, q, ang in rots:
            (c.rx, c.ry, c.rz)[kind](q, ang)
        for a, b in brick:
            c.cz(a, b)
    return c.apply(qt.create_qureg(N))


def sampler(plan, p_noise):
    """One trajectory: the circuit with depolarising noise p_noise after
    every layer, then one bitstring sampled from the final state."""
    def shot(key):
        amps = basis_planes(0, n=N, rdt=jnp.float32)
        for rots, brick in plan:
            for kind, q, ang in rots:
                amps = (V.rx, V.ry, V.rz)[kind](amps, N, q, ang)
            for a, b in brick:
                amps = V.cz(amps, N, a, b)
            if p_noise:
                for q in range(N):
                    amps, key, _ = T.depolarising(amps, key, N, q, p_noise)
        key, sub = jax.random.split(key)
        probs = amps[0] ** 2 + amps[1] ** 2
        return jax.random.categorical(sub, jnp.log(probs + 1e-30))
    return shot


def main():
    print(f"{N}-qubit RCS, depolarising p={P_DEPOL} per qubit per layer, "
          f"{SHOTS} trajectories per depth")
    print("fidelity = XEB(noisy samples) / XEB(ideal samples) — the raw "
          "XEB exceeds 1 at shallow depth (not yet Porter-Thomas), so "
          "the ideal sampler's own score is the correct normalizer")
    print(f"{'depth':>5} {'fidelity':>9} {'(1-p)^channels':>15}")
    for depth in (2, 4, 6, 8):
        plan = layers(depth)
        ideal = ideal_state(plan)

        def xeb_of(p_noise, seed):
            keys = jax.random.split(jax.random.key(seed), SHOTS)
            samples = jax.jit(jax.vmap(sampler(plan, p_noise)))(keys)
            return calc_linear_xeb(ideal, samples)

        fidelity = xeb_of(P_DEPOL, depth) / xeb_of(0.0, 1000 + depth)
        predict = (1.0 - P_DEPOL) ** (N * depth)
        print(f"{depth:>5} {fidelity:>9.3f} {predict:>15.3f}")


if __name__ == "__main__":
    main()
