"""QAOA for MaxCut, end to end: differentiable angles, then sampling.

The cost layer e^{-i gamma C} is a product of ZZ parity rotations
(each ONE fused flip-form pass, see ops/apply.py apply_pauli_string),
the mixer is rx on every qubit, and the p-layer energy
<gamma, beta| C |gamma, beta> is a single traced function — so the
angle optimization runs on exact jax.grad gradients (the reference
offers no derivatives; its closest path is finite differences over
full re-simulations). After optimizing, the same state is SAMPLED and
the best observed bitstring is checked against the brute-force MaxCut.

Graph: the 3-regular 8-vertex circulant C8(1, 4) (ring + diameters).

Run: python examples/qaoa_maxcut.py
"""

import dataclasses
import os
import sys

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

N = 8
EDGES = [(i, (i + 1) % N) for i in range(N)] + [(i, i + 4) for i in range(4)]
LAYERS = 2


def cut_value(bits):
    return sum((bits >> i & 1) != (bits >> j & 1) for i, j in EDGES)


def ansatz(amps, params):
    from quest_tpu import variational as V

    gammas, betas = params[:LAYERS], params[LAYERS:]
    for q in range(N):
        amps = V.h(amps, N, q)
    for l in range(LAYERS):
        for i, j in EDGES:
            # e^{-i gamma (1 - Z_i Z_j)/2} = global phase * parity(-gamma)
            amps = V.parity(amps, N, (i, j), -gammas[l])
        for q in range(N):
            amps = V.rx(amps, N, q, 2 * betas[l])
    return amps


def main():
    import quest_tpu as qt
    from quest_tpu import measurement as meas
    from quest_tpu import variational as V

    # energy = sum over edges of 0.5 * <Z_i Z_j>; cut = |E|/2 - energy
    codes, coeffs = [], []
    for i, j in EDGES:
        term = [0] * N
        term[i] = term[j] = 3
        codes.append(term)
        coeffs.append(0.5)
    zz_sum = V.expectation(ansatz, N, codes, coeffs)
    value_and_grad = jax.jit(jax.value_and_grad(zz_sum))

    params = jnp.asarray([0.2] * LAYERS + [0.3] * LAYERS, dtype=jnp.float32)
    for step in range(120):
        e, g = value_and_grad(params)
        params = params - 0.05 * g
    exp_cut = len(EDGES) / 2 - float(zz_sum(params))

    best = max(range(1 << N), key=cut_value)
    print(f"p={LAYERS} QAOA expected cut: {exp_cut:.3f} "
          f"(max cut {cut_value(best)}, random baseline {len(EDGES)/2})")
    assert exp_cut > len(EDGES) / 2 + 1, "optimizer did not beat random"

    q = qt.create_qureg(N)
    q = dataclasses.replace(q, amps=ansatz(q.amps, params))
    shots = np.asarray(meas.sample(q, 256, jax.random.PRNGKey(8)))
    cuts = np.array([cut_value(int(s)) for s in shots])
    print(f"sampled best cut: {cuts.max()} "
          f"(mean {cuts.mean():.2f} over {len(shots)} shots)")
    assert cuts.max() == cut_value(best), "never sampled an optimal cut"
    print("OK")


if __name__ == "__main__":
    main()
