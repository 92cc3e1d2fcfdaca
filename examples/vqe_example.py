"""Variational quantum eigensolver on a transverse-field Ising chain.

Demonstrates the differentiable layer (quest_tpu/variational.py) — a
capability with no analogue in the reference: the full energy
<psi(theta)| H |psi(theta)> is one traced JAX function, so jax.grad
yields EXACT reverse-mode gradients through the simulation and the
optimization loop runs entirely on device-compiled programs.

H = -J sum_i Z_i Z_{i+1} - h sum_i X_i   (J = 1, h = 0.75, N = 6)

Run: python examples/vqe_example.py
"""

import numpy as np


import jax
import jax.numpy as jnp

from quest_tpu import variational as V

N = 6
J, HF = 1.0, 0.75
LAYERS = 3


def hamiltonian():
    codes, coeffs = [], []
    for i in range(N - 1):           # -J Z_i Z_{i+1}
        term = [0] * N
        term[i] = term[i + 1] = 3
        codes.append(term)
        coeffs.append(-J)
    for i in range(N):               # -h X_i
        term = [0] * N
        term[i] = 1
        codes.append(term)
        coeffs.append(-HF)
    return codes, coeffs


def ansatz(amps, params):
    """Hardware-efficient ansatz: ry layers + cz entangler bricks."""
    p = params.reshape(LAYERS, N)
    for l in range(LAYERS):
        for q in range(N):
            amps = V.ry(amps, N, q, p[l, q])
        for q in range(l % 2, N - 1, 2):
            amps = V.cz(amps, N, q, q + 1)
    return amps


def exact_ground_energy():
    """Dense diagonalization oracle (64x64 — trivial at N=6)."""
    import functools
    I2 = np.eye(2)
    X = np.array([[0, 1], [1, 0]])
    Z = np.diag([1.0, -1.0])

    def kron_at(op, i, op2=None, j=None):
        mats = [I2] * N
        mats[i] = op
        if op2 is not None:
            mats[j] = op2
        # qubit 0 is the LEAST significant bit -> rightmost kron factor
        return functools.reduce(np.kron, reversed(mats))
    H = np.zeros((1 << N, 1 << N))
    for i in range(N - 1):
        H += -J * kron_at(Z, i, Z, i + 1)
    for i in range(N):
        H += -HF * kron_at(X, i)
    return float(np.linalg.eigvalsh(H)[0])


def main():
    codes, coeffs = hamiltonian()
    energy = V.expectation(ansatz, N, codes, coeffs)
    value_and_grad = jax.jit(jax.value_and_grad(energy))

    rng = np.random.default_rng(7)
    params = jnp.asarray(rng.uniform(-0.1, 0.1, LAYERS * N),
                         dtype=jnp.float32)
    lr = 0.1
    for step in range(300):
        e, g = value_and_grad(params)
        params = params - lr * g
        if step % 50 == 0:
            print(f"step {step:3d}: E = {float(e):+.6f}")
    e_final = float(energy(params))
    e_exact = exact_ground_energy()
    print(f"final   : E = {e_final:+.6f}")
    print(f"exact   : E = {e_exact:+.6f}  "
          f"(gap {abs(e_final - e_exact):.4f} — limited by ansatz depth)")


if __name__ == "__main__":
    main()
