"""Quantum phase estimation: read an eigenphase to t-bit precision.

Estimates the eigenphase of U = phase(2*pi*PHI) acting on |1>, with a
t-qubit counting register: Hadamards, controlled-U^(2^k) powers (all
diagonal — communication-free on every engine), then the INVERSE QFT
via Circuit.inverse() (the adjoint-circuit feature; the reference has
no circuit object to invert). Self-checking: with PHI exactly
representable in t bits the measurement is deterministic.

Run: python examples/phase_estimation.py
"""

import numpy as np

T_BITS = 8
PHI = 0.30078125            # 77/256 — exactly t-bit representable


def qpe_circuit(t, phi):
    from quest_tpu.circuit import Circuit, qft_circuit

    n = t + 1                     # counting register [0..t), eigenvector at t
    c = Circuit(n)
    c.x(t)                        # eigenvector |1> of the phase gate
    for q in range(t):
        c.h(q)
    for k in range(t):
        # controlled-U^(2^k): counting qubit k controls phase 2^k * 2pi phi
        c.cphase(2 * np.pi * phi * (1 << k), k, t)
    # inverse QFT on the counting register, bit-reversed convention:
    # qft_circuit includes the final swaps, so its adjoint undoes them too
    iqft = qft_circuit(t).inverse()
    for op in iqft.ops:
        c.ops.append(op)
    return c


def main():
    import jax

    import quest_tpu as qt
    from quest_tpu import measurement as meas

    t = T_BITS
    q = qt.create_qureg(t + 1)
    q = qpe_circuit(t, PHI).apply_banded(q)

    shots = np.asarray(meas.sample(q, 64, jax.random.PRNGKey(3)))
    counting = shots & ((1 << t) - 1)
    # counting register bit k holds phase bit... sample the modal outcome
    vals, counts = np.unique(counting, return_counts=True)
    mode = int(vals[np.argmax(counts)])
    est = mode / (1 << t)
    print(f"t={t} bits, true phase {PHI}")
    print(f"modal outcome {mode} -> estimate {est} "
          f"({counts.max()}/{len(shots)} shots)")
    assert abs(est - PHI) < 1e-12, "QPE missed an exactly-representable phase"
    assert counts.max() == len(shots), "exact phase should be deterministic"
    print("OK")


if __name__ == "__main__":
    main()
