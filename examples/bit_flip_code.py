"""Three-qubit bit-flip code: encode, noise, syndrome, CORRECT — compiled.

The full quantum-error-correction cycle as ONE compiled dynamic circuit:
encode a random state across qubits 0-2, inject X noise with known
per-qubit probability, extract the syndrome into two ancillas (CNOT
parity checks), measure the ancillas mid-circuit, and apply the
feedback correction the syndrome dictates (gate_if on both ancilla
outcomes). The reference cannot express this without returning to the
host between the syndrome measurement and the correction.

Self-checking over many shots: whenever at most one data qubit flipped
(probability 1 - O(p^2)), the decoded state equals the input exactly;
the observed logical-failure rate matches the analytic 3p^2 - 2p^3.

Run: python examples/bit_flip_code.py
"""

import numpy as np

THETA = 0.9
P_FLIP = 0.15


def qec_circuit():
    """Qubits 0-2 data, 3-4 syndrome ancillas. Measurement indices:
    0 = ancilla 3 (parity of data 0,1), 1 = ancilla 4 (parity 1,2)."""
    from quest_tpu.circuit import Circuit

    c = Circuit(5)
    c.ry(0, THETA)                    # the state to protect
    c.cnot(0, 1)                      # encode |psi>_L
    c.cnot(0, 2)
    return c


def noise_and_correct(c, flips):
    from quest_tpu.ops.matrices import PAULI_X

    for q in range(3):
        if flips[q]:
            c.gate(PAULI_X, (q,))
    # syndrome extraction
    c.cnot(0, 3)
    c.cnot(1, 3)                      # ancilla 3 = q0 XOR q1
    c.cnot(1, 4)
    c.cnot(2, 4)                      # ancilla 4 = q1 XOR q2
    c.measure(3)                      # outcome 0
    c.measure(4)                      # outcome 1
    # decode the syndrome in-circuit: (1,0) -> q0, (1,1) -> q1, (0,1) -> q2
    c.gate_if(PAULI_X, (0,), [(0, 1), (1, 0)])
    c.gate_if(PAULI_X, (1,), [(0, 1), (1, 1)])
    c.gate_if(PAULI_X, (2,), [(0, 0), (1, 1)])
    return c


def main():
    import jax

    import quest_tpu as qt
    from quest_tpu.state import to_dense

    rng = np.random.default_rng(11)
    want = np.zeros(2, dtype=complex)
    want[0], want[1] = np.cos(THETA / 2), np.sin(THETA / 2)

    shots, failures = 400, 0
    for s in range(shots):
        flips = rng.random(3) < P_FLIP
        c = noise_and_correct(qec_circuit(), flips)
        q, outs = c.apply_measured(
            qt.create_qureg(5, dtype=np.complex128), jax.random.PRNGKey(s))
        v = to_dense(q).reshape(4, 2, 2, 2)   # [anc, q2, q1, q0]
        # decode: logical state lives on qubit 0 after un-encoding; here
        # just check the corrected codeword against the ideal encoding
        anc = int(np.asarray(outs)[0]) + 2 * int(np.asarray(outs)[1])
        code = v[anc]
        ideal = np.zeros((2, 2, 2), dtype=complex)
        ideal[0, 0, 0], ideal[1, 1, 1] = want[0], want[1]
        fid = abs(np.vdot(ideal, code)) ** 2
        ok = fid > 1 - 1e-9
        if not ok:
            failures += 1
            assert flips.sum() >= 2, (
                f"shot {s}: correction failed with {flips.sum()} flips")
    rate = failures / shots
    p = P_FLIP
    analytic = 3 * p * p * (1 - p) + p ** 3
    print(f"{shots} shots at p={p}: logical failures {failures} "
          f"({rate:.3f}; analytic {analytic:.3f})")
    assert abs(rate - analytic) < 0.05
    print("OK — every <=1-flip shot recovered the exact state")


if __name__ == "__main__":
    main()
