"""QuEST's mixTwoQubitDepolarising: rho -> (1 - p) rho + p/15 times the
sum of P rho P over the 15 two-qubit Paulis P = A (x) B other than
I (x) I; 16 Kraus operators.

The program's Circuit has no method of this name, so the op goes in
through its general `kraus` with these operators."""

import numpy as np

from qbench.gates import I, X, Y, Z


def kraus(p):
    paulis = (I, X, Y, Z)
    return [np.sqrt(1 - p) * np.eye(4, dtype=np.complex128)] + [
        np.sqrt(p / 15) * np.kron(a, b) for a in paulis for b in paulis
        if a is not I or b is not I]


def build(circuit, qubits, p):
    return circuit.kraus(tuple(qubits), kraus(p))
