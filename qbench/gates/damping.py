"""QuEST's mixDamping: amplitude damping with decay probability p."""

import numpy as np


def kraus(p):
    return [np.array([[1, 0], [0, np.sqrt(1 - p)]], dtype=np.complex128),
            np.array([[0, np.sqrt(p)], [0, 0]], dtype=np.complex128)]


def build(circuit, qubits, p):
    return circuit.damping(*qubits, p)
