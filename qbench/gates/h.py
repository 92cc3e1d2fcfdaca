"""Hadamard: (X + Z) / sqrt(2)."""

import numpy as np

from qbench.gates import X, Z


def matrix(param=None):
    return (X + Z) / np.sqrt(2), False


def build(circuit, qubits, param=None):
    return circuit.h(*qubits)
