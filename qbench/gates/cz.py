"""Controlled Z: diag(1, 1, 1, -1)."""

import numpy as np


def matrix(param=None):
    return np.diag([1, 1, 1, -1]).astype(np.complex128), True


def build(circuit, qubits, param=None):
    return circuit.cz(*qubits)
