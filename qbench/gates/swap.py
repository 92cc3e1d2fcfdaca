"""Swap of two qubits: |a b> -> |b a>."""

import numpy as np


def matrix(param=None):
    return np.eye(4, dtype=np.complex128)[[0, 2, 1, 3]], False


def build(circuit, qubits, param=None):
    return circuit.swap(*qubits)
