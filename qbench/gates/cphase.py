"""Controlled phase: diag(1, 1, 1, e^{i theta})."""

import numpy as np


def matrix(theta):
    return np.diag([1, 1, 1, np.exp(1j * theta)]).astype(np.complex128), True


def build(circuit, qubits, theta):
    return circuit.cphase(theta, *qubits)
