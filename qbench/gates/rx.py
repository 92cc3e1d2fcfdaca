"""Rx(theta) = exp(-i theta X / 2)."""

from qbench.gates import X, rotation


def matrix(theta):
    return rotation(X, theta), False


def build(circuit, qubits, theta):
    return circuit.rx(*qubits, theta)
