"""Ry(theta) = exp(-i theta Y / 2)."""

from qbench.gates import Y, rotation


def matrix(theta):
    return rotation(Y, theta), False


def build(circuit, qubits, theta):
    return circuit.ry(*qubits, theta)
