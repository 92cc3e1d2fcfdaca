"""Rz(theta) = exp(-i theta Z / 2)."""

from qbench.gates import Z, rotation


def matrix(theta):
    return rotation(Z, theta), True


def build(circuit, qubits, theta):
    return circuit.rz(*qubits, theta)
