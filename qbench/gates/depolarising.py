"""QuEST's mixDepolarising: rho -> (1 - p) rho + p/3 (X rho X + Y rho Y +
Z rho Z)."""

import numpy as np

from qbench.gates import I, X, Y, Z


def kraus(p):
    return [np.sqrt(1 - p) * I] + [np.sqrt(p / 3) * s for s in (X, Y, Z)]


def build(circuit, qubits, p):
    return circuit.depolarising(*qubits, p)
