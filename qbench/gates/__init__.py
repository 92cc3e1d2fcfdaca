"""Gates and channels, one file each, found by an op's name.

A unitary's module gives `matrix(param) -> (matrix, is_diagonal)`, a
channel's `kraus(param) -> [K]`: textbook definitions for the reference,
with local index bit j on the op's j-th qubit. Each module's
`build(circuit, qubits, param)` adds the op to a quest_tpu Circuit
through its public method, in that method's own argument order. Nothing
here imports the program."""

from __future__ import annotations

import importlib

import numpy as np

I = np.eye(2, dtype=np.complex128)
X = np.array([[0, 1], [1, 0]], dtype=np.complex128)
Y = np.array([[0, -1j], [1j, 0]], dtype=np.complex128)
Z = np.array([[1, 0], [0, -1]], dtype=np.complex128)


def gate(name: str):
    try:
        return importlib.import_module(f"qbench.gates.{name}")
    except ModuleNotFoundError:
        raise KeyError(f"qbench/gates has no gate {name!r}") from None


def rotation(axis: np.ndarray, theta: float) -> np.ndarray:
    """exp(-i theta axis / 2)."""
    return np.cos(theta / 2) * I - 1j * np.sin(theta / 2) * axis
