"""A state vector of n qubits: amplitude index bit q is qubit q.

The reference keeps the natural order, as (2, 2^(n-7), 128) planes. Inputs are random product states, one random pure qubit each."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from qbench import reference as R
from qbench.gates import gate
from qbench.registers import fused_engine, kron, outer_planes

LANE_BITS = 7
DENSITY = False
SMALL_QUBITS = 10


def state_bits(num_qubits: int) -> int:
    return num_qubits


program, program_buffer, state_bytes = fused_engine(state_bits, DENSITY)


def windows(num_qubits: int):
    return R.windows(num_qubits, LANE_BITS, 7)


def layout(num_qubits: int):
    return None


def random_factors(rng: np.random.Generator, num_qubits: int) -> np.ndarray:
    v = rng.normal(size=(num_qubits, 2)) + 1j * rng.normal(size=(num_qubits, 2))
    return (v / np.linalg.norm(v, axis=1, keepdims=True)).astype(np.complex64)


def _planes(f, num_qubits, lane_bits):
    """The product state as high (x) low, low spanning the lane bits."""
    k = min(lane_bits, num_qubits)
    low = kron([f[q] for q in range(k)])
    high = kron([f[q] for q in range(k, num_qubits)] or [jnp.ones(1, f.dtype)])
    return outer_planes(high[:, None], low[None, :], 1 << lane_bits)


@functools.partial(jax.jit, static_argnames=("num_qubits",),
                   donate_argnums=(1,), keep_unused=True)
def program_input(factors, buffer, num_qubits):
    """(2, 2^(n-7), 128) f32 re/im planes in natural order, written over
    `buffer` (donated; kept though unread, so the output takes its
    place)."""
    del buffer
    return _planes(factors, num_qubits, 7)


@functools.partial(jax.jit, static_argnames=("num_qubits",))
def reference_input(factors, num_qubits):
    return _planes(factors, num_qubits, LANE_BITS)


def lower(ops, num_qubits):
    out = []
    for op in ops:
        mat, diag = gate(op.name).matrix(op.param)
        out.append((tuple(op.qubits), mat, diag))
    return out
