"""A density matrix of N qubits, held as QuEST holds it: a vector of 2N
bits, index r + 2^N c for the element rho[r, c].

The reference interleaves the bits: position 2q holds r_q and 2q + 1
holds c_q, so a one-qubit channel acts on two adjacent positions and
whole qubits group into windows. Inputs are products of random one-qubit
mixed states."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from qbench import reference as R
from qbench.gates import gate
from qbench.registers import fused_engine, kron, outer_planes

LANE_BITS = 8
DENSITY = True
SMALL_QUBITS = 5


def state_bits(num_qubits: int) -> int:
    return 2 * num_qubits


program, program_buffer, state_bytes = fused_engine(state_bits, DENSITY)


def windows(num_qubits: int):
    return R.windows(2 * num_qubits, LANE_BITS, 6, align=2)


def layout(num_qubits: int):
    """Natural bit of each reference position."""
    return tuple(q if j % 2 == 0 else q + num_qubits
                 for j, q in ((j, j // 2) for j in range(2 * num_qubits)))


_PAULI = np.array([[[0, 1], [1, 0]], [[0, -1j], [1j, 0]], [[1, 0], [0, -1]]])


def random_factors(rng: np.random.Generator, num_qubits: int) -> np.ndarray:
    """(N, 2, 2): rho_q = (I + r.sigma) / 2, |r| in [0.5, 1]."""
    d = rng.normal(size=(num_qubits, 3))
    r = d / np.linalg.norm(d, axis=1, keepdims=True)
    r *= rng.uniform(0.5, 1.0, size=(num_qubits, 1))
    rho = 0.5 * (np.eye(2) + np.einsum("qk,kab->qab", r, _PAULI))
    return rho.astype(np.complex64)


@functools.partial(jax.jit, static_argnames=("num_qubits",),
                   donate_argnums=(1,))
def program_input(factors, buffer, num_qubits):
    """(2, 2^(2N-7), 128) f32 re/im planes of rho in natural order, written
    over `buffer` (donated; its contents are not read). With
    A the low k = min(7, N) qubits and B the rest, index
    r_A + 2^k r_B + 2^N c_A + 2^(N+k) c_B holds F_B[r_B, c_B] F_A[r_A, c_A]."""
    k = min(7, num_qubits)
    fa = kron([factors[q] for q in range(k)])
    fb = kron([factors[q] for q in range(k, num_qubits)]
               or [jnp.ones((1, 1), factors.dtype)])
    # one c_B at a time: XLA materialises a broadcast over all of them
    fbt, fat = fb.T, fa.T[:, None, :]
    rows = fat.shape[0] * fbt.shape[1] * fat.shape[2] // 128

    def body(cb, out):
        part = outer_planes(fbt[cb][None, :, None], fat, 128)
        return jax.lax.dynamic_update_slice_in_dim(out, part, cb * rows, 1)
    return jax.lax.fori_loop(0, fbt.shape[0], body, buffer)


@functools.partial(jax.jit, static_argnames=("num_qubits",))
def reference_input(factors, num_qubits):
    """rho in the interleaved layout: kron of vec(rho_q), r_q the low bit;
    the low LANE_BITS / 2 qubits span the lanes."""
    v = [factors[q].T.reshape(4) for q in range(num_qubits)]
    k = LANE_BITS // 2
    low = kron(v[:k])
    high = kron(v[k:] or [jnp.ones(1, factors.dtype)])
    return outer_planes(high[:, None], low[None, :], 1 << LANE_BITS)


def lower(ops, num_qubits):
    """Every op as a superoperator sum_k conj(K) (x) K on (ket, bra)
    positions: ket bits are the low local bits."""
    out = []
    for op in ops:
        ket = tuple(2 * q for q in op.qubits)
        bra = tuple(2 * q + 1 for q in op.qubits)
        g = gate(op.name)
        if hasattr(g, "kraus"):
            ks, diag = g.kraus(op.param), False
        else:
            u, diag = g.matrix(op.param)
            ks = [u]
        sup = sum(np.kron(np.conj(k), k) for k in ks)
        out.append((ket + bra, sup, diag))
    return out
