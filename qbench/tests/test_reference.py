"""The plain reference against dense numpy on the CPU: the QFT against
numpy's FFT, ops whose positions span two windows (a swap among them:
each is one chunked pass) and QuEST's two-qubit depolarising channel from
its definition."""

import jax
import numpy as np
import pytest

from qbench import generators, reference as R
from qbench.gates import gate
from qbench.generators import Op
from qbench.registers import density, statevector


def dense(mat, pos, n):
    """The 2^n matrix of `mat` on positions pos (local bit j on pos[j]),
    by tensor contraction: numpy's axis a of (2,) * n is bit n - 1 - a."""
    k = len(pos)
    eye = np.eye(1 << n, dtype=np.complex128).reshape((2,) * n + (1 << n,))
    m = np.asarray(mat).reshape((2,) * (2 * k))
    axes = [n - 1 - p for p in pos]
    out = np.tensordot(m, eye, axes=([2 * k - 1 - j for j in range(k)], axes))
    out = np.moveaxis(out, list(range(k)), [axes[k - 1 - i] for i in range(k)])
    return out.reshape(1 << n, 1 << n)


def run_reference(lowered, x, reg, n):
    """The reference's passes on planes x; the output as a vector in
    natural index order."""
    nq = n if reg is statevector else n // 2
    passes = R.plan_passes(lowered, reg.windows(nq))
    fn, arrays = R.reference_program(passes, reg.LANE_BITS)
    out = np.asarray(jax.device_get(fn(x, arrays)))
    v = (out[0] + 1j * out[1]).reshape(-1)
    layout = reg.layout(nq) or tuple(range(n))
    p = np.arange(v.size)
    nat = sum(((p >> j) & 1) << b for j, b in enumerate(layout))
    got = np.empty_like(v)
    got[nat] = v
    return got, passes


def planes(v, lane_bits):
    return np.stack([v.real, v.imag]).astype(np.float32).reshape(
        2, -1, 1 << lane_bits)


def random_state(n, seed):
    rng = np.random.default_rng(seed)
    v = rng.normal(size=1 << n) + 1j * rng.normal(size=1 << n)
    return v / np.linalg.norm(v)


def test_qft_is_the_normalised_inverse_dft():
    """Amplitude index bit q is qubit q, and the QFT's output index is the
    frequency: |x> -> sum_y e^{2 pi i x y / 2^n} |y> / 2^(n/2)."""
    n = 10
    v = random_state(n, 11)
    ops = generators.ops({"generator": "qft"}, n)
    assert len(ops) == n + n * (n - 1) // 2 + n // 2
    got, passes = run_reference(statevector.lower(ops, n),
                                planes(v, statevector.LANE_BITS),
                                statevector, n)
    # windows [0, 7), [7, 10): the closing swaps (0, 9), (1, 8) and (2, 7)
    # each span two
    assert [k for k, _ in passes].count("cross") == 3
    np.testing.assert_allclose(got, np.fft.ifft(v, norm="ortho"), atol=2e-6)


def random_unitary(k, seed):
    rng = np.random.default_rng(seed)
    a = rng.normal(size=(1 << k, 1 << k)) + 1j * rng.normal(size=(1 << k,
                                                                   1 << k))
    return np.linalg.qr(a)[0]


# chunks of the whole state (every row bit inside one block), of one row
# per block (every row bit a block of its own), and between the two
@pytest.mark.parametrize("chunk", [R.CROSS_CHUNK, 1 << 7, 1 << 10])
def test_ops_across_windows_match_dense(chunk, monkeypatch):
    monkeypatch.setattr(R, "CROSS_CHUNK", chunk)
    n = 10                                      # windows [0, 7), [7, 10)
    swap, _ = gate("swap").matrix(None)
    ops = [((2, 8), random_unitary(2, 1), False),        # lane and row
           ((1, 9), swap, False),                        # a swap
           ((9, 3), random_unitary(2, 2), False),        # row and lane
           ((7, 0, 9), random_unitary(3, 3), False),     # two rows, a lane
           ((8, 9), random_unitary(2, 4), False),        # one window
           ((5, 9), np.diag([1, 1, 1, 1j]), True)]       # diagonal
    v = random_state(n, 5)
    want = v.copy()
    for pos, mat, _ in ops:
        want = dense(mat, pos, n) @ want
    got, passes = run_reference(ops, planes(v, statevector.LANE_BITS),
                                statevector, n)
    assert [k for k, _ in passes].count("cross") == 4
    np.testing.assert_allclose(got, want, atol=2e-6)


# windows [0, 7), [7, 14), [14, 16): a lane and the top row, a lane and
# the lowest row, two rows, and a row before a lane
@pytest.mark.parametrize("pair", [(0, 15), (6, 7), (8, 15), (15, 2)])
def test_swap_across_windows_is_one_pass(pair):
    n = 16
    swap, _ = gate("swap").matrix(None)
    v = random_state(n, 7)
    got, passes = run_reference([(pair, swap, False)],
                                planes(v, statevector.LANE_BITS),
                                statevector, n)
    assert [k for k, _ in passes] == ["cross"]
    # amplitude i moves to i with bits p and q exchanged
    p, q = pair
    i = np.arange(v.size)
    flip = ((i >> p) ^ (i >> q)) & 1
    want = np.empty_like(v)
    want[i ^ (flip << p) ^ (flip << q)] = v
    np.testing.assert_allclose(got, want, atol=2e-6)


def two_qubit_depolarising(rho, p, qubits, n):
    """QuEST's definition: (1 - p) rho + p/15 sum of P rho P over the
    two-qubit Paulis P other than the identity."""
    paulis = [np.eye(2), np.array([[0, 1], [1, 0]]),
              np.array([[0, -1j], [1j, 0]]), np.diag([1, -1])]
    out = (1 - p) * rho
    for a in range(4):
        for b in range(4):
            if a or b:
                pp = dense(np.kron(paulis[b], paulis[a]), qubits, n)
                out = out + p / 15 * pp @ rho @ pp.conj().T
    return out


@pytest.mark.parametrize("chunk", [R.CROSS_CHUNK, 1 << 8])
@pytest.mark.parametrize("nq", [4, 6])
def test_density_channels_match_dense(nq, chunk, monkeypatch):
    """At 4 qubits every position sits in the lanes; at 6 the channel on
    (3, 4) spans the lanes and the rows."""
    monkeypatch.setattr(R, "CROSS_CHUNK", chunk)
    p = 0.3
    ops = [Op("rx", (0,), 0.3), Op("cz", (3, 1)), Op("ry", (3,), 1.1),
           Op("two_qubit_depolarising", (3, 1 if nq == 4 else 4), p),
           Op("swap", (0, nq - 1)), Op("damping", (2,), 0.2),
           Op("h", (0,))]
    factors = density.random_factors(np.random.default_rng(3), nq)
    rho = np.ones((1, 1))
    for f in factors:
        rho = np.kron(f, rho)
    for op in ops:
        g = gate(op.name)
        if op.name == "two_qubit_depolarising":
            rho = two_qubit_depolarising(rho, p, op.qubits, nq)
        elif hasattr(g, "kraus"):
            ks = [dense(k, op.qubits, nq) for k in g.kraus(op.param)]
            rho = sum(k @ rho @ k.conj().T for k in ks)
        else:
            u = dense(g.matrix(op.param)[0], op.qubits, nq)
            rho = u @ rho @ u.conj().T
    x = density.reference_input(factors, num_qubits=nq)
    got, passes = run_reference(density.lower(ops, nq), x, density, 2 * nq)
    assert ("cross" in [k for k, _ in passes]) == (nq == 6)
    # natural index r + 2^N c holds rho[r, c]
    np.testing.assert_allclose(got.reshape(1 << nq, 1 << nq).T, rho,
                               atol=2e-6)


def test_kraus_sets_are_trace_preserving():
    for name, p in (("depolarising", 0.1), ("damping", 0.2),
                    ("two_qubit_depolarising", 0.5)):
        ks = gate(name).kraus(p)
        assert sum(k.conj().T @ k for k in ks) == pytest.approx(
            np.eye(ks[0].shape[0]))
    assert len(gate("two_qubit_depolarising").kraus(0.1)) == 16
