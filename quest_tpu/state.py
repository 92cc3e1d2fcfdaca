"""The simulation state: a functional `Qureg` pytree.

The reference's Qureg (QuEST/include/QuEST.h:160-191) is a mutable pair of
real/imag C arrays plus chunk metadata. Here the state is an immutable
pytree holding ONE real jax.Array of shape (2, 2^N): plane 0 the real
parts, plane 1 the imaginary parts — the same split-storage layout the
reference uses (QuEST.h ComplexArray), chosen on TPU for speed (measured
2.3x over interleaved complex64 on the memory-bound butterflies) and
because complex buffers cannot cross the host<->device boundary on this
platform (see quest_tpu.cplx).

For a density matrix, rho_{r,c} lives at flat index r + c*2^N: an N-qubit
density matrix IS a 2N-qubit statevector under the Choi isomorphism,
exactly as the reference stores it (QuEST/src/QuEST.c:48-60). Qubit indices
are little-endian: qubit q is bit q of the flat amplitude index.

Distribution metadata (the reference's chunkId/numChunks) is carried by the
array's sharding, not by the pytree: a sharded Qureg is simply one whose
amplitude axis is laid out over a Mesh (see quest_tpu.parallel).

The logical `dtype` of a Qureg remains complex64/complex128 at the API
surface; the planes are the matching real dtype.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from quest_tpu import precision
from quest_tpu import validation


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Qureg:
    """Functional quantum register: statevector or density matrix.

    amps: (2, 2**num_state_qubits) real array — [0] real, [1] imag planes.
          For a density matrix over N qubits, num_state_qubits = 2N and
          plane[:, r + c*2**N] holds rho[r, c].
    """

    amps: jax.Array
    num_qubits: int = dataclasses.field(metadata=dict(static=True))
    is_density: bool = dataclasses.field(metadata=dict(static=True))

    @property
    def num_state_qubits(self) -> int:
        return 2 * self.num_qubits if self.is_density else self.num_qubits

    @property
    def num_amps(self) -> int:
        return 1 << self.num_state_qubits

    @property
    def dtype(self):
        """Logical (complex) amplitude dtype."""
        return precision.complex_dtype_of(self.amps.dtype)

    @property
    def real_dtype(self):
        return np.dtype(self.amps.dtype)

    def replace_amps(self, amps: jax.Array) -> "Qureg":
        return dataclasses.replace(self, amps=amps)


def _planes(num_state_qubits: int, rdt):
    return jnp.zeros((2, 1 << num_state_qubits), dtype=rdt)


@partial(jax.jit, static_argnames=("n", "rdt", "shape"))
def _basis_planes_hl(hi, lo, *, n, rdt, shape=None):
    """Planes of a computational-basis state built in ONE fused buffer
    (zeros().at[...].set() briefly materializes TWO full-state buffers —
    at 30 qubits that is 16 GB and exhausts the chip's HBM). The target
    index arrives split as (index >> 20, index & 0xFFFFF) so every iota
    stays within int32 regardless of jax_enable_x64 (int64 iotas silently
    truncate when x64 is off). `shape` builds the buffer directly in a
    caller-chosen view of (2, 2^n) — reshaping OUTSIDE the jit would
    relayout-copy the whole state (another 8 GB at 30q)."""
    lo_bits = min(n, 20)
    view = (2, 1 << (n - lo_bits), 1 << lo_bits)
    ih = jax.lax.broadcasted_iota(jnp.int32, view, 1)
    il = jax.lax.broadcasted_iota(jnp.int32, view, 2)
    plane = jax.lax.broadcasted_iota(jnp.int32, view, 0)
    hit = (ih == hi) & (il == lo) & (plane == 0)
    out = jnp.where(hit, 1.0, 0.0).astype(rdt)
    return out.reshape(shape if shape is not None else (2, 1 << n))


def _basis_planes(flat_index, *, n, rdt, shape=None):
    lo_bits = min(n, 20)
    return _basis_planes_hl(int(flat_index) >> lo_bits,
                            int(flat_index) & ((1 << lo_bits) - 1),
                            n=n, rdt=rdt, shape=shape)


def basis_planes(flat_index, *, n, rdt=np.float32, shape=None):
    """PUBLIC: the (2, 2^n) re/im planes of computational-basis state
    |flat_index>, built in one fused device buffer, optionally directly
    in a caller-chosen view `shape` (see fused_state_shape — building in
    the target layout avoids an out-of-jit relayout copy, 8 GB at 30q).
    Benchmarks and scripts should use this instead of allocating
    zeros().at[...].set(...)."""
    return _basis_planes(flat_index, n=n, rdt=rdt, shape=shape)


def fused_state_shape(n: int):
    """The fused (Pallas band-segment) engine's native state view for an
    n-qubit register: (2, 2^(n-7), 128) — same physical (8, 128) tiling
    as the kernel blocks, so engine-boundary reshapes are free bitcasts.
    The ONE place this layout constant lives for out-of-package callers
    (compiled_fused callers, bench.py, benchmarks/run.py)."""
    from quest_tpu.ops.pallas_band import LANE_QUBITS, LANES, usable
    if not usable(n):
        raise ValueError(
            f"the fused engine needs n >= {LANE_QUBITS + 3} qubits "
            f"(one (8, 128) f32 tile per block), got n={n}")
    return (2, 1 << (n - LANE_QUBITS), LANES)


@partial(jax.jit, static_argnames=("n", "rdt", "sharding"))
def _zero_planes_sharded(*, n, rdt, sharding):
    """|0...0> planes built in place on `sharding`: each device writes
    only its own shard (building the whole register first and then
    device_put-ing it would hold all of it on one device — 32 GiB at
    32 qubits)."""
    return jax.lax.with_sharding_constraint(
        _basis_planes(0, n=n, rdt=rdt), sharding)


def _make(num_qubits: int, is_density: bool, dtype, sharding=None) -> Qureg:
    validation.validate_num_qubits(num_qubits)
    dtype = np.dtype(dtype) if dtype is not None else precision.get_default_dtype()
    n = 2 * num_qubits if is_density else num_qubits
    rdt = precision.real_dtype_of(dtype)
    if sharding is None:
        amps = _basis_planes(0, n=n, rdt=rdt)
    else:
        amps = _zero_planes_sharded(n=n, rdt=rdt, sharding=sharding)
    return Qureg(amps=amps, num_qubits=num_qubits, is_density=is_density)


def create_qureg(num_qubits: int, env=None, dtype=None) -> Qureg:
    """Statevector register initialized to |0...0> (ref: QuEST.c:34-46)."""
    sharding = env.sharding_for(num_qubits) if env is not None else None
    return _make(num_qubits, False, dtype, sharding)


def create_density_qureg(num_qubits: int, env=None, dtype=None) -> Qureg:
    """Density-matrix register initialized to |0..0><0..0| (ref: QuEST.c:48-60)."""
    sharding = env.sharding_for(2 * num_qubits) if env is not None else None
    return _make(num_qubits, True, dtype, sharding)


@jax.jit
def _device_copy(x):
    return x + jnp.zeros((), dtype=x.dtype)


def clone(qureg: Qureg) -> Qureg:
    """Deep copy (ref createCloneQureg, QuEST.c:62-72) — a fresh device
    buffer, so later donation of either register cannot invalidate the
    other."""
    return qureg.replace_amps(_device_copy(qureg.amps))


# ---------------------------------------------------------------------------
# State initializers (ref: QuEST_cpu.c:1366-1655 init kernels)
# ---------------------------------------------------------------------------



def _init_amps(qureg: Qureg, amps) -> Qureg:
    """Install freshly built planes, PRESERVING the register's sharding.
    Every init_* builds a new array (functional design), which would
    otherwise land on the default device and silently de-shard a
    mesh-sharded register — after which every downstream op compiles as
    a single-device program (measured: GSPMD gathers the full state).
    The ONE place init results are committed."""
    sh = getattr(qureg.amps, "sharding", None)
    if getattr(sh, "mesh", None) is not None:
        amps = jax.device_put(amps, sh)
    return qureg.replace_amps(amps)


def init_blank_state(qureg: Qureg) -> Qureg:
    """All amplitudes zero (an unnormalized, unphysical state)."""
    return _init_amps(qureg,
                      _planes(qureg.num_state_qubits, qureg.real_dtype))


def init_zero_state(qureg: Qureg) -> Qureg:
    """|0...0> or |0..0><0..0|."""
    return _init_amps(qureg, _basis_planes(
        0, n=qureg.num_state_qubits, rdt=qureg.real_dtype))


def init_plus_state(qureg: Qureg) -> Qureg:
    """|+>^N; density: uniform matrix 1/2^N (ref QuEST_cpu.c:1406-1473)."""
    n = qureg.num_qubits
    if qureg.is_density:
        val = 1.0 / (1 << n)
    else:
        val = 1.0 / np.sqrt(1 << n)
    rdt = qureg.real_dtype
    re = jnp.full((qureg.num_amps,), val, dtype=rdt)
    im = jnp.zeros((qureg.num_amps,), dtype=rdt)
    return _init_amps(qureg, jnp.stack([re, im]))


def init_classical_state(qureg: Qureg, state_index: int) -> Qureg:
    """Basis state |k> or |k><k| (ref QuEST_cpu.c:1475-1539)."""
    validation.validate_state_index(qureg, state_index)
    if qureg.is_density:
        flat = state_index + (state_index << qureg.num_qubits)
    else:
        flat = state_index
    return _init_amps(qureg, _basis_planes(
        flat, n=qureg.num_state_qubits, rdt=qureg.real_dtype))


def init_debug_state(qureg: Qureg) -> Qureg:
    """Deterministic unphysical state: amp[k] = (2k + i(2k+1))/10.

    Matches the reference's initDebugState exactly (QuEST_cpu.c:1559-1590),
    which the whole test strategy leans on.
    """
    rdt = qureg.real_dtype
    k = jnp.arange(qureg.num_amps, dtype=rdt)
    return _init_amps(qureg,
                      jnp.stack([(2.0 * k) / 10.0, (2.0 * k + 1.0) / 10.0]))


@partial(jax.jit, static_argnames=("n", "qubit", "outcome", "rdt"))
def _single_qubit_outcome_planes(*, n, qubit, outcome, rdt):
    # scatter value must carry the register dtype: a bare Python float is
    # f64 under x64 and JAX is hardening the implicit down-cast to an error
    norm = jnp.asarray(1.0 / np.sqrt(1 << (n - 1)), dtype=rdt)
    pre, post = 1 << (n - 1 - qubit), 1 << qubit
    re = jnp.zeros((pre, 2, post), dtype=rdt).at[:, outcome, :].set(norm)
    return jnp.stack([re.reshape(-1), jnp.zeros((1 << n,), dtype=rdt)])


def init_state_of_single_qubit(qureg: Qureg, qubit: int, outcome: int) -> Qureg:
    """Uniform superposition over basis states whose bit `qubit` equals
    `outcome` (ref statevec_initStateOfSingleQubit, QuEST_cpu.c:1513-1555).
    Built ON DEVICE in one fused buffer — the whole point at 30q, where a
    host-side arange/where would materialize 2^n indices in host RAM."""
    validation.validate_state_vector(qureg)
    validation.validate_target(qureg, qubit)
    validation.validate_outcome(outcome)
    return _init_amps(qureg, _single_qubit_outcome_planes(
        n=qureg.num_state_qubits, qubit=qubit, outcome=outcome,
        rdt=qureg.real_dtype))


def init_pure_state(qureg: Qureg, pure: Qureg) -> Qureg:
    """Set qureg to the pure state |psi> (statevec copy) or |psi><psi|
    (ref densmatr_initPureState, QuEST_cpu.c / QuEST.c:139-146)."""
    validation.validate_pure_state_args(qureg, pure)
    rdt = qureg.real_dtype
    if not qureg.is_density:
        return _init_amps(qureg, pure.amps.astype(rdt))
    re, im = pure.amps[0].astype(rdt), pure.amps[1].astype(rdt)
    # rho[r, c] = psi_r conj(psi_c); flat index r + c*2^N = column-major,
    # i.e. row-major of rho^T
    rho_re = jnp.outer(re, re) + jnp.outer(im, im)
    rho_im = jnp.outer(im, re) - jnp.outer(re, im)
    return _init_amps(qureg,
                      jnp.stack([rho_re.T.reshape(-1), rho_im.T.reshape(-1)]))


def _host_pair(reals, imags, rdt):
    reals = np.asarray(reals, dtype=rdt).reshape(-1)
    imags = np.asarray(imags, dtype=rdt).reshape(-1)
    return np.stack([reals, imags])


def init_state_from_amps(qureg: Qureg, reals, imags) -> Qureg:
    """Overwrite all amplitudes from real/imag arrays (ref QuEST.c:155-161)."""
    reals = np.asarray(reals).reshape(-1)
    imags = np.asarray(imags).reshape(-1)
    validation.validate_equal_lengths(reals, imags)
    validation.validate_num_amps(qureg, 0, reals.size)
    if reals.size != qureg.num_amps:
        raise validation.QuESTError(
            "Invalid number of amplitudes: must match the register size")
    return _init_amps(qureg,
                      jnp.asarray(_host_pair(reals, imags, qureg.real_dtype)))


def set_amps(qureg: Qureg, start_index: int, reals, imags) -> Qureg:
    """Overwrite a contiguous slice of amplitudes (ref QuEST.c:779-786)."""
    validation.validate_state_vector(qureg)
    reals = np.asarray(reals).reshape(-1)
    imags = np.asarray(imags).reshape(-1)
    validation.validate_equal_lengths(reals, imags)
    validation.validate_num_amps(qureg, start_index, reals.size)
    vals = jnp.asarray(_host_pair(reals, imags, qureg.real_dtype))
    amps = jax.lax.dynamic_update_slice(qureg.amps, vals, (0, start_index))
    return qureg.replace_amps(amps)


def set_density_amps(qureg: Qureg, start_row: int, start_col: int, reals, imags) -> Qureg:
    """Debug-grade density amplitude writer (ref QuEST_debug.h:44-48).

    Writes a flat run of amplitudes starting at rho[start_row, start_col] in
    the column-major flat ordering.
    """
    if not qureg.is_density:
        raise validation.QuESTError(
            "Invalid operation: setDensityAmps requires a density matrix")
    reals = np.asarray(reals).reshape(-1)
    imags = np.asarray(imags).reshape(-1)
    validation.validate_equal_lengths(reals, imags)
    dim = 1 << qureg.num_qubits
    validation.validate_amp_index(qureg, start_row, dim=dim)
    validation.validate_amp_index(qureg, start_col, dim=dim)
    start = start_row + (start_col << qureg.num_qubits)
    validation.validate_num_amps(qureg, start, reals.size)
    vals = jnp.asarray(_host_pair(reals, imags, qureg.real_dtype))
    amps = jax.lax.dynamic_update_slice(qureg.amps, vals, (0, start))
    return qureg.replace_amps(amps)


# ---------------------------------------------------------------------------
# Amplitude getters (ref QuEST.c:671-705)
# ---------------------------------------------------------------------------


def _fetch_amp(qureg: Qureg, flat: int) -> complex:
    pair = np.asarray(jax.device_get(qureg.amps[:, flat]))
    return complex(pair[0], pair[1])


def get_amp(qureg: Qureg, index: int) -> complex:
    validation.validate_amp_index(qureg, index)
    validation.validate_state_vector(qureg)
    return _fetch_amp(qureg, index)


def get_real_amp(qureg: Qureg, index: int) -> float:
    return get_amp(qureg, index).real


def get_imag_amp(qureg: Qureg, index: int) -> float:
    return get_amp(qureg, index).imag


def get_prob_amp(qureg: Qureg, index: int) -> float:
    a = get_amp(qureg, index)
    return a.real * a.real + a.imag * a.imag


def get_density_amp(qureg: Qureg, row: int, col: int) -> complex:
    if not qureg.is_density:
        raise validation.QuESTError(
            "Invalid operation: getDensityAmp requires a density matrix")
    validation.validate_amp_index(qureg, row, dim=1 << qureg.num_qubits)
    validation.validate_amp_index(qureg, col, dim=1 << qureg.num_qubits)
    return _fetch_amp(qureg, row + (col << qureg.num_qubits))


def get_num_qubits(qureg: Qureg) -> int:
    return qureg.num_qubits


def get_num_amps(qureg: Qureg) -> int:
    """Statevector amplitude count (ref getNumAmps requires a statevector)."""
    validation.validate_state_vector(qureg)
    return qureg.num_amps


def to_dense(qureg: Qureg) -> np.ndarray:
    """Fetch the full state to host: (2^N,) complex vector or (2^N, 2^N)
    complex matrix."""
    planes = np.asarray(jax.device_get(qureg.amps))
    arr = planes[0] + 1j * planes[1]
    if qureg.is_density:
        dim = 1 << qureg.num_qubits
        return arr.reshape(dim, dim, order="F")
    return arr
