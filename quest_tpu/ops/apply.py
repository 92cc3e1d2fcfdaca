"""Core gate application: low-rank segment views over split re/im planes.

TPU-native storage: a register of 2^n amplitudes is ONE real array of shape
(2, 2^n) — plane 0 real parts, plane 1 imaginary parts. Measured on TPU
(v5e) this is 2.3x faster than XLA's interleaved complex64 for the
memory-bound butterfly kernels, and it sidesteps two hard platform limits:
complex buffers cannot cross the host<->device boundary here, and the naive
(2,)*n tensor view exceeds the TPU backend's supported rank for n >~ 16.

Instead of viewing the state as a rank-n tensor, every operation reshapes
each plane into a LOW-RANK "segment view": only the qubits the gate touches
get their own size-2 axis; the contiguous index ranges between them stay
fused as large segments. A k-target gate with c controls therefore works on
a rank-(2(k+c)+1) tensor regardless of n — large contiguous dims that XLA
tiles well.

A k-qubit gate is applied as a FLIP-FORM butterfly:

    out = sum over d in {0,1}^k of  C_d * rev_d(x)

where rev_d reverses the target axes selected by bit-pattern d and C_d is
the coefficient tensor C_d[b] = m[b, b XOR d], broadcast along the
non-target axes. Every term is elementwise (multiply-accumulate against an
axis-reversed read of the SAME input buffer), so XLA fuses the whole gate
into one memory pass with exactly two live full-state buffers — the
in-place discipline of the reference's kernels (QuEST_cpu.c:1656-1713).
[The earlier slice/concat reassembly made XLA materialize a fresh
full-state temp per concat and OOMed a 16 GB chip at 26 qubits.]

For CONCRETE numpy operands, zero C_d terms are skipped at trace time — an
X gate emits a pure axis reversal, no arithmetic (the analogue of the
reference's dedicated pauliX kernel vs its general unitary kernel,
QuEST_cpu.c:2464 vs 1656).

Index conventions (identical to the reference, QuEST.h little-endian):
  - flat amplitude index i; qubit q is bit q of i
  - a k-qubit operator matrix m[r, c] uses bit j of r/c for targets[j]
    (targets[0] is the LEAST significant matrix bit, matching the
    reference's multiQubitUnitary semantics, QuEST_cpu.c:1814-1898)

Operands are (re, im) float pairs — numpy arrays (concrete: baked into the
program, zeros skipped) or traced jnp arrays (dynamic parameters).
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from quest_tpu import precision

Axes = Tuple[int, ...]


def seg_view(n: int, qubits_desc: Sequence[int]):
    """Reshape dims for a (2^n,) plane giving each qubit in `qubits_desc`
    (sorted strictly descending) its own size-2 axis, with the index ranges
    between them left as fused segments. Returns (dims, axis_of)."""
    dims = []
    axis_of: Dict[int, int] = {}
    prev = n
    for q in qubits_desc:
        dims.append(1 << (prev - 1 - q))
        axis_of[q] = len(dims)
        dims.append(2)
        prev = q
    dims.append(1 << prev)
    return tuple(dims), axis_of


def _split_view(n: int, targets, controls):
    qubits = tuple(sorted(set(targets) | set(controls), reverse=True))
    return seg_view(n, qubits)


def bit_tensor(ndims: int, axis: int):
    """(0, 1) along `axis`, broadcastable against a segment view."""
    shape = [1] * ndims
    shape[axis] = 2
    return jnp.arange(2).reshape(shape)


def apply_pauli_string(amps, n, term):
    """P|psi> for a whole Pauli string in ONE fused elementwise pass.

    A Pauli string is a bit-flip permutation (its X/Y factors) times a
    per-index sign (its Z/Y factors) times the global phase (-i)^{#Y}:

        (P psi)[j] = (-i)^{ny} * (-1)^{parity(j & zy)} * psi[j ^ x]

    One flip+sign+scale pass on the planes — no matmuls, no per-factor
    passes (the reference applies the factors gate-by-gate,
    QuEST_common.c:449-462). `term` is one Pauli code (0..3) per qubit.
    Serves calc_expec_pauli_sum / apply_pauli_sum (calculations.py) and
    the fused multi_rotate_pauli (gates.py)."""
    x_bits = tuple(q for q, p in enumerate(term) if p in (1, 2))
    zy_bits = tuple(q for q, p in enumerate(term) if p in (2, 3))
    ny = sum(1 for p in term if p == 2)
    if not x_bits and not zy_bits:
        return amps
    involved = tuple(sorted(set(x_bits) | set(zy_bits), reverse=True))
    dims, axis_of = seg_view(n, involved)
    re = amps[0].reshape(dims)
    im = amps[1].reshape(dims)
    axes = [axis_of[q] for q in x_bits]
    if axes:
        re = jnp.flip(re, axis=axes)
        im = jnp.flip(im, axis=axes)
    sign = parity_sign(len(dims), axis_of, zy_bits, amps.dtype)
    if sign is not None:
        re = re * sign
        im = im * sign
    # global phase (-i)^{ny}: a quarter-turn plane rotation, not a multiply
    k = ny % 4
    if k == 1:      # * -i
        re, im = im, -re
    elif k == 2:    # * -1
        re, im = -re, -im
    elif k == 3:    # * i
        re, im = -im, re
    return jnp.stack([re.reshape(-1), im.reshape(-1)])


def parity_sign(ndims: int, axis_of, qubits, dtype):
    """(-1)^{parity of the listed qubits' bits} as a broadcast product of
    per-axis (+1, -1) vectors — no 2^k table, no permutation. Returns
    None for an empty qubit list. The ONE home of this idiom
    (apply_parity_phase, the Pauli flip-form in calculations.py)."""
    sign = None
    for q in qubits:
        shape = [1] * ndims
        shape[axis_of[q]] = 2
        vec = jnp.array([1.0, -1.0], dtype=dtype).reshape(shape)
        sign = vec if sign is None else sign * vec
    return sign


def norm_control_states(controls, control_states):
    """Empty `control_states` means all-ones. The ONE place this
    normalization lives: a silent zip truncation against default-empty
    states once DROPPED controls entirely (found by the variational
    tests) — every consumer that pairs controls with states must
    normalize through here first."""
    if controls and not control_states:
        return (1,) * len(controls)
    if len(controls) != len(control_states):
        from quest_tpu import validation as val
        val._err("Invalid control state: must give exactly one bit per "
                 "control qubit.")
    return tuple(control_states)


def control_mask(ndims: int, axis_of, controls, control_states):
    """Boolean tensor broadcastable against the segment view, True where all
    control qubits carry their required state; None if no controls."""
    control_states = norm_control_states(controls, control_states)
    mask = None
    for c, s in zip(controls, control_states):
        vec = bit_tensor(ndims, axis_of[c]) == s
        mask = vec if mask is None else (mask & vec)
    return mask


def _as_pair(op_pair, rdtype):
    """Normalize an operand pair. Concrete numpy pairs stay numpy (so zero
    entries can be skipped at trace time); traced values become jnp arrays."""
    re, im = op_pair
    if isinstance(re, np.ndarray) and isinstance(im, np.ndarray):
        return np.asarray(re, dtype=rdtype), np.asarray(im, dtype=rdtype), True
    return (jnp.asarray(re, dtype=rdtype), jnp.asarray(im, dtype=rdtype),
            False)


_UNROLL_MAX_TARGETS = 4  # beyond this the 2^k-term flip butterfly explodes
                         # compile time; use the gather+matmul path instead


def apply_matrix(
    amps: jax.Array,
    n: int,
    op_pair,
    targets: Sequence[int],
    controls: Sequence[int] = (),
    control_states: Sequence[int] = (),
) -> jax.Array:
    """Apply a (2^k, 2^k) operator (as an (re, im) pair) to `targets` of the
    n-qubit state `amps` of shape (2, 2^n). Non-unitary operators are fine
    (the same path applies Kraus superoperators to the doubled density
    register). Returns the new (2, 2^n) planes."""
    targets = tuple(int(t) for t in targets)
    controls = tuple(int(c) for c in controls)
    control_states = norm_control_states(controls, control_states)
    k = len(targets)
    if k > _UNROLL_MAX_TARGETS:
        return _apply_matrix_matmul(amps, n, op_pair, targets, controls,
                                    control_states)
    if n >= 14 and any(q < _LANE_QUBITS for q in targets):
        # Large registers: a segment view exposing a low qubit leaves a
        # tiny minor dim, which the TPU pads to (8, 128) tiles — up to
        # 64x memory (measured OOM on 24-state-qubit channels). Keep the
        # minor dim at 128 lanes: low-qubit content becomes embedded
        # 128x128 lane operators, high target bits become block slices.
        return _apply_matrix_laneblock(amps, n, op_pair, targets, controls,
                                       control_states)
    mre, mim, concrete = _as_pair(op_pair, amps.dtype)
    mre = mre.reshape(1 << k, 1 << k)
    mim = mim.reshape(1 << k, 1 << k)
    dims, axis_of = _split_view(n, targets, controls)
    ndims = len(dims)
    re = amps[0].reshape(dims)
    im = amps[1].reshape(dims)
    taxes = [axis_of[t] for t in targets]
    nre, nim = _flip_form(re, im, mre, mim, concrete, targets, dims,
                          axis_of, taxes)
    mask = control_mask(ndims, axis_of, controls, control_states)
    if mask is not None:
        nre = jnp.where(mask, nre, re)
        nim = jnp.where(mask, nim, im)
    return jnp.stack([nre.reshape(-1), nim.reshape(-1)])


def apply_matrix_rows(amps3, n, op_pair, targets,
                      controls: Sequence[int] = (),
                      control_states: Sequence[int] = ()):
    """apply_matrix on the fused-engine layout: `amps3` is the
    (2, 2^(n-7), 128) shaped state the Pallas segment kernels consume,
    and the result keeps that shape. The point is what does NOT happen:
    no flat (2, 2^n) intermediate ever exists, so XLA never converts
    between the (rows, 128)-tiled kernel layout and the flat layout — a
    conversion that materializes a full-state copy (measured: the 8 GiB
    copy_bitcast that pushed the 30-qubit density-channel bench past
    HBM). All row-axis reshapes here split the major axis only, which is
    layout-free. Matrix ops with a lane-qubit (< 7) target ride the
    128x128 lane-block embedding (_laneblock_core); all-row-target ops
    ride the flip-form butterfly over the row view with the lane axis as
    trailing batch. Oversized operators (k > _UNROLL_MAX_TARGETS) fall
    back to the flat path with one explicit round-trip."""
    targets = tuple(int(t) for t in targets)
    controls = tuple(int(c) for c in controls)
    control_states = norm_control_states(controls, control_states)
    k = len(targets)
    if k > _UNROLL_MAX_TARGETS:
        flat = apply_matrix(amps3.reshape(2, -1), n, op_pair, targets,
                            controls, control_states)
        return flat.reshape(amps3.shape)
    if any(t < _LANE_QUBITS for t in targets):
        return _laneblock_core(amps3, n, op_pair, targets, controls,
                               control_states)
    # every target in row space; controls may sit on either side
    mre, mim, concrete = _as_pair(op_pair, amps3.dtype)
    mre = mre.reshape(1 << k, 1 << k)
    mim = mim.reshape(1 << k, 1 << k)
    rows_n = n - _LANE_QUBITS
    row_ts = tuple(t - _LANE_QUBITS for t in targets)
    hi_cs = [(c - _LANE_QUBITS, s)
             for c, s in zip(controls, control_states) if c >= _LANE_QUBITS]
    lo_cs = [(c, s)
             for c, s in zip(controls, control_states) if c < _LANE_QUBITS]
    qubits = tuple(sorted(set(row_ts) | {c for c, _ in hi_cs},
                          reverse=True))
    rdims, axis_of = seg_view(rows_n, qubits)
    dims = rdims + (_LANES,)
    re = amps3[0].reshape(dims)
    im = amps3[1].reshape(dims)
    taxes = [axis_of[t] for t in row_ts]
    nre, nim = _flip_form(re, im, mre, mim, concrete, row_ts, dims,
                          axis_of, taxes)
    mask = control_mask(len(dims), axis_of,
                        tuple(c for c, _ in hi_cs),
                        tuple(s for _, s in hi_cs))
    if lo_cs:
        # lane-qubit controls: a (128,) predicate on the lane axis — the
        # lane axis is never split (that would break the 128-lane tiling)
        lane = np.arange(_LANES)
        lmask = np.ones(_LANES, dtype=bool)
        for c, s in lo_cs:
            lmask &= ((lane >> c) & 1) == s
        lvec = jnp.asarray(lmask).reshape((1,) * (len(dims) - 1)
                                          + (_LANES,))
        mask = lvec if mask is None else (mask & lvec)
    if mask is not None:
        nre = jnp.where(mask, nre, re)
        nim = jnp.where(mask, nim, im)
    shape = amps3.shape[1:]
    return jnp.stack([nre.reshape(shape), nim.reshape(shape)])


def _flip_form(re, im, mre, mim, concrete, targets, dims, axis_of, taxes):
    """The flip-form butterfly loop (module docstring): out = sum_d
    C_d * rev_d(x) over the target axes `taxes` of the segment views
    `re`/`im`. Control masking is the caller's job. Shared by the flat
    apply_matrix and the shaped row-view path (apply_matrix_rows)."""
    k = len(targets)
    lib = np if concrete else jnp
    rows = np.arange(1 << k)
    nre = None
    nim = None
    for d in range(1 << k):
        # coefficient vector c[b] = m[b, b ^ d], laid out along target axes
        cre = mre[rows, rows ^ d]
        cim = mim[rows, rows ^ d]
        if concrete and np.all(cre == 0.0) and np.all(cim == 0.0):
            continue
        rev = [taxes[j] for j in range(k) if (d >> j) & 1]
        xr = jnp.flip(re, rev) if rev else re
        xi = jnp.flip(im, rev) if rev else im
        fre = _diag_broadcast(cre, k, targets, dims, axis_of, lib)
        fim = _diag_broadcast(cim, k, targets, dims, axis_of, lib)
        if concrete and np.all(cim == 0.0):
            if np.all(cre == 1.0):
                tr, ti = xr, xi       # pure amplitude permutation (X-like)
            else:
                tr, ti = fre * xr, fre * xi
        elif concrete and np.all(cre == 0.0):
            tr, ti = -fim * xi, fim * xr
        else:
            tr = fre * xr - fim * xi
            ti = fre * xi + fim * xr
        nre = tr if nre is None else nre + tr
        nim = ti if nim is None else nim + ti

    if nre is None:  # all-zero matrix
        nre = jnp.zeros_like(re)
        nim = jnp.zeros_like(im)
    return nre, nim


def _f64_mxu_enabled() -> bool:
    """Whether f64 band contractions ride the MXU limb scheme
    (_limb_band_contract). Default: on for TPU backends (where native
    f64 dots are software-emulated scalar-by-scalar — the measured
    9 gates/s @ 26q wall, VERDICT r4 item 2), off elsewhere (XLA-CPU
    has real f64 units). QUEST_F64_MXU=1/0 forces either way (1 is how
    the CPU test suite exercises the scheme's numerics); parse and
    default live in the knob registry (env.KNOBS)."""
    from quest_tpu.env import knob_value
    return knob_value("QUEST_F64_MXU")


_LIMB_BITS = 8          # limb width: bf16-exact integers (<= 2^8)
_LIMB_RADIX = float(1 << _LIMB_BITS)
_LIMB_CUTOFF = 5        # keep pair-dots with i+j <= CUTOFF: representation
                        # + truncation error ~2^-49 of the row max, under
                        # the f64 REAL_EPS 1e-13 with margin; 21 dots per
                        # real contraction


def _limb_band_contract(g64, x64):
    """f64 band contraction out[p,a,q] = sum_b g[a,b] x[p,b,q] computed
    EXACTLY on f32/bf16 matmul hardware via fixed-point limb slicing
    (the Ozaki-scheme idea, recast for the band layout):

      * each contraction vector (x over b per (p,q); g row over b) is
        scaled by its own max and sliced into 8-bit INTEGER limbs —
        integers <= 2^8 are exact in bf16, their products are <= 2^16,
        and a 128-term f32 accumulation of those stays < 2^24, so every
        limb-pair dot is EXACT even at DEFAULT (single-bf16-pass) MXU
        precision;
      * pair-dots are summed as int32 (native VPU ops; up to 6 exact
        integer pair-dots per weight class), and only the final
        6-term weighted combine runs in (emulated) f64.

    Error: ~2^-49 relative to each contraction row's max — norm-class
    f64 accuracy — at 21 single-pass MXU dots per real contraction
    instead of a software-emulated f64 matmul. The per-row scaling is
    what makes the accuracy NORM-relative: a global scale would swamp
    small-amplitude rows (a 30q uniform superposition sits at 2^-15)."""
    f32, f64 = jnp.float32, jnp.float64
    nl = _LIMB_CUTOFF + 1

    def limbs(v, axis):
        s = jnp.max(jnp.abs(v), axis=axis, keepdims=True)
        s = jnp.where(s == 0.0, 1.0, s)
        # snap the scale UP to a power of two: the normalizing division
        # and the final recombine multiply are then EXACT, leaving limb
        # truncation as the scheme's only error term (and grid-aligned
        # inputs round-trip bit-exactly). The guard row protects the
        # |r| <= 1 invariant against log2 rounding down — an li > 256
        # would silently break the exact-bf16-product argument.
        s = jnp.exp2(jnp.ceil(jnp.log2(s)))
        r = v / s
        s = jnp.where(jnp.max(jnp.abs(r), axis=axis, keepdims=True) > 1.0,
                      s * 2.0, s)
        r = v / s
        out = []
        for _ in range(nl):
            r = r * _LIMB_RADIX
            li = jnp.round(r)
            r = r - li
            out.append(li.astype(f32))
        return s, out

    sg, gl = limbs(g64, axis=1)             # g: (band, band), rows over b
    sx, xl = limbs(x64, axis=1)             # x: (pre, band, post) over b

    def pair_dot(gj, xi):
        return jnp.einsum("ab,pbq->paq", gj, xi,
                          precision=jax.lax.Precision.DEFAULT)

    total = None
    for s_tot in range(_LIMB_CUTOFF + 1):
        sub = None
        for i in range(min(s_tot + 1, nl)):
            j = s_tot - i
            if j >= nl:
                continue
            d = pair_dot(gl[j], xl[i]).astype(jnp.int32)
            sub = d if sub is None else sub + d
        term = sub.astype(f64) * (_LIMB_RADIX ** -(s_tot + 2))
        total = term if total is None else total + term
    return sg.reshape(1, -1, 1) * sx * total


def _f64_chunk_elems() -> int:
    """Row-chunk size (elements) for the f64 limb path. The un-chunked
    scheme materializes six full-band f32 limb slices per limbs() call
    (three calls per complex contraction via Gauss) plus int32 partials
    — ~4x the f64 state in HLO temps, which OOMed 28q on a 15.75 GiB
    v5e (scripts/probe_f64.py, measured 2026-08-02). Chunking the
    contraction bounds the temps at chunk size; the path is HBM-bound,
    so per-chunk MXU efficiency is unaffected at this granularity.
    QUEST_F64_CHUNK overrides (elements per chunk; 0 disables chunking);
    knobs parse loudly per the config convention — the registry parser
    (env.KNOBS) rejects non-integers, negatives and non-powers-of-two
    HERE instead of as an opaque reshape error deep inside tracing
    (_limb_apply_chunked derives its chunk count by exact division;
    ADVICE r5 item 1)."""
    from quest_tpu.env import knob_value
    return knob_value("QUEST_F64_CHUNK")


_LIMB_TEMP_MULT = 4     # measured working-set multiplier of the limb
# application: six f32 limb slices per limbs() call (x two live calls,
# g's being negligible) plus the int32 weight-class partials come to
# ~4x the f64 bytes being contracted. The UN-chunked form materializes
# this against the whole state — the ~4x working set that OOMed 28q on
# a 15.75 GiB v5e (scripts/probe_f64.py probe_28q, 2026-08-02); the
# chunked form pays it per chunk only.

_V5E_HBM_BYTES = int(15.75 * 2 ** 30)   # the modeled chip when no
# capacity is given (a v5e's usable HBM, read off its own OOM report, r3)


def f64_capacity_stats(n: int, chunk_elems: int = None,
                       hbm_bytes: int = None) -> dict:
    """CPU-side peak-memory model of an f64 limb band pass at register
    size `n` — the plan_stats()['f64'] record that answers the
    28q-capacity sizing question WITHOUT a chip (docs/PRECISION.md):

        peak = 2 x state (in + out planes around the donated update)
             + _LIMB_TEMP_MULT x the f64 bytes one chunk contracts

    chunk_elems defaults to the effective QUEST_F64_CHUNK (0 = chunking
    off — the un-chunked ~4x-state working set); hbm_bytes to the
    QUEST_HBM_BYTES override when set (the same knob the bench's OOM
    gate honors — a non-v5e chip answers for ITS capacity), else the
    v5e constant: this is a host-side model, it asks no device.
    `fits_hbm` is the routing gate bench.py's f64 ladder checks before
    paying a 28q compile (the un-chunked 28q attempt burned its full
    compile before the guaranteed OOM)."""
    state_bytes = 2 * 8 * (1 << n)          # f64 re+im planes
    if chunk_elems is None:
        chunk_elems = _f64_chunk_elems()
    chunk_elems = int(chunk_elems)
    if chunk_elems and chunk_elems < (1 << n):
        chunk_bytes = 2 * 8 * chunk_elems   # re+im chunk pair
    else:
        chunk_elems = 0                     # effectively un-chunked
        chunk_bytes = state_bytes
    temp_bytes = _LIMB_TEMP_MULT * chunk_bytes
    if hbm_bytes is None:
        from quest_tpu.env import knob_value
        hbm_bytes = knob_value("QUEST_HBM_BYTES")   # parses loudly
        if hbm_bytes is None:
            hbm_bytes = _V5E_HBM_BYTES
    peak = 2 * state_bytes + temp_bytes
    # deliberately NO backend-dependent fields (e.g. the QUEST_F64_MXU
    # default probes jax.default_backend()): plan_stats must stay pure
    # host math — callable before backend init
    return {
        "n": int(n),
        "state_bytes": state_bytes,
        "chunk_elems": chunk_elems,
        "chunk_temp_bytes": temp_bytes,
        "peak_bytes": peak,
        "hbm_bytes": int(hbm_bytes),
        "fits_hbm": peak <= int(hbm_bytes),
    }


def mode_key():
    """The apply-level trace-mode flags: everything THIS module reads
    from the environment at trace time, derived from the knob registry
    (env.engine_mode_key, layer='apply' = matmul precision, the f64-MXU
    switch, the limb chunk size). Any jit cache over functions that
    trace through ops/apply must carry this key, or flipping a knob
    mid-process returns stale programs (ADVICE r5 item 2: the eager
    per-gate workers in ops/gates.py had exactly that hole). circuit's
    _engine_mode_key is the all-layer superset."""
    from quest_tpu.env import engine_mode_key
    return engine_mode_key(layer="apply")


def _chunk_grid(pre: int, band: int, post: int,
                chunk_elems: int) -> Tuple[int, int]:
    """(chunks along pre, chunks along post) for _limb_apply_chunked.
    The larger axis splits first (its chunks stay contiguous); the
    other axis splits ONLY when the first alone cannot reach the
    needed chunk count — the wide-band/unbalanced case (e.g. pre=4,
    band=128, post=4096 with a small QUEST_F64_CHUNK) where the old
    single-axis split left chunks of band*post elements and broke the
    "temps never exceed chunk size" guarantee (ADVICE r5 item 3).

    Every quantity is a power of two (state sizes are; the registry
    parser pins chunk_elems), so all divisions here are exact. The
    resulting chunk size (pre//ncp) * band * (post//ncq) is <=
    chunk_elems whenever chunk_elems >= band; one band row is the
    floor — the band axis itself is never split (the contraction
    needs it whole)."""
    size = pre * band * post
    nc_needed = max(1, size // int(chunk_elems))
    if pre >= post:
        ncp = min(pre, nc_needed)
        ncq = min(post, nc_needed // ncp)
    else:
        ncq = min(post, nc_needed)
        ncp = min(pre, nc_needed // ncq)
    chunk = (pre // ncp) * band * (post // ncq)
    assert chunk <= max(int(chunk_elems), band), \
        (pre, band, post, chunk_elems, ncp, ncq)
    return ncp, ncq


def _limb_apply_chunked(gre, gim, re, im, real_only, chunk_elems):
    """The complex f64 band application of apply_band, computed through
    _limb_band_contract one row-chunk at a time under jax.lax.map so
    the limb slices and int32 partials never exceed chunk size (strict
    for chunk_elems >= band; the band axis is the floor — see
    _chunk_grid). The larger of the pre/post axes chunks first and the
    other splits only when needed, so balanced shapes keep the old
    single-relayout behavior while wide-band/unbalanced shapes still
    honor the bound."""
    pre, band, post = re.shape
    ncp, ncq = _chunk_grid(pre, band, post, chunk_elems)
    pc, qc = pre // ncp, post // ncq
    gre64 = jnp.asarray(gre, jnp.float64)
    gim64 = jnp.asarray(gim, jnp.float64)

    def resh(x):
        x = x.reshape(ncp, pc, band, ncq, qc)
        x = jnp.moveaxis(x, 3, 1)           # (ncp, ncq, pc, band, qc)
        return x.reshape(ncp * ncq, pc, band, qc)

    def unresh(x):
        x = x.reshape(ncp, ncq, pc, band, qc)
        x = jnp.moveaxis(x, 1, 3)
        return x.reshape(pre, band, post)

    def body(xs):
        re_c, im_c = xs
        if real_only:
            return (_limb_band_contract(gre64, re_c),
                    _limb_band_contract(gre64, im_c))
        t1 = _limb_band_contract(gre64, re_c)
        t2 = _limb_band_contract(gim64, im_c)
        t3 = _limb_band_contract(gre64 + gim64, re_c + im_c)
        return t1 - t2, t3 - t1 - t2

    nre, nim = jax.lax.map(body, (resh(re), resh(im)))
    return unresh(nre), unresh(nim)


def apply_band(
    amps: jax.Array,
    n: int,
    op_pair,
    ql: int,
    w: int,
    preds: Sequence[Tuple[int, int]] = (),
) -> jax.Array:
    """Apply a composed (2^w, 2^w) band operator to qubits [ql, ql+w) of
    the n-qubit state `amps` (2, 2^n), optionally masked by out-of-band
    (qubit, want) control predicates.

    The band occupies one contiguous bit-range of the amplitude index, so
    the state reshapes to (pre, 2^w, post) and the operator applies as ONE
    axis contraction — a batched matmul on the MXU (out[p,a,q] =
    sum_b G[a,b] x[p,b,q]). This is how every single-qubit gate reaches
    the matrix unit; see quest_tpu/ops/fusion.py for the planner."""
    gre, gim, concrete = _as_pair(op_pair, amps.dtype)
    real_only = concrete and np.all(gim == 0.0)
    band = 1 << w
    post = 1 << ql
    pre = (1 << n) >> (ql + w)
    re = amps[0].reshape(pre, band, post)
    im = amps[1].reshape(pre, band, post)
    gre = jnp.asarray(gre).reshape(band, band)
    gim = jnp.asarray(gim).reshape(band, band)
    hi = precision.matmul_precision()

    limb64 = amps.dtype == jnp.float64 and _f64_mxu_enabled()
    chunk = _f64_chunk_elems() if limb64 else 0
    if limb64 and chunk and re.size > chunk:
        # large-register f64: chunked limb application keeps the HLO
        # temps bounded (28q would OOM un-chunked; _f64_chunk_elems)
        nre, nim = _limb_apply_chunked(gre, gim, re, im, real_only, chunk)
    else:
        if limb64:
            # f64 on matmul hardware without f64 dots: exact-integer
            # limb slices on the MXU (see _limb_band_contract)
            def contract(g, x):
                return _limb_band_contract(jnp.asarray(g, jnp.float64), x)
        else:
            def contract(g, x):
                return jnp.einsum("ab,pbq->paq", g, x, precision=hi)

        if real_only:
            nre = contract(gre, re)
            nim = contract(gre, im)
        else:
            # Gauss 3-multiplication complex matmul (25% fewer MXU passes)
            t1 = contract(gre, re)
            t2 = contract(gim, im)
            t3 = contract(gre + gim, re + im)
            nre = t1 - t2
            nim = t3 - t1 - t2

    if preds:
        mask = None
        for q, s in preds:
            if q < ql:
                ids = jnp.arange(post).reshape(1, 1, post)
            else:
                ids = jnp.arange(pre).reshape(pre, 1, 1)
                q = q - (ql + w)
            bit = ((ids >> q) & 1) == s
            mask = bit if mask is None else (mask & bit)
        nre = jnp.where(mask, nre, re)
        nim = jnp.where(mask, nim, im)
    return jnp.stack([nre.reshape(-1), nim.reshape(-1)])


_LANE_QUBITS = 7
_LANES = 1 << _LANE_QUBITS


import functools


@functools.lru_cache(maxsize=256)
def _lane_basis(low_rel, lc_rel, lcs):
    """(2^kl, 2^kl, 128, 128) basis: entry (i, j) is the lane-space
    embedding of e_ij over the low target qubits with low controls; plus
    the identity-on-unsatisfied-controls completion. Cached per
    (targets, controls) signature — deep circuits reuse it."""
    from quest_tpu.ops import fusion as F
    kl = len(low_rel)
    dim = 1 << kl
    unsat = F.embed_operator(np.zeros((dim, dim)), low_rel, lc_rel, lcs,
                             _LANE_QUBITS).real
    basis = np.zeros((dim, dim, _LANES, _LANES))
    for i in range(dim):
        for j in range(dim):
            e = np.zeros((dim, dim))
            e[i, j] = 1.0
            # embed_operator folds identity-on-unsatisfied-controls into
            # EVERY embedding; strip it so the linear combination
            # L = sum sub[i,j] B_ij scales only the gate content
            basis[i, j] = F.embed_operator(e, low_rel, lc_rel, lcs,
                                           _LANE_QUBITS).real - unsat
    return basis, unsat


def _apply_matrix_laneblock(amps, n, op_pair, targets, controls,
                            control_states):
    """Matrix on a big register where some target is a lane
    qubit (< 7): per high-target bit pattern pair (r, c), a 128x128 lane
    operator applies as (rows, 128) @ L_rc^T — the minor dim never drops
    below 128 lanes (TPU tiling stays 1x). Works for traced operands (the
    embedding is a linear combination of precomputed basis matrices)."""
    rows = 1 << (n - _LANE_QUBITS)
    out = _laneblock_core(amps.reshape(2, rows, _LANES), n, op_pair,
                          targets, controls, control_states)
    return out.reshape(2, -1)


_PASSTHROUGH_CHUNKS = 8          # capacity-mode sweep granularity
_CHUNK_MIN_BYTES = 1 << 30       # chunk once a plane reaches 1 GiB


def _laneblock_core(st2, n, op_pair, targets, controls,
                    control_states, chunks=None):
    """_apply_matrix_laneblock's body on the STACKED (2, rows, 128)
    planes, returning the same shape — shared with apply_matrix_rows,
    whose callers keep the state in the kernel layout and must never
    see a flat (2, 2^n) intermediate (the layout round-trip costs a
    full-state copy on TPU). The stacked carry matters for the chunked
    path: a fori_loop over separate per-plane carries forces XLA to
    materialize each plane as its own buffer (measured: +8 GiB at 30q),
    while ONE stacked carry aliases the donated input. `chunks`: None =
    auto (chunk the sweep once a plane reaches _CHUNK_MIN_BYTES), 1 =
    whole-plane, N = force N chunks (tests exercise the chunked path at
    small sizes)."""
    rdtype = st2.dtype
    mre, mim, concrete = _as_pair(op_pair, rdtype)
    k = len(targets)
    mre = mre.reshape(1 << k, 1 << k)
    mim = mim.reshape(1 << k, 1 << k)
    low_idx = [j for j, t in enumerate(targets) if t < _LANE_QUBITS]
    high_idx = [j for j, t in enumerate(targets) if t >= _LANE_QUBITS]
    kl, kh = len(low_idx), len(high_idx)
    lc = [c for c in controls if c < _LANE_QUBITS]
    lcs = [s for c, s in zip(controls, control_states) if c < _LANE_QUBITS]
    hc = [(c, s) for c, s in zip(controls, control_states)
          if c >= _LANE_QUBITS]
    basis, unsat = _lane_basis(tuple(targets[j] for j in low_idx),
                               tuple(lc), tuple(lcs))
    lib = np if concrete else jnp
    # cast in BOTH branches: the float64 basis otherwise promotes a
    # float32 state to float64 under jax_enable_x64 (doubling the state
    # buffer — the very OOM this path prevents)
    if concrete:
        basis_l = basis.astype(rdtype)
        unsat_l = unsat.astype(rdtype)
    else:
        basis_l = jnp.asarray(basis, dtype=rdtype)
        unsat_l = jnp.asarray(unsat, dtype=rdtype)

    def _indices(hpat):
        """Matrix indices whose low bits sweep and high bits equal hpat."""
        out = np.zeros(1 << kl, dtype=np.int64)
        for a in range(1 << kl):
            v = 0
            for b, j in enumerate(low_idx):
                v |= ((a >> b) & 1) << j
            for b, j in enumerate(high_idx):
                v |= ((hpat >> b) & 1) << j
            out[a] = v
        return out

    def sub_block(m, rh, ch):
        """(2^kl, 2^kl) sub-matrix for high pattern (rh, ch)."""
        rows, cols = _indices(rh), _indices(ch)
        return m[np.ix_(rows, cols)] if concrete else m[rows][:, cols]

    def lane_op(m, rh, ch, with_unsat):
        sub = sub_block(m, rh, ch)
        L = lib.tensordot(sub, basis_l, axes=([0, 1], [0, 1]))
        if with_unsat:
            L = L + unsat_l
        return L

    # row-space view: high target bits get axes; trailing lane axis 128
    rows_n = n - _LANE_QUBITS
    high_bits = sorted({targets[j] - _LANE_QUBITS for j in high_idx} |
                       {c - _LANE_QUBITS for c, _ in hc}, reverse=True)
    rdims, raxis = seg_view(rows_n, tuple(high_bits))
    dims = rdims + (_LANES,)
    view = st2.reshape((2,) + dims)
    taxes = [raxis[targets[j] - _LANE_QUBITS] for j in high_idx]
    ndims = len(dims)

    hi = precision.matmul_precision()

    def matmul(x, L):
        flat = x.reshape(-1, _LANES)
        return jnp.matmul(flat, L.T, precision=hi).reshape(x.shape)

    def apply_view(vre, vim):
        """The block-matmul sweep on one view with the `dims` axis
        structure (the chunked path calls it with a shorter free axis —
        only sizes change, never axis numbering)."""

        def block(x, combo):
            idx = [slice(None)] * ndims
            for b, ax in enumerate(taxes):
                v = (combo >> b) & 1
                idx[ax] = slice(v, v + 1)
            return x[tuple(idx)]

        out_re = [None] * (1 << kh)
        out_im = [None] * (1 << kh)
        for rh in range(1 << kh):
            nr = ni = None
            for ch in range(1 << kh):
                Lre = lane_op(mre, rh, ch, with_unsat=(rh == ch))
                Lim = lane_op(mim, rh, ch, with_unsat=False)
                xr, xi_ = block(vre, ch), block(vim, ch)
                if concrete and np.all(np.asarray(Lim) == 0.0):
                    if np.all(np.asarray(Lre) == 0.0):
                        continue
                    tr, ti = matmul(xr, Lre), matmul(xi_, Lre)
                else:
                    t1 = matmul(xr, Lre)
                    t2 = matmul(xi_, Lim)
                    t3 = matmul(xr + xi_, Lre + Lim)
                    tr, ti = t1 - t2, t3 - t1 - t2
                nr = tr if nr is None else nr + tr
                ni = ti if ni is None else ni + ti
            if nr is None:
                nr = jnp.zeros_like(block(vre, rh))
                ni = jnp.zeros_like(block(vim, rh))
            out_re[rh] = nr
            out_im[rh] = ni

        for b in range(kh):
            ax = taxes[b]
            out_re = [jnp.concatenate([out_re[2 * i], out_re[2 * i + 1]],
                                      axis=ax)
                      for i in range(len(out_re) // 2)]
            out_im = [jnp.concatenate([out_im[2 * i], out_im[2 * i + 1]],
                                      axis=ax)
                      for i in range(len(out_im) // 2)]
        nre, nim = out_re[0], out_im[0]

        if hc:
            mask = None
            for c, s in hc:
                shape = [1] * ndims
                shape[raxis[c - _LANE_QUBITS]] = 2
                vec = jnp.arange(2).reshape(shape) == s
                mask = vec if mask is None else (mask & vec)
            nre = jnp.where(mask, nre, vre)
            nim = jnp.where(mask, nim, vim)
        return nre, nim

    # Near HBM capacity the block matmuls cost full-plane layout copies
    # (measured at 30q: XLA hoists a 4 GiB transposed copy PER PLANE so
    # the strided target-axis blocks become contiguous — with the state
    # itself that is 20 GiB > v5e's 15.75). Chunk the sweep over the
    # largest FREE segment axis (the op never mixes it): a fori_loop
    # reads one chunk, applies the sweep, and writes it back in place,
    # so only chunk-sized temps are ever live.
    free_axes = [ax for ax in range(ndims - 1)
                 if ax not in raxis.values()]
    chunk_ax = max(free_axes, key=lambda ax: dims[ax], default=None)
    if chunks is None:
        plane_bytes = st2[0].size * st2.dtype.itemsize
        chunks = _PASSTHROUGH_CHUNKS if plane_bytes >= _CHUNK_MIN_BYTES \
            else 1
    if chunk_ax is not None and chunks > 1:
        chunks = min(chunks, dims[chunk_ax])   # powers of 2 throughout
    if chunk_ax is not None and chunks > 1 \
            and dims[chunk_ax] % chunks == 0:
        cs = dims[chunk_ax] // chunks
        vax = chunk_ax + 1                     # skip the plane axis

        def body(i, carry):
            start = i * cs
            chunk = lax.dynamic_slice_in_dim(carry, start, cs, axis=vax)
            nr, ni = apply_view(chunk[0], chunk[1])
            return lax.dynamic_update_slice_in_dim(
                carry, jnp.stack([nr, ni]), start, axis=vax)

        out = lax.fori_loop(0, chunks, body, view)
    else:
        nre, nim = apply_view(view[0], view[1])
        out = jnp.stack([nre, nim])
    return out.reshape(st2.shape)


def _apply_matrix_matmul(amps, n, op_pair, targets, controls,
                         control_states):
    """Many-target path: move target axes minor-most, apply the operator as
    a (rest, 2^k) @ (2^k, 2^k) matmul (MXU once 2^k is lane-sized), move
    back. This is the analogue of the reference's general gather/matvec/
    scatter kernel (QuEST_cpu.c:1814-1898) expressed as one contraction."""
    k = len(targets)
    mre, mim, concrete = _as_pair(op_pair, amps.dtype)
    lib = np if concrete else jnp
    m_re = mre.reshape((2,) * (2 * k))
    m_im = mim.reshape((2,) * (2 * k))
    # matrix row/col bit j <-> axis (k-1-j) / (2k-1-j); permute so both row
    # and col axes run in DESCENDING target-qubit order (matching the order
    # target axes appear in the state's segment view)
    order = sorted(range(k), key=lambda j: -targets[j])
    perm = [k - 1 - j for j in order] + [2 * k - 1 - j for j in order]
    m2 = lib.transpose(m_re, perm).reshape(1 << k, 1 << k)
    m2i = lib.transpose(m_im, perm).reshape(1 << k, 1 << k)

    dims, axis_of = _split_view(n, targets, controls)
    ndims = len(dims)
    taxes = [axis_of[t] for t in sorted(targets, reverse=True)]
    rest_axes = [a for a in range(ndims) if a not in taxes]
    fwd = rest_axes + taxes

    def to2d(x):
        t = jnp.transpose(x.reshape(dims), fwd)
        return t.reshape(-1, 1 << k)

    re2 = to2d(amps[0])
    im2 = to2d(amps[1])
    hi = precision.matmul_precision()
    # new[r, s'] = sum_s m2[s', s] v[r, s] -> v @ m2^T
    m2_t, m2i_t = jnp.asarray(m2).T, jnp.asarray(m2i).T
    nre = jnp.matmul(re2, m2_t, precision=hi) - jnp.matmul(im2, m2i_t,
                                                           precision=hi)
    nim = jnp.matmul(re2, m2i_t, precision=hi) + jnp.matmul(im2, m2_t,
                                                            precision=hi)

    inv = [0] * ndims
    for pos, a in enumerate(fwd):
        inv[a] = pos
    tshape = [dims[a] for a in fwd]

    def back(x2):
        return jnp.transpose(x2.reshape(tshape), inv)

    nre_t, nim_t = back(nre), back(nim)
    mask = control_mask(ndims, axis_of, controls, control_states)
    if mask is not None:
        nre_t = jnp.where(mask, nre_t, amps[0].reshape(dims))
        nim_t = jnp.where(mask, nim_t, amps[1].reshape(dims))
    return jnp.stack([nre_t.reshape(-1), nim_t.reshape(-1)])


def _diag_broadcast(d, k, targets, dims, axis_of, lib):
    """Reshape a (2^k,) diagonal so entry bits line up with target axes of
    the segment view. d index bit j corresponds to targets[j]."""
    view = d.reshape((2,) * k)  # axis i <-> bit (k-1-i) <-> targets[k-1-i]
    qubit_of_axis = [targets[k - 1 - i] for i in range(k)]
    # transpose to descending qubit order (= ascending view-axis order)
    perm = sorted(range(k), key=lambda i: -qubit_of_axis[i])
    view = lib.transpose(view, perm) if k > 1 else view
    shape = [1] * len(dims)
    for t in targets:
        shape[axis_of[t]] = 2
    return view.reshape(shape)


def apply_diagonal(
    amps: jax.Array,
    n: int,
    d_pair,
    targets: Sequence[int],
    controls: Sequence[int] = (),
    control_states: Sequence[int] = (),
) -> jax.Array:
    """Multiply by a diagonal operator given as a (2^k,) (re, im) pair over
    `targets`. Diagonal gates never permute amplitudes — the reference
    exploits this to skip communication (QuEST_cpu.c:2940-3109); here it is
    a pure broadcast multiply that XLA fuses into neighbouring ops."""
    targets = tuple(int(t) for t in targets)
    controls = tuple(int(c) for c in controls)
    k = len(targets)
    dre, dim_, concrete = _as_pair(d_pair, amps.dtype)
    dims, axis_of = _split_view(n, targets, controls)
    ndims = len(dims)
    re = amps[0].reshape(dims)
    im = amps[1].reshape(dims)
    lib = np if concrete else jnp
    fre = _diag_broadcast(dre.reshape(-1), k, targets, dims, axis_of, lib)
    fim = _diag_broadcast(dim_.reshape(-1), k, targets, dims, axis_of, lib)
    if concrete and np.all(fim == 0.0):
        nre, nim = re * fre, im * fre
    else:
        nre = re * fre - im * fim
        nim = re * fim + im * fre
    mask = control_mask(ndims, axis_of, controls, control_states)
    if mask is not None:
        nre = jnp.where(mask, nre, re)
        nim = jnp.where(mask, nim, im)
    return jnp.stack([nre.reshape(-1), nim.reshape(-1)])


def apply_parity_phase(
    amps: jax.Array,
    n: int,
    targets: Sequence[int],
    angle: jax.Array,
) -> jax.Array:
    """exp(-i angle/2 * Z x ... x Z) over `targets`
    (ref statevec_multiRotateZ semantics, QuEST_cpu.c:3069-3109).

    The phase of each amplitude depends only on the parity of its target
    bits: factor exp(-i angle/2 * (-1)^parity), via a broadcast product of
    per-axis (+1, -1) sign vectors — no 2^k table, no permutation."""
    targets = tuple(int(t) for t in targets)
    dims, axis_of = _split_view(n, targets, ())
    re = amps[0].reshape(dims)
    im = amps[1].reshape(dims)
    rdt = amps.dtype
    sign = parity_sign(len(dims), axis_of, targets, rdt)
    half = jnp.asarray(angle, dtype=rdt) / 2.0
    cosf = jnp.cos(half)          # even in sign
    sinf = jnp.sin(half) * sign   # odd in sign
    nre = re * cosf + im * sinf
    nim = im * cosf - re * sinf
    return jnp.stack([nre.reshape(-1), nim.reshape(-1)])


def apply_phase_on_all_ones(
    amps: jax.Array,
    n: int,
    qubits: Sequence[int],
    term_pair,
) -> jax.Array:
    """Multiply amplitudes whose `qubits` bits are ALL 1 by the scalar
    `term` = (re, im). Implements the symmetric multi-controlled phase
    family (controlledPhaseShift / multiControlledPhaseShift / ...PhaseFlip,
    ref QuEST_cpu.c:2960-3035) — all listed qubits play identical roles."""
    qubits = tuple(int(q) for q in qubits)
    tre, tim, concrete = _as_pair(term_pair, amps.dtype)
    lib = np if concrete else jnp
    one = lib.ones((), dtype=amps.dtype)
    zero = lib.zeros((), dtype=amps.dtype)
    dre = lib.stack([one, lib.asarray(tre, dtype=amps.dtype).reshape(())])
    dim_ = lib.stack([zero, lib.asarray(tim, dtype=amps.dtype).reshape(())])
    return apply_diagonal(amps, n, (dre, dim_), (qubits[0],),
                          controls=qubits[1:],
                          control_states=(1,) * (len(qubits) - 1))
