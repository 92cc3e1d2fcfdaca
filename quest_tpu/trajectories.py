"""Quantum-trajectory noise simulation: stochastic Kraus unraveling.

The reference simulates noise ONLY via density matrices — 2^{2N}
amplitudes (`QuEST.c` mixDamping/mixKrausMap on the doubled register),
which caps noisy registers at half the qubit count of pure states. The
trajectory method unravels a channel into a stochastic choice of Kraus
branch per shot: each trajectory is a STATEVECTOR (2^N), and averaging
|psi><psi| over shots converges to the channel's density matrix. On TPU
the method is a natural fit: a trajectory is a pure traced function of a
`jax.random` key, so `jax.vmap` runs a whole batch of shots as one
compiled program, and every gate inside rides the same engines as
noiseless simulation.

    key = jax.random.key(0)
    def shot(k):
        amps = state.basis_planes(0, n=n, rdt=jnp.float32)
        amps = V.h(amps, n, 0)
        amps, k, _ = T.damping(amps, k, n, 0, 0.3)
        amps, k, _ = T.depolarising(amps, k, n, 1, 0.1)
        return amps
    batch = jax.vmap(shot)(jax.random.split(key, 4096))  # (shots, 2, 2^n)

Averages of observables over the batch estimate the open-system result
to O(1/sqrt(shots)); `tests/test_trajectories.py` pins the estimator
against the exact density-matrix engine.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from quest_tpu import cplx
from quest_tpu.ops import apply as A
from quest_tpu.ops import matrices as M


def _targets_tuple(targets):
    return (targets,) if np.isscalar(targets) else tuple(targets)


_VALIDATED_KRAUS: set = set()


def _validate_kraus_once(ops, num_targets: int) -> None:
    """validate_kraus_ops, memoized BY VALUE: the CPTP check is O(m d^3)
    host math, and a per-shot Python loop (or every retrace of a vmapped
    shot) would re-run it for the SAME channel thousands of times. One
    validation per distinct (target count, operator values) channel per
    process; the batched engine validates at plan time through the same
    memo (regression-pinned in tests/test_batched.py)."""
    key = (num_targets, tuple((K.shape, K.tobytes()) for K in ops))
    if key in _VALIDATED_KRAUS:
        return
    from quest_tpu import validation as val
    val.validate_kraus_ops(ops, num_targets)
    _VALIDATED_KRAUS.add(key)


def kraus(amps, key, n, targets, ops: Sequence) -> Tuple:
    """One stochastic application of the Kraus map {K_k} to `targets`:
    branch k is drawn with Born probability p_k = ||K_k psi||^2 and the
    state renormalizes to K_k psi / sqrt(p_k). Returns
    (new_amps, next_key, branch_index).

    All branches are evaluated (their norms are needed for the
    probabilities anyway) and the draw selects via a one-hot weighted
    sum — branch-free, so the whole thing jits and vmaps cleanly."""
    targets = _targets_tuple(targets)
    ops = [np.asarray(K, dtype=np.complex128) for K in ops]
    # same CPTP check as the density engine's mix_kraus_map: a
    # mis-normalized set would otherwise converge silently to a
    # DIFFERENT channel (categorical renormalizes the probabilities).
    # Memoized by value — one validation per distinct channel per
    # process, however many shots call through here
    _validate_kraus_once(ops, len(targets))
    key, sub = jax.random.split(key)
    ws = [A.apply_matrix(amps, n, cplx.pack(K), targets) for K in ops]
    ps = jnp.stack([jnp.sum(w[0] * w[0] + w[1] * w[1]) for w in ws])
    # zero-probability branches are masked OUT (-inf logit), not
    # epsilon-floored: a flat epsilon could still draw an impossible
    # branch (p exactly 0) with probability ~eps*k
    logits = jnp.where(ps > 0, jnp.log(jnp.maximum(ps, jnp.finfo(ps.dtype).tiny)),
                       -jnp.inf)
    k = jax.random.categorical(sub, logits)
    onehot = jax.nn.one_hot(k, len(ops), dtype=amps.dtype)
    w = ws[0] * onehot[0]
    for i in range(1, len(ops)):
        w = w + ws[i] * onehot[i]
    return w / jnp.sqrt(ps[k]), key, k


def unitary_mixture(amps, key, n, targets, probs, unitaries) -> Tuple:
    """Stochastic application of a UNITARY mixture sum_k p_k U . U+:
    the branch probabilities are state-independent, so the draw happens
    first and only the chosen branch applies (lax.switch) — one gate
    per shot instead of one per branch. This covers every unital Pauli
    channel (dephasing/depolarising/pauli); general Kraus maps need
    `kraus` (state-dependent Born probabilities)."""
    targets = _targets_tuple(targets)
    probs = np.asarray(probs, dtype=np.float64)
    key, sub = jax.random.split(key)
    logits = np.where(probs > 0, np.log(np.maximum(probs, 1e-300)), -np.inf)
    k = jax.random.categorical(sub, jnp.asarray(logits))
    branches = [
        (lambda a, U=np.asarray(U, dtype=np.complex128):
         A.apply_matrix(a, n, cplx.pack(U), targets))
        for U in unitaries]
    return jax.lax.switch(k, branches, amps), key, k


def _validate_channel_prob(p: float, what: str) -> float:
    """Trajectory channels accept the full CPTP range 0 <= p <= 1 —
    wider than the density API's maximal-mixing caps (1/2, 3/4, ...,
    QuEST_validation.c:113-117), which encode a convention, not
    validity. Out-of-range still fails loudly instead of unraveling to
    an all-NaN state."""
    from quest_tpu.validation import QuESTError
    p = float(p)
    if not (0.0 <= p <= 1.0):
        raise QuESTError(
            f"Invalid probability: the {what} probability must be in "
            f"[0, 1] for a trajectory unraveling, got {p}")
    return p


def damping(amps, key, n, target, prob):
    """Amplitude damping as a trajectory branch (ref mixDamping
    semantics, QuEST_cpu.c:48-130 — here at statevector cost)."""
    p = _validate_channel_prob(prob, "damping")
    return kraus(amps, key, n, target, M.damping_kraus(p))


def dephasing(amps, key, n, target, prob):
    """Phase damping (ref mixDephasing) — a unitary mixture, so only
    the drawn branch applies."""
    p = _validate_channel_prob(prob, "dephasing")
    return unitary_mixture(amps, key, n, target, [1.0 - p, p],
                           [M.PAULI_I, M.PAULI_Z])


def depolarising(amps, key, n, target, prob):
    """Depolarising channel (ref mixDepolarising) — unitary mixture."""
    p = _validate_channel_prob(prob, "depolarising")
    return unitary_mixture(amps, key, n, target,
                           [1.0 - p, p / 3.0, p / 3.0, p / 3.0],
                           list(M.PAULIS))


def pauli(amps, key, n, target, px, py, pz):
    """Probabilistic Pauli error (ref mixPauli) — unitary mixture."""
    px = _validate_channel_prob(px, "Pauli-X")
    py = _validate_channel_prob(py, "Pauli-Y")
    pz = _validate_channel_prob(pz, "Pauli-Z")
    _validate_channel_prob(px + py + pz, "total Pauli error")
    return unitary_mixture(amps, key, n, target,
                           [1.0 - px - py - pz, px, py, pz],
                           list(M.PAULIS))


def average_density(batch) -> jax.Array:
    """Dense (2^n, 2^n) estimator: mean over the shot axis of
    |psi><psi|. For validation at small n — real workloads should
    average observables instead (O(shots * 2^n), not O(shots * 4^n))."""
    re, im = batch[:, 0, :], batch[:, 1, :]
    psi = re + 1j * im
    return jnp.einsum("sa,sb->ab", psi, psi.conj()) / psi.shape[0]


# ---------------------------------------------------------------------------
# batched execution engine: B trajectories through ONE sweep launch
# ---------------------------------------------------------------------------
#
# jax.vmap over the eager per-gate workers (the module docstring's
# pattern) batches the SHOTS but keeps the per-gate pass structure: a
# B-shot workload pays B x the per-gate HBM traffic and launch count the
# sweep-fusion layer (PR 3) just eliminated for single states. The
# engine below instead rides the whole unitary structure of a NOISY
# Circuit through the batched sweep kernels — a leading batch grid
# dimension streams B states per launch — and turns each stochastic
# channel application into a per-state ONE-HOT SELECT:
#
#   * the channel's Kraus branches are classified at plan time:
#     UNITARY MIXTURES (every K_k proportional to a unitary —
#     dephasing, depolarising, Pauli) have state-independent Born
#     probabilities, so their draws depend only on the per-shot keys
#     and the selected branch fuses ANYWHERE in a sweep;
#   * GENERAL KRAUS channels (damping) need the pre-channel state: the
#     per-branch probabilities p_k = <psi|K_k^+ K_k|psi> come from the
#     targets' reduced density matrix (ONE batched reduction pass —
#     cheaper than the eager path's apply-every-branch-and-norm), the
#     draw one-hot-selects K_k, and the 1/sqrt(p_k) renormalization is
#     folded into the selected operator. The stage is a LAUNCH BARRIER
#     before (its operand reads the state between launches) but fuses
#     with everything after it.
#
# Either way the selected 2x2 rides as a (B, 8) kernel operand row per
# state (pallas_band.BatchSelStage) — the launch count of the whole
# noisy program is the UNBATCHED plan's, independent of B
# (plan_stats below; scripts/check_batch_golden.py holds the golden).
# Off-TPU (or engine="banded") the same plan executes as one vmapped
# banded-XLA program — still one compiled dispatch for the batch, with
# the band-composed pass structure instead of per-gate passes.


@dataclasses.dataclass(frozen=True)
class _XlaChannel:
    """Plan marker for a channel the kernels do not inline (multi-qubit
    Kraus, sub-kernel-tier registers): applied between sweeps as a
    vmapped XLA matrix op; segment_plan passes it through as an ("xla",
    item) part, which is also a sweep barrier."""
    index: int

    def qubits(self):
        return ()


def _mixture_probs(kraus_ops):
    """(p_k,) when every K_k is PROPORTIONAL to a unitary (K^+K = p I —
    the Born probabilities are then state-independent), else None."""
    probs = []
    for K in kraus_ops:
        d = K.shape[0]
        KK = K.conj().T @ K
        p = float(np.real(np.trace(KK)) / d)
        if not np.allclose(KK, p * np.eye(d), atol=1e-10):
            return None
        probs.append(p)
    return np.asarray(probs, dtype=np.float64)


def _traj_channels_and_items(circuit, n: int, use_kernels: bool):
    """Split a noisy Circuit into the batched engine's plan stream:
    fusion-plan items for the unitary stretches, interleaved with
    ChannelItem (kernel-inlined 1q channels) / _XlaChannel markers.
    Returns (items, channels) where channels[i] holds the static
    per-channel data (targets, Kraus stacks, mixture probabilities)."""
    from quest_tpu.circuit import flatten_ops
    from quest_tpu.ops import fusion as F
    from quest_tpu.ops import pallas_band as PB
    from quest_tpu.validation import QuESTError

    bands = PB.plan_bands(n) if use_kernels else None
    items: list = []
    channels: list = []
    stretch: list = []

    def close():
        nonlocal stretch
        if stretch:
            flat = F.maybe_schedule(
                flatten_ops(tuple(stretch), n, False), n)
            items.extend(F.plan(flat, n, bands=bands))
            stretch = []

    for op in circuit.ops:
        if op.kind == "superop":
            meta = op.meta
            if not (isinstance(meta, tuple) and meta
                    and meta[0] == "kraus"):
                raise QuESTError(
                    "Invalid operation: this channel op carries no raw "
                    "Kraus metadata; build channels through the Circuit "
                    "noise builders (kraus/damping/depolarising/"
                    "dephasing) for trajectory unraveling.")
            kraus_ops = [np.asarray(K, dtype=np.complex128)
                         for K in meta[1]]
            # plan-time validation (build-time validation already ran
            # for Circuit-built channels; the memo makes this free)
            _validate_kraus_once(kraus_ops, len(op.targets))
            probs = _mixture_probs(kraus_ops)
            idx = len(channels)
            inline = use_kernels and len(op.targets) == 1
            channels.append({
                "index": idx,
                "targets": tuple(op.targets),
                "ops": kraus_ops,
                "mixture_probs": probs,
                "inline": inline,
            })
            close()
            if inline:
                items.append(PB.ChannelItem(op.targets[0], idx,
                                            barrier=probs is None))
            else:
                items.append(_XlaChannel(idx))
            continue
        if op.kind in ("measure", "classical"):
            raise QuESTError(
                "Invalid operation: run_batched does not thread "
                "mid-circuit measurement outcomes; use "
                "compiled_measured per shot for dynamic circuits.")
        stretch.append(op)
    close()
    return items, channels


def _reduced_density(flat_b, n: int, targets):
    """(B, 2^k, 2^k) complex reduced density matrix of `targets` for a
    (B, 2, 2^n) batch of planes — ONE pass over the batch, serving the
    Born probabilities of every branch at once (tr(K^+K rho))."""
    b = flat_b.shape[0]
    k = len(targets)
    if k == 1 and n >= 7:
        # the common case, on the (rows, 128) view every engine keeps:
        # each amplitude meets its partner across the target bit through
        # a roll along lanes or rows, and the 2x2 entries are masked
        # sums. Reshaping the target bit out as its own axis leaves a
        # tiny minor dim, and a complex batched einsum lays the batch
        # axis minor: both pad 16x on the TPU (2.1 GiB of temporaries
        # for a 64 MiB bucket at 20q, compile rehearsal, PR 21).
        q = targets[0]
        x = flat_b.reshape(b, 2, -1, 128)
        axis, bit = (3, q) if q < 7 else (2, q - 7)
        idx = jax.lax.broadcasted_iota(jnp.int32, x.shape[2:], axis - 2)
        low = ((idx >> bit) & 1) == 0
        y = jnp.roll(x, -(1 << bit), axis=axis)

        def total(v):
            return jnp.sum(v, axis=(1, 2))
        re, im, pre, pim = x[:, 0], x[:, 1], y[:, 0], y[:, 1]
        norm = total(re * re + im * im)
        d0 = total(jnp.where(low, re * re + im * im, 0.0))
        r01 = (total(jnp.where(low, re * pre + im * pim, 0.0))
               + 1j * total(jnp.where(low, im * pre - re * pim, 0.0)))
        return jnp.stack([jnp.stack([d0 + 0j, r01], -1),
                          jnp.stack([jnp.conj(r01), norm - d0 + 0j], -1)],
                         -2)
    psi = flat_b[:, 0, :] + 1j * flat_b[:, 1, :]
    v = psi.reshape((b,) + (2,) * n)
    # axis of qubit q in the (b, 2, ..., 2) view; index bit j of the
    # merged target axis must equal targets[j], so the MSB-most moved
    # axis is targets[k-1]
    order = [1 + (n - 1 - q) for q in reversed(targets)]
    v = jnp.moveaxis(v, order, range(1, 1 + k))
    v = v.reshape(b, 1 << k, -1)
    return jnp.einsum("bir,bjr->bij", v, jnp.conj(v))


def _channel_select(ch, subkeys_b, flat_b, n: int):
    """Draw each state's branch for channel `ch` and build the selected
    (renormalized) operators: (draw (B,) i32, op_re (B, d, d) f32,
    op_im (B, d, d) f32). `flat_b` is only read for general Kraus
    channels (state-dependent probabilities)."""
    ops = ch["ops"]
    m = len(ops)
    kre = np.stack([K.real for K in ops]).astype(np.float32)
    kim = np.stack([K.imag for K in ops]).astype(np.float32)
    tiny = jnp.finfo(jnp.float32).tiny
    if ch["mixture_probs"] is not None:
        probs = ch["mixture_probs"]
        # logits constructed EXACTLY like unitary_mixture's (ambient
        # dtype, same masking): categorical's gumbel bits depend on the
        # logits dtype, so any deviation here would make batched draws
        # diverge from the eager path's on identical keys
        logits = jnp.asarray(np.where(probs > 0,
                                      np.log(np.maximum(probs, 1e-300)),
                                      -np.inf))
        draw = jax.vmap(
            lambda kk: jax.random.categorical(kk, logits))(subkeys_b)
        psel = jnp.asarray(probs, dtype=jnp.float32)[draw]
    else:
        mkm = np.stack([(K.conj().T @ K) for K in ops])
        rho = _reduced_density(flat_b, n, ch["targets"])
        ps = jnp.real(jnp.einsum("mij,bji->bm",
                                 jnp.asarray(mkm, rho.dtype), rho))
        logits = jnp.where(ps > 0,
                           jnp.log(jnp.maximum(ps, tiny)), -jnp.inf)
        draw = jax.vmap(jax.random.categorical)(subkeys_b, logits)
        psel = jnp.take_along_axis(ps, draw[:, None], axis=1)[:, 0]
    onehot = jax.nn.one_hot(draw, m, dtype=jnp.float32)
    inv = jax.lax.rsqrt(jnp.maximum(psel, tiny))[:, None, None]
    op_re = jnp.einsum("bm,mij->bij", onehot, jnp.asarray(kre)) * inv
    op_im = jnp.einsum("bm,mij->bij", onehot, jnp.asarray(kim)) * inv
    return draw.astype(jnp.int32), op_re, op_im


def _pack_rows(op_re, op_im):
    """(B, 2, 2) re/im pairs -> the (B, 8) BatchSelStage operand rows
    [g00re, g00im, g01re, g01im, g10re, g10im, g11re, g11im]."""
    return jnp.stack([op_re[:, 0, 0], op_im[:, 0, 0],
                      op_re[:, 0, 1], op_im[:, 0, 1],
                      op_re[:, 1, 0], op_im[:, 1, 0],
                      op_re[:, 1, 1], op_im[:, 1, 1]], axis=1)


def _resolve_engine(engine, n: int, interpret: bool) -> str:
    from quest_tpu.ops import pallas_band as PB
    if engine is not None:
        if engine not in ("fused", "banded", "host"):
            raise ValueError(f"engine must be 'fused', 'banded' or "
                             f"'host', got {engine!r}")
        return engine
    if interpret:
        return "fused" if PB.usable(n) else "banded"
    try:
        on_tpu = jax.default_backend() == "tpu"
    except Exception:           # pragma: no cover - no backend
        on_tpu = False
    if on_tpu:
        return "fused" if PB.usable(n) else "banded"
    # off-chip the XLA banded path trades memory passes for 50x the
    # FLOPs (band matmuls the MXU would eat for free): the native host
    # engine is the honest CPU fast path, exactly like the bench ladder
    from quest_tpu import host as H
    return "host" if H.available() else "banded"


def _apply_2x2_native(planes, q, op_re, op_im):
    """Per-state 2x2 on qubit `q` of (B, 2, 2^n) float planes through
    the NATIVE host engine's blocked butterfly, in place — per-call
    re-encode of the tiny one-gate program is microseconds, the
    butterfly itself runs at the native engine's memory rate (measured
    ~6x this host's numpy elementwise rate, which is allocation-bound)."""
    from quest_tpu import host as H
    from quest_tpu.circuit import GateOp

    n = planes.shape[-1].bit_length() - 1
    for s in range(planes.shape[0]):
        k = (op_re[s] + 1j * op_im[s]).astype(np.complex128)
        step = H.compile_circuit_host(
            (GateOp("matrix", (q,), operand=k),), n, False)
        step(planes[s])


_vmapped_categorical = None


def _draw_categorical(subkeys_b, logits_b):
    """One process-wide jitted vmap(categorical) — the host path's only
    per-channel jax work (the per-state logits ride in as data, so every
    channel of a given (B, m) shape shares one compiled draw)."""
    global _vmapped_categorical
    if _vmapped_categorical is None:
        _vmapped_categorical = jax.jit(jax.vmap(jax.random.categorical))
    return _vmapped_categorical(subkeys_b, logits_b)


def _host_channel_select(ch, subkeys_b, planes):
    """The host engine's channel select — numpy throughout except the
    (B, m) categorical draw, which stays jax so identically-keyed shots
    take the SAME branches as the jax engines. For a 1q general-Kraus
    channel the Born probabilities come from a transpose-free numpy
    reduced density (one pass over the chunk); mixtures never read the
    state. Returns (draw (B,), op_re (B, d, d), op_im (B, d, d))."""
    ops = ch["ops"]
    m = len(ops)
    b = planes.shape[0]
    tiny = np.finfo(np.float32).tiny
    if ch["mixture_probs"] is not None:
        probs = ch["mixture_probs"]
        logits = jnp.asarray(np.where(probs > 0,
                                      np.log(np.maximum(probs, 1e-300)),
                                      -np.inf))
        draw = np.asarray(_draw_categorical(
            subkeys_b, jnp.broadcast_to(logits, (b,) + logits.shape)))
        psel = np.asarray(probs, dtype=np.float32)[draw]
    else:
        nq = planes.shape[-1].bit_length() - 1
        q = ch["targets"][0]
        pre, post = 1 << (nq - 1 - q), 1 << q
        # reduced density from strided REAL views via einsum reductions
        # — no complex/full-state temporaries (numpy elementwise with
        # fresh allocations runs allocation-bound on small hosts)
        r = planes[:, 0].reshape(b, pre, 2, post)
        i = planes[:, 1].reshape(b, pre, 2, post)
        r0, r1, i0, i1 = r[:, :, 0], r[:, :, 1], i[:, :, 0], i[:, :, 1]

        def dot(x, y):
            return np.einsum("bpr,bpr->b", x, y)

        rho = np.empty((b, 2, 2), dtype=np.complex64)
        rho[:, 0, 0] = dot(r0, r0) + dot(i0, i0)
        rho[:, 1, 1] = dot(r1, r1) + dot(i1, i1)
        re01 = dot(r0, r1) + dot(i0, i1)
        im01 = dot(i0, r1) - dot(r0, i1)
        rho[:, 0, 1] = re01 + 1j * im01
        rho[:, 1, 0] = re01 - 1j * im01
        mkm = np.stack([(K.conj().T @ K) for K in ops])
        ps = np.real(np.einsum("mij,bji->bm", mkm, rho)).astype(
            np.float32)
        logits = np.where(ps > 0,
                          np.log(np.maximum(ps, tiny)),
                          -np.inf).astype(np.float32)
        draw = np.asarray(_draw_categorical(subkeys_b,
                                            jnp.asarray(logits)))
        psel = np.take_along_axis(ps, draw[:, None], axis=1)[:, 0]
    kre = np.stack([K.real for K in ops]).astype(np.float32)
    kim = np.stack([K.imag for K in ops]).astype(np.float32)
    inv = (1.0 / np.sqrt(np.maximum(psel, tiny)))[:, None, None]
    onehot = np.eye(m, dtype=np.float32)[draw]
    op_re = np.einsum("bm,mij->bij", onehot, kre) * inv
    op_im = np.einsum("bm,mij->bij", onehot, kim) * inv
    return draw.astype(np.int32), op_re, op_im


def _compiled_traj_host(circuit, n: int, bucket: int, key_, channels):
    """The CPU fast path: unitary stretches run through the NATIVE host
    engine's cache-blocked C++ kernels per state (quest_tpu/host.py —
    the off-chip rung of the bench ladder, ~20x the XLA-CPU banded
    path's gate rate), channels as vectorized numpy butterflies of the
    per-state selected branch. Draws reuse the SAME jax key chain and
    _channel_select math as the jax engines, so identically-keyed shots
    take identical branches whatever the engine. Returns a plain Python
    fn(keys (B, ...)) -> (planes (B, 2, 2^n) numpy, draws (B, C));
    raises host.HostEngineUnsupported when the native library or an
    op's kernel is unavailable (the caller falls back loudly)."""
    from quest_tpu import host as H

    num_chan = len(channels)
    # ("hstep", step) | ("chan", idx) | ("mixrun", [idx, ...]) — a
    # mixrun is a maximal run of CONSECUTIVE 1q mixture channels (the
    # per-qubit noise layer of a NISQ model): their draws are
    # state-independent, so each state's selected 2x2s apply as ONE
    # native program — the blocked engine sweeps the state once for
    # the whole layer instead of once per channel
    program = []
    stretch: list = []
    chan_count = 0

    def close():
        nonlocal stretch
        if stretch:
            program.append(
                ("hstep", H.compile_circuit_host(tuple(stretch), n,
                                                 False)))
            stretch = []

    for op in circuit.ops:
        if op.kind == "superop":
            close()
            idx = chan_count
            chan_count += 1
            ch = channels[idx]
            if (ch["mixture_probs"] is not None
                    and len(ch["targets"]) == 1
                    and program and program[-1][0] == "mixrun"):
                program[-1][1].append(idx)
            elif (ch["mixture_probs"] is not None
                    and len(ch["targets"]) == 1):
                program.append(("mixrun", [idx]))
            else:
                program.append(("chan", idx))
        else:
            stretch.append(op)
    close()

    def chain(k):
        subs = []
        for _ in range(num_chan):
            k, s = jax.random.split(k)
            subs.append(s)
        return jnp.stack(subs)

    # ONE jitted prelude per chunk computes everything that does not
    # read the state: the per-state key chain AND every mixture
    # channel's draw + selected operator (state-independent Born
    # probabilities) — per-channel eager dispatches would otherwise
    # dominate dense noise models (a per-qubit-per-layer circuit has
    # ~n*depth channels, each a host<->device round trip)
    mix_idx = [i for i, ch in enumerate(channels)
               if ch["mixture_probs"] is not None]

    def prelude(keys_b):
        subkeys = jax.vmap(chain)(keys_b)
        mix = {i: _channel_select(channels[i], subkeys[:, i], None, n)
               for i in mix_idx}
        return subkeys, mix
    prelude_j = jax.jit(prelude) if num_chan else None

    def fn(keys_b):
        b = keys_b.shape[0]
        if num_chan:
            subkeys, mix = prelude_j(keys_b)
            mix = {i: tuple(np.asarray(x) for x in v)
                   for i, v in mix.items()}
        planes = np.zeros((b, 2, 1 << n), dtype=np.float32)
        planes[:, 0, 0] = 1.0
        draws: dict = {}
        for el in program:
            if el[0] == "hstep":
                for s in range(b):
                    el[1](planes[s])          # native, in place
                continue
            if el[0] == "mixrun":
                from quest_tpu.circuit import GateOp
                sel = {}
                for idx in el[1]:
                    draw, op_re, op_im = mix[idx]
                    draws[idx] = np.asarray(draw).astype(np.int32)
                    sel[idx] = (np.asarray(op_re), np.asarray(op_im))
                for s in range(b):
                    ops_s = tuple(
                        GateOp("matrix", channels[idx]["targets"],
                               operand=(sel[idx][0][s]
                                        + 1j * sel[idx][1][s]
                                        ).astype(np.complex128))
                        for idx in el[1])
                    H.compile_circuit_host(ops_s, n, False)(planes[s])
                continue
            idx = el[1]
            ch = channels[idx]
            if idx in mix:
                draw, op_re, op_im = mix[idx]
                draw = draw.astype(np.int32)
            elif len(ch["targets"]) == 1:
                draw, op_re, op_im = _host_channel_select(
                    ch, subkeys[:, idx], planes)
            else:
                draw, op_re, op_im = _channel_select(
                    ch, subkeys[:, idx], jnp.asarray(planes), n)
                draw = np.asarray(draw)
            draws[idx] = draw
            if len(ch["targets"]) == 1:
                _apply_2x2_native(planes, ch["targets"][0],
                                  np.asarray(op_re), np.asarray(op_im))
            else:
                out = jax.vmap(
                    lambda a, re_, im_: A.apply_matrix(
                        a, n, (re_, im_), ch["targets"]))(
                    jnp.asarray(planes), jnp.asarray(op_re),
                    jnp.asarray(op_im))
                planes = np.asarray(out)
        if num_chan:
            out_draws = np.stack([draws[i] for i in range(num_chan)],
                                 axis=1).astype(np.int32)
        else:
            out_draws = np.zeros((b, 0), dtype=np.int32)
        return planes, out_draws

    circuit._compiled[key_] = fn
    return fn


def _compiled_traj(circuit, n: int, bucket: int, engine: str,
                   interpret: bool):
    """One jitted program fn(keys (B, ...)) -> (planes (B, 2, 2^n),
    draws (B, C) i32) running `bucket` trajectories of a noisy Circuit
    from |0...0>. Cached on the Circuit per (bucket, engine, mode)."""
    from quest_tpu.circuit import _engine_mode_key, _xla_part_applier
    from quest_tpu.ops import pallas_band as PB

    key_ = ("traj-batched", n, bucket, engine, interpret,
            _engine_mode_key())
    fn = circuit._compiled.get(key_)
    if fn is not None:
        return fn

    if engine == "host":
        from quest_tpu import host as H
        _, channels = _traj_channels_and_items(circuit, n, False)
        try:
            return _compiled_traj_host(circuit, n, bucket, key_,
                                       channels)
        except H.HostEngineUnsupported as e:
            import sys
            print(f"[trajectories] host engine unavailable ({e}); "
                  f"falling back to the banded engine", file=sys.stderr)
            engine = "banded"
            key_ = ("traj-batched", n, bucket, engine, interpret,
                    _engine_mode_key())
            fn = circuit._compiled.get(key_)
            if fn is not None:
                return fn

    use_kernels = engine == "fused" and PB.usable(n)
    items, channels = _traj_channels_and_items(circuit, n, use_kernels)
    num_chan = len(channels)

    if use_kernels:
        parts = PB.maybe_sweep(
            PB.segment_plan(items, n, batch=bucket), n)
        seg_cache: dict = {}
        program = []
        for part in parts:
            if part[0] == "segment":
                # planner invariant the operand computation leans on: a
                # barrier (general-Kraus) stage reads the state at its
                # LAUNCH boundary, so it must lead its sweep
                # (segment_plan flushes before it; sweep_plan never
                # merges its segment backward)
                for j, st in enumerate(part[1]):
                    assert not (isinstance(st, PB.BatchSelStage)
                                and st.barrier and j != 0), part[1]
                seg = PB.compile_segment_cached(
                    seg_cache, tuple(part[1]), n, interpret=interpret,
                    batch=bucket)
                program.append(("sweep", seg, part[1], part[2]))
            elif isinstance(part[1], _XlaChannel):
                program.append(("chan_xla", part[1].index))
            else:
                program.append(
                    ("xla", jax.vmap(_xla_part_applier(part, n))))
    else:
        # banded program: stretches of plan items between channels,
        # each one vmapped application over the batch
        program = []
        run: list = []
        for it in items:
            if isinstance(it, (PB.ChannelItem, _XlaChannel)):
                if run:
                    program.append(("stretch", tuple(run)))
                    run = []
                program.append(("chan_xla", it.index))
            else:
                run.append(it)
        if run:
            program.append(("stretch", tuple(run)))

    def apply_chan_xla(flat_b, idx, subkeys_b, draws):
        ch = channels[idx]
        draw, op_re, op_im = _channel_select(ch, subkeys_b, flat_b, n)
        draws[idx] = draw
        out = jax.vmap(
            lambda a, re_, im_: A.apply_matrix(a, n, (re_, im_),
                                               ch["targets"]))(
            flat_b, op_re, op_im)
        return out

    def run_program(keys_b):
        flat_b = jnp.zeros((bucket, 2, 1 << n), dtype=jnp.float32)
        flat_b = flat_b.at[:, 0, 0].set(1.0)

        # per-channel subkeys, chained per state exactly like the eager
        # path (key, sub = split(key) at each channel in program order)
        def chain(k):
            subs = []
            for _ in range(num_chan):
                k, s = jax.random.split(k)
                subs.append(s)
            return jnp.stack(subs)
        subkeys = jax.vmap(chain)(keys_b) if num_chan else None
        draws: dict = {}

        if use_kernels:
            a = flat_b.reshape(bucket, 2, -1, PB.LANES)
            for el in program:
                if el[0] == "sweep":
                    _, seg, stages, arrays = el
                    call_arrays = []
                    for st, arr in zip(stages, arrays):
                        if isinstance(st, PB.BatchSelStage):
                            ch = channels[st.index]
                            draw, op_re, op_im = _channel_select(
                                ch, subkeys[:, st.index],
                                a.reshape(bucket, 2, -1), n)
                            draws[st.index] = draw
                            call_arrays.append(_pack_rows(op_re, op_im))
                        else:
                            call_arrays.append(arr)
                    a = seg(a, call_arrays)
                elif el[0] == "chan_xla":
                    flat = a.reshape(bucket, 2, -1)
                    flat = apply_chan_xla(flat, el[1],
                                          subkeys[:, el[1]], draws)
                    a = flat.reshape(bucket, 2, -1, PB.LANES)
                else:
                    a = el[1](a)
            flat_b = a.reshape(bucket, 2, -1)
        else:
            from quest_tpu.circuit import _apply_banded_items
            for el in program:
                if el[0] == "stretch":
                    flat_b = jax.vmap(
                        lambda s, its=el[1]: _apply_banded_items(
                            s, n, its))(flat_b)
                else:
                    flat_b = apply_chan_xla(flat_b, el[1],
                                            subkeys[:, el[1]], draws)

        if num_chan:
            out_draws = jnp.stack([draws[i] for i in range(num_chan)],
                                  axis=1)
        else:
            out_draws = jnp.zeros((bucket, 0), dtype=jnp.int32)
        return flat_b, out_draws

    fn = jax.jit(run_program)
    circuit._compiled[key_] = fn
    return fn


def _bucket_for(shots: int, chunk: int = None) -> int:
    """The compiled bucket size a `shots`-trajectory run dispatches
    (docs/BATCHING.md): chunk=None caps the implicit whole-run bucket at
    the largest bucket <= shots (257 shots = one 256-chunk + a padded
    remainder, not a 512-state launch doubling peak memory); an explicit
    chunk buckets itself. The ONE home of this rule — run_batched,
    plan_stats and the durable trajectory executor
    (resilience/durable.py) all chunk through it, so an interrupted and
    an uninterrupted run dispatch the identical program sequence."""
    from quest_tpu.env import batch_bucket
    per_call = shots if chunk is None else max(1, min(int(chunk), shots))
    bucket = batch_bucket(per_call)
    if chunk is None and bucket > shots:
        smaller = batch_bucket(max(1, bucket // 2))
        if smaller < bucket:
            bucket = smaller
    return bucket


def _dispatch_chunk(fn, keys, lo: int, bucket: int):
    """One bucket-sized dispatch of shots [lo, lo+bucket): slice the
    key chain, pad the tail chunk by re-running key 0 of the chunk
    (broadcast — sliced off after), launch, unpad. The ONE home of the
    pad rule, shared by run_batched and the durable trajectory
    executor (resilience/durable.py) — their bit-identity pin depends
    on the two dispatch loops staying byte-equivalent."""
    kb = keys[lo:lo + bucket]
    pad = bucket - kb.shape[0]
    if pad:
        kb = jnp.concatenate(
            [kb, jnp.broadcast_to(kb[:1], (pad,) + kb.shape[1:])])
    planes, draws = fn(kb)
    if pad:
        planes, draws = planes[:-pad], draws[:-pad]
    return planes, draws


def program_key(circuit, engine: str = None, interpret: bool = False):
    """(resolved engine name, hashable PROGRAM IDENTITY) of the batched
    trajectory program family `run_batched` would execute for this
    circuit — the serving layer's batch-compatibility rule for
    trajectory requests (quest_tpu.serve, docs/SERVING.md): two shot
    requests may coalesce into one launch iff their identities are
    EQUAL. Mirrors Circuit.program_key: the circuit OBJECT (identity,
    kept alive by the key), op count, register size, the resolved
    engine, the interpret flag and engine_mode_key(). Bucket size is
    not part of the identity (all buckets share the plan; the compiled
    per-bucket programs cache on the circuit)."""
    from quest_tpu.circuit import _engine_mode_key

    n = circuit.num_qubits
    engine = _resolve_engine(engine, n, interpret)
    return engine, ("traj-batched", circuit, len(circuit.ops), n, engine,
                    interpret, _engine_mode_key())


def run_batched(circuit, key, shots: int, *, engine: str = None,
                interpret: bool = False, chunk: int = None,
                observable=None):
    """Run `shots` stochastic trajectories of a NOISY Circuit (channels
    built via the Circuit noise builders: kraus/damping/depolarising/
    dephasing) as batched statevector unravelings from |0...0>.
    Returns (planes, draws): planes (shots, 2, 2^n) f32 — average
    |psi><psi| (average_density) or observables over the shot axis to
    estimate the open-system result — and draws (shots, C) i32, the
    branch index every channel took in every shot (C channels in
    program order).

    THE fast path for noisy sampling: where jax.vmap of the eager
    per-gate workers pays B x the per-gate launch and HBM-pass count,
    this engine plans the circuit ONCE and rides all B states through
    the batched sweep kernels — launches do not scale with B
    (plan_stats; docs/BATCHING.md). Channel draws become per-state
    one-hot selects inside the kernels (pallas_band.BatchSelStage).

    shots are independent, keyed by jax.random.split(key, shots) —
    identical keys reproduce identical trajectories, batched or not.
    Batch sizes BUCKET like compiled_batched (env.batch_bucket,
    QUEST_BATCH_BUCKET): the compiled program serves any shot count in
    its bucket (the pad shots re-run the first key and are sliced off).
    `chunk` bounds live memory: at most bucket_of(chunk) states are
    resident at once, sequential chunks reuse the ONE compiled program.
    engine: 'fused' (batched Pallas kernels; interpret=True for CPU
    testing), 'banded' (vmapped banded XLA), or 'host' (native
    cache-blocked C++ kernels for the unitary stretches + numpy channel
    butterflies — the off-chip default, ~20x the XLA-CPU banded gate
    rate; falls back to 'banded' loudly without the native library);
    None picks by backend. Draws are engine-independent up to Born-prob
    rounding: mixture-channel draws use constant probabilities and are
    exactly reproducible across engines; general-Kraus (state-dependent)
    probabilities are computed by a different f32 route per engine
    (full-state norms / reduced-density trace / numpy einsum, agreeing
    to ~1e-7 relative), so a draw can differ between engines only when
    the key lands within that margin of a branch boundary.

    `observable` keeps LARGE runs statevector-free on the host: a
    callable mapping a (b, 2, 2^n) chunk of final planes to per-shot
    values (leading axis preserved); the return becomes
    (values (shots, ...), draws) and no chunk's states outlive its
    reduction — 256 shots at 24 qubits would otherwise materialize
    32 GiB of output planes."""
    n = circuit.num_qubits
    shots = int(shots)
    if shots < 1:
        raise ValueError(f"shots must be >= 1, got {shots}")
    engine = _resolve_engine(engine, n, interpret)
    bucket = _bucket_for(shots, chunk)
    fn = _compiled_traj(circuit, n, bucket, engine, interpret)

    keys = jax.random.split(key, shots)
    dispatch = fn
    if observable is not None:
        # reduce the padded bucket BEFORE the unpad slice: the
        # constant-bucket-shaped reduction is the memory contract (no
        # full planes leave the device), so the observable wraps fn
        # rather than riding _dispatch_chunk's sliced output
        def dispatch(kb, fn=fn):
            planes, draws = fn(kb)
            return observable(planes), draws
    planes_out, draws_out = [], []
    for lo in range(0, shots, bucket):
        planes, draws = _dispatch_chunk(dispatch, keys, lo, bucket)
        planes_out.append(planes)
        draws_out.append(draws)
    if len(planes_out) == 1:
        return planes_out[0], draws_out[0]
    return (jnp.concatenate(planes_out, axis=0),
            jnp.concatenate(draws_out, axis=0))


def plan_stats(circuit, shots: int) -> dict:
    """CPU-assertable batched-trajectory plan statistics (no compile,
    no chip): how many HBM sweeps one application of the noisy circuit
    costs — INDEPENDENT of the shot count, the batched engine's whole
    point (`hbm_sweeps` here equals the shots=1 plan's; the golden gate
    is scripts/check_batch_golden.py) — plus the channel mix (inlined
    BatchSelStage channels vs XLA-applied ones)."""
    from quest_tpu.ops import pallas_band as PB

    n = circuit.num_qubits
    bucket = _bucket_for(shots)   # run_batched's chunk=None cap rule
    use_kernels = PB.usable(n)
    items, channels = _traj_channels_and_items(circuit, n, use_kernels)
    if use_kernels:
        parts = PB.maybe_sweep(
            PB.segment_plan(items, n, batch=bucket), n)
        rec = PB.batched_stats(parts, shots, bucket)
    else:
        rec = {"batch": int(shots), "bucket": bucket,
               "states_per_sweep": bucket,
               "hbm_sweeps": len(items), "kernel_sweeps": 0,
               "batched_stages": 0}
    rec["channels"] = len(channels)
    rec["inline_channels"] = sum(1 for ch in channels if ch["inline"])
    rec["mixture_channels"] = sum(
        1 for ch in channels if ch["mixture_probs"] is not None)
    return rec
