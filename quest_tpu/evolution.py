"""Trotterized real- and imaginary-time evolution at sweep speed
(docs/EVOLUTION.md).

The TPU brute-force paper (arXiv:2111.10466) is ground states AND time
evolution; the stack already has both halves of the engine — the
commutation-aware diagonal pooling of the scheduler (ops/fusion.py,
docs/SCHEDULER.md) and the one-sweep Pauli-sum expectation engine
(ops/expec.py, docs/EXPECTATION.md) — but until now no dynamics
workload rode them: a Trotter step written against the eager gate API
pays one full-state pass per non-commuting term
(gates.multi_rotate_pauli's flip-form is one pass, but there are M of
them per step).

`trotter_circuit` compiles a `expec.PauliSum`-shaped Hamiltonian into a
Circuit whose per-step layer is emitted POOLING-FIRST:

  * every I/Z-only term exponentiates EXACTLY to a parity phase
    (exp(-i tau c Z..Z) = multiRotateZ(2 tau c)); the whole diagonal
    block is emitted as one contiguous run and pre-composed into
    k-qubit `ComposedDiag` groups (fusion.compose_diag_runs — the
    pooling entry for synthesized layers), which the Pallas planner
    lowers to additive MultiPhaseStage/DiagVecStage stages riding ONE
    HBM sweep;
  * off-diagonal terms partition into FRAMES — maximal families whose
    X/Y support can share one basis-rotation conjugation (U P U+ = Z
    per rotated qubit, the multi_rotate_pauli convention) — so each
    frame costs its rotation band operators ONCE for every term in it,
    and the rotated cores are again a pooled diagonal run;
  * order-2 (Strang) emission telescopes across steps: the trailing
    half-group of step s merges with the leading half-group of step
    s+1, so a k-step quench carries k-1 full interior groups, not
    2k halves.

The result: a 30q TFIM order-2 step lowers to a steady-state THREE HBM
sweeps through `compiled_fused(iters=steps)` (the band geometry floor —
one sublane-region sweep plus one per scattered 7-bit band, the same
bound QFT-30 meets at 6), versus ~2n per-term passes for the legacy
emission. `QUEST_TROTTER_FUSION=0` (keyed knob) restores the honest
per-term baseline: per-term emission, dispatched through the eager
per-term workers exactly as a user would write the loop today
(one flip-form pass per term per application).

`run_evolution` drives the workload end-to-end: chunked fused dispatch,
per-chunk energy tracking through the fused expec reduction on the
DEVICE-RESIDENT state (only the scalar expectation ever reaches the
host), imaginary-time projection with in-trace renormalization, durable
deep quenches through `resilience.durable.run_durable` (the Trotter
descriptor rides the checkpoint cursor and is validated at resume), and
sharded meshes. `trotter_ansatz` is the variational surface: dt and the
coefficient vector are RUNTIME operands of one traced program (the
ops/expec.py contract), so a VQE/QAOA optimizer loop over an evolved
ansatz — including one that REBUILDS the ansatz every iteration —
compiles zero programs after warmup (`variational.sweep`'s value-keyed
program cache; CompileAuditor-pinned in tests/test_evolution.py).

Introspection: `TrotterCircuit.plan_stats()["trotter"]` reports steps,
order, diag-group/frame counts and `hbm_sweeps_per_step` — the
STEADY-STATE marginal sweep rate ((sweeps(2m) - sweeps(m)) / m, so the
one-time boundary segment of a deep quench does not bias the per-step
figure) — CPU-assertable without a chip, gated in
scripts/check_evolution_golden.py.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from quest_tpu import precision
from quest_tpu.circuit import Circuit, GateOp
from quest_tpu.ops import apply as A
from quest_tpu.ops import expec as E
from quest_tpu.ops import fusion as F
from quest_tpu.state import Qureg

_SQ2 = 1.0 / np.sqrt(2.0)
# U P U+ = Z for P in {X, Y}: the multi_rotate_pauli basis convention
# (circuit.Circuit.multi_rotate_pauli / ref QuEST_common.c:410-447) —
# applied U ... parity ... U+, so the rotated core is a pure Z string
_TO_Z = {
    1: np.array([[_SQ2, _SQ2], [-_SQ2, _SQ2]], dtype=np.complex128),
    2: np.array([[_SQ2, -1j * _SQ2], [-1j * _SQ2, _SQ2]],
                dtype=np.complex128),
}

_NOISE_KINDS = ("depolarising", "damping", "dephasing")


def fusion_enabled() -> bool:
    """QUEST_TROTTER_FUSION (keyed, default on): pooled frame-grouped
    Trotter emission + fused-engine dispatch; 0 restores the legacy
    per-term emission, dispatched through the eager per-term workers
    (one flip-form pass per term — the honest reference baseline the
    bench A/Bs against)."""
    from quest_tpu.env import knob_value
    return knob_value("QUEST_TROTTER_FUSION")


# ---------------------------------------------------------------------------
# the Trotter plan: diagonal block + basis-rotation frames
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _Frame:
    """One basis-rotation family: `axes` maps each rotated qubit to its
    X(1)/Y(2) axis; every term in `terms` is diagonal in the rotated
    frame (its X/Y support matches `axes`, its Z dressing sits on
    unrotated qubits)."""
    axes: Tuple[Tuple[int, int], ...]
    terms: Tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class TrotterPlan:
    """Static (hashable) evolution plan: one commuting DIAGONAL group
    (I/Z-only terms), one group per FRAME, plus the all-identity terms
    (a global phase). `supports[i]` is term i's nonzero-support qubit
    tuple — the parity targets of its (possibly rotated) Z core."""
    n: int
    diag: Tuple[int, ...]
    identity: Tuple[int, ...]
    frames: Tuple[_Frame, ...]
    supports: Tuple[Tuple[int, ...], ...]

    @property
    def num_groups(self) -> int:
        return (1 if self.diag else 0) + len(self.frames)

    def group_seq(self) -> Tuple:
        """The Strang group sequence: the diagonal block first (it is
        the cheapest to repeat at the halved ends), then each frame."""
        seq: List = []
        if self.diag:
            seq.append(("diag", self.diag))
        for f in self.frames:
            seq.append(("frame", f))
        return tuple(seq)


@functools.lru_cache(maxsize=256)
def _plan_trotter(codes_key) -> TrotterPlan:
    n = len(codes_key[0]) if codes_key else 0
    diag: List[int] = []
    identity: List[int] = []
    supports: List[Tuple[int, ...]] = []
    offdiag: List[Tuple[int, Tuple[Tuple[int, int], ...],
                        Tuple[int, ...]]] = []
    for i, row in enumerate(codes_key):
        xy = tuple((q, p) for q, p in enumerate(row) if p in (1, 2))
        z = tuple(q for q, p in enumerate(row) if p == 3)
        supports.append(tuple(q for q, p in enumerate(row) if p))
        if not xy and not z:
            identity.append(i)
        elif not xy:
            diag.append(i)
        else:
            offdiag.append((i, xy, z))
    # greedy first-fit frame assignment: a term joins a frame iff its
    # X/Y axes agree with the frame's on every shared qubit, none of
    # its X/Y qubits carries another in-frame term's Z dressing, and
    # none of its Z qubits is rotated by the frame — exactly the
    # condition under which ALL the frame's cores stay diagonal in the
    # one rotated basis
    frames: List[List] = []      # [axes dict, z_blocked set, term list]
    for i, xy, z in offdiag:
        placed = False
        for fr in frames:
            axes, zb, terms = fr
            if any(axes.get(q, p) != p or q in zb for q, p in xy):
                continue
            if any(q in axes for q in z):
                continue
            axes.update(xy)
            zb.update(z)
            terms.append(i)
            placed = True
            break
        if not placed:
            frames.append([dict(xy), set(z), [i]])
    return TrotterPlan(
        n=n, diag=tuple(diag), identity=tuple(identity),
        frames=tuple(_Frame(tuple(sorted(a.items())), tuple(t))
                     for a, _, t in frames),
        supports=tuple(supports))


def as_pauli_sum(hamiltonian, coeffs=None, num_qubits: int = None
                 ) -> E.PauliSum:
    """Normalize the Hamiltonian argument every evolution entry point
    accepts — an `expec.PauliSum`, a (codes, coeffs) pair, or a codes
    array with `coeffs=` — into one validated PauliSum spec."""
    if isinstance(hamiltonian, E.PauliSum):
        if coeffs is not None:
            raise ValueError("pass coefficients inside the PauliSum, "
                             "not as a separate coeffs= argument")
        return hamiltonian
    if coeffs is None and isinstance(hamiltonian, tuple) \
            and len(hamiltonian) == 2:
        hamiltonian, coeffs = hamiltonian
    codes = np.asarray(hamiltonian)
    if num_qubits is None:
        if codes.ndim != 2:
            raise ValueError(
                "pass num_qubits= (or a 2-D codes array) so the term "
                "width is unambiguous")
        num_qubits = int(codes.shape[1])
    return E.PauliSum.of(codes, coeffs, num_qubits)


# ---------------------------------------------------------------------------
# circuit emission
# ---------------------------------------------------------------------------


class TrotterCircuit(Circuit):
    """A Circuit compiled from a Hamiltonian by `trotter_circuit`.
    Carries its Trotter descriptor and extends `plan_stats()` with the
    "trotter" record (steps, order, group counts, and the steady-state
    `hbm_sweeps_per_step` — the CI-gated sweep-speed metric). Treat it
    as IMMUTABLE: equal (hamiltonian, dt, order, steps, noise) calls
    return the same memoized instance, so serve requests over equal
    evolution jobs share one program family (circuit.program_key keys
    on object identity)."""

    trotter: dict

    def plan_stats(self, density: bool = False, batch: int = None,
                   devices: int = None) -> dict:
        # a noisy circuit only runs as a Circuit on the density
        # register (the trajectory path unravels it and reports
        # through trajectories.plan_stats), so plan it there
        density = density or self.trotter["noise"] is not None
        rec = super().plan_stats(density=density, batch=batch,
                                 devices=devices)
        # report THIS circuit's emission (the memoized `pooled` bit),
        # not whatever the knob reads now — a knob flip after build
        # changes what the NEXT trotter_circuit call emits, never what
        # this one dispatches
        rec["trotter"] = trotter_plan_stats(
            self.trotter["spec"], self.trotter["dt"],
            order=self.trotter["order"], steps=self.trotter["steps"],
            density=density, pooled=self.trotter["pooled"],
            noise=self.trotter["noise"])
        return rec

    def _plan_extra(self, density: bool) -> dict:
        # the plan IR's subsystem-extension hook (quest_tpu/plan.py):
        # autotuned TrotterCircuit plans carry the frame record too
        density = density or self.trotter["noise"] is not None
        return {"trotter": trotter_plan_stats(
            self.trotter["spec"], self.trotter["dt"],
            order=self.trotter["order"], steps=self.trotter["steps"],
            density=density, pooled=self.trotter["pooled"],
            noise=self.trotter["noise"])}


def _zy_angle(coef: float, tau: float, scale: float) -> float:
    # exp(-i tau c P) == exp(-i angle/2 P) at angle = 2 tau c
    return 2.0 * float(coef) * float(tau) * float(scale)


def _emit_group(c: Circuit, plan: TrotterPlan, spec: E.PauliSum,
                group, tau: float, scale: float, pooled: bool) -> None:
    kind, payload = group
    if kind == "diag":
        ops = [GateOp("parity", plan.supports[i], (), (),
                      _zy_angle(spec.coeffs[i], tau, scale))
               for i in payload]
        if pooled:
            ops = F.compose_diag_runs(ops)
        c.ops.extend(ops)
        return
    frame: _Frame = payload
    for q, ax in frame.axes:
        c.gate(_TO_Z[ax], (q,))
    ops = [GateOp("parity", plan.supports[i], (), (),
                  _zy_angle(spec.coeffs[i], tau, scale))
           for i in frame.terms]
    if pooled:
        ops = F.compose_diag_runs(ops)
    c.ops.extend(ops)
    for q, ax in frame.axes:
        c.gate(np.asarray(_TO_Z[ax]).conj().T, (q,))


def _emit_identity_phase(c: Circuit, theta: float) -> None:
    """The all-identity terms' global phase exp(-i theta), as a uniform
    single-qubit diagonal (diagonal-class: pools/fuses like any other
    phase; its density dual conjugates away, as a global phase must)."""
    if abs(theta) < 1e-300 or c.num_qubits == 0:
        return
    p = np.exp(-1j * theta)
    c._add("diagonal", (0,), np.array([p, p], dtype=np.complex128))


def _emit_noise(c: Circuit, noise) -> None:
    kind, prob = noise
    for q in range(c.num_qubits):
        getattr(c, kind)(q, prob)


def _emit_trotter(c: Circuit, plan: TrotterPlan, spec: E.PauliSum,
                  dt: float, order: int, steps: int, noise,
                  pooled: bool) -> None:
    seq = plan.group_seq()
    m = len(seq)
    telescope = pooled and noise is None and order == 2 and m > 1
    for s in range(steps):
        if m:
            if order == 1 or m == 1:
                for g in seq:
                    _emit_group(c, plan, spec, g, dt, 1.0, pooled)
            elif telescope:
                # Strang with the leading half-group merged into the
                # previous step's trailing one: G1 appears at full
                # weight between interior steps, half at the ends
                if s == 0:
                    _emit_group(c, plan, spec, seq[0], dt, 0.5, pooled)
                for g in seq[1:-1]:
                    _emit_group(c, plan, spec, g, dt, 0.5, pooled)
                _emit_group(c, plan, spec, seq[-1], dt, 1.0, pooled)
                for g in reversed(seq[1:-1]):
                    _emit_group(c, plan, spec, g, dt, 0.5, pooled)
                _emit_group(c, plan, spec, seq[0], dt,
                            0.5 if s == steps - 1 else 1.0, pooled)
            else:
                _emit_group(c, plan, spec, seq[0], dt, 0.5, pooled)
                for g in seq[1:-1]:
                    _emit_group(c, plan, spec, g, dt, 0.5, pooled)
                _emit_group(c, plan, spec, seq[-1], dt, 1.0, pooled)
                for g in reversed(seq[1:-1]):
                    _emit_group(c, plan, spec, g, dt, 0.5, pooled)
                _emit_group(c, plan, spec, seq[0], dt, 0.5, pooled)
        if noise is not None:
            _emit_noise(c, noise)
    if plan.identity and pooled:
        # legacy per-term emission drops the global phase, exactly like
        # the reference's all-identity multiRotatePauli no-op
        theta = float(dt) * float(steps) * sum(
            float(spec.coeffs[i]) for i in plan.identity)
        _emit_identity_phase(c, theta)
    c._compiled.clear()


@functools.lru_cache(maxsize=64)
def _trotter_circuit_cached(spec: E.PauliSum, dt: float, order: int,
                            steps: int, noise, pooled: bool
                            ) -> TrotterCircuit:
    plan = _plan_trotter(spec.codes)
    c = TrotterCircuit(spec.num_qubits)
    c.trotter = {"spec": spec, "dt": dt, "order": order, "steps": steps,
                 "noise": noise, "pooled": pooled, "plan": plan}
    _emit_trotter(c, plan, spec, dt, order, steps, noise, pooled)
    return c


def trotter_circuit(hamiltonian, dt, *, coeffs=None, num_qubits=None,
                    order: int = 2, steps: int = 1,
                    noise=None) -> TrotterCircuit:
    """Compile exp(-i dt H)^steps into a Circuit via the order-1 (Lie)
    or order-2 (Strang) product formula over the plan's commuting
    groups (diagonal block + basis-rotation frames). With
    QUEST_TROTTER_FUSION=1 (default) the emission is pooled — composed
    diagonal groups, shared frame rotations, telescoped Strang halves —
    so the fused engine runs a step in a few HBM sweeps; with 0 it is
    the legacy per-term stream. `noise=(kind, prob)` with kind in
    {depolarising, damping, dephasing} appends the per-qubit channel
    after every step (the trajectory path: run the returned circuit
    through `trajectories.run_batched` or
    `run_evolution_trajectories`).

    Memoized BY VALUE: equal arguments return the SAME TrotterCircuit,
    so repeated serve submissions of one evolution job coalesce into
    one program family, and rebuilt-but-equal circuits hit every
    compiled-program cache. Treat the returned circuit as immutable."""
    spec = as_pauli_sum(hamiltonian, coeffs, num_qubits)
    if order not in (1, 2):
        raise ValueError(f"order must be 1 or 2, got {order!r}")
    steps = int(steps)
    if steps < 1:
        raise ValueError(f"steps must be >= 1, got {steps}")
    if noise is not None:
        kind, prob = noise
        if kind not in _NOISE_KINDS:
            raise ValueError(
                f"noise kind must be one of {_NOISE_KINDS}, got {kind!r}")
        noise = (kind, float(prob))
    return _trotter_circuit_cached(spec, float(dt), order, steps, noise,
                                   fusion_enabled())


# ---------------------------------------------------------------------------
# plan introspection (CPU-assertable — the Circuit.plan_stats discipline)
# ---------------------------------------------------------------------------


def _fused_sweeps(circ: Circuit, n: int, density: bool) -> int:
    """HBM passes one application of `circ` costs on the engine that
    would actually run it (fused kernel sweeps on the kernel tier,
    banded full-state passes below it) — pure host planning."""
    from quest_tpu.ops import pallas_band as PB
    flat = circ._planned_flat(n, density)
    if PB.usable(n):
        items = F.plan(flat, n, bands=PB.plan_bands(n))
        return len(PB.maybe_sweep(PB.segment_plan(items, n), n))
    return F.plan_stats(F.plan(flat, n))["full_state_passes"]


def _per_term_passes(plan: TrotterPlan, order: int) -> int:
    """The legacy model: one flip-form pass per term application per
    step (gates.multi_rotate_z / multi_rotate_pauli — what the eager
    per-term loop dispatches; all-identity terms are no-ops, exactly
    like the reference)."""
    applied = len(plan.diag) + sum(len(f.terms) for f in plan.frames)
    if order == 1:
        return applied
    # Strang applies the first group's terms twice (half steps), the
    # last once, interior groups twice
    seq = plan.group_seq()
    if len(seq) <= 1:
        return applied
    total = 0
    for gi, g in enumerate(seq):
        cnt = (len(g[1]) if g[0] == "diag" else len(g[1].terms))
        total += cnt if gi == len(seq) - 1 else 2 * cnt
    return total


def _diag_group_count(plan: TrotterPlan) -> int:
    """Composed-diagonal groups one pooled step emits (the diag block's
    groups plus each frame's rotated core groups)."""
    count = 0
    for kind, payload in plan.group_seq():
        idx = payload if kind == "diag" else payload.terms
        ops = [GateOp("parity", plan.supports[i], (), (), 0.0)
               for i in idx]
        count += len(F.compose_diag_runs(ops))
    return count


def trotter_plan_stats(hamiltonian, dt, *, coeffs=None, num_qubits=None,
                       order: int = 2, steps: int = 1,
                       density: bool = False,
                       pooled: bool = None, noise=None) -> dict:
    """The "trotter" plan record, CPU-side (no compile, no chip):
    term/group/frame counts, the pooled emission's STEADY-STATE
    `hbm_sweeps_per_step` — the marginal rate (sweeps(2m) - sweeps(m))/m
    over the fused engine's sweep plan, so a deep quench's one-time
    boundary segment does not bias the per-step figure — and the legacy
    per-term model `baseline_hbm_sweeps_per_step` (one flip-form pass
    per term application). With QUEST_TROTTER_FUSION=0
    `hbm_sweeps_per_step` REPORTS the baseline: that is what the legacy
    dispatch runs (the expec.plan_stats convention), and the record is
    what scripts/check_evolution_golden.py pins against the fused one.
    `pooled` overrides the knob read — TrotterCircuit.plan_stats passes
    the emission its circuit was actually built with, and its `noise`:
    a noisy step disables Strang telescoping and interleaves per-qubit
    channels, so the marginal is measured over the NOISY emission —
    planned on the density register, the one register kind that runs
    channels as a Circuit (the trajectory path unravels instead and
    reports through trajectories.plan_stats)."""
    spec = as_pauli_sum(hamiltonian, coeffs, num_qubits)
    plan = _plan_trotter(spec.codes)
    fused = fusion_enabled() if pooled is None else bool(pooled)
    baseline = _per_term_passes(plan, order)
    plan_density = density or noise is not None
    n = 2 * spec.num_qubits if plan_density else spec.num_qubits
    if fused:
        m = 4
        c1 = _trotter_circuit_cached(spec, float(dt), order, m, noise,
                                     True)
        c2 = _trotter_circuit_cached(spec, float(dt), order, 2 * m,
                                     noise, True)
        marginal = (_fused_sweeps(c2, n, plan_density)
                    - _fused_sweeps(c1, n, plan_density)) / m
        sweeps_per_step = marginal
    else:
        sweeps_per_step = float(baseline)
    return {
        "steps": int(steps),
        "order": int(order),
        "terms": len(spec.codes),
        "diag_terms": len(plan.diag),
        "identity_terms": len(plan.identity),
        "frames": len(plan.frames),
        "diag_groups": _diag_group_count(plan),
        "fusion": bool(fused),
        "noise": noise,
        "hbm_sweeps_per_step": sweeps_per_step,
        "baseline_hbm_sweeps_per_step": baseline,
    }


# ---------------------------------------------------------------------------
# the traced core: runtime coefficients + dt (the variational surface)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=256)
def _frame_band_ops(axes: Tuple[Tuple[int, int], ...], n: int):
    """Per-band composed rotation operators of one frame (and their
    inverses), as concrete numpy pairs for ops/apply.apply_band —
    ceil(width/7) MXU passes per frame side instead of one per rotated
    qubit."""
    by_band: Dict[int, np.ndarray] = {}
    for q, ax in axes:
        b = F._band_of(q)
        ql, w = F.band_range(n, b)
        emb = F.embed_operator(_TO_Z[ax], [q - ql], [], [], w)
        cur = by_band.get(b)
        by_band[b] = emb if cur is None else emb @ cur
    out = []
    for b in sorted(by_band):
        ql, w = F.band_range(n, b)
        op = by_band[b]
        inv = op.conj().T
        out.append((ql, w, (op.real.copy(), op.imag.copy()),
                    (inv.real.copy(), inv.imag.copy())))
    return tuple(out)


def _parity_decay(amps, n: int, targets, w):
    """Imaginary-time diagonal factor exp(-w * s(j)) with s the parity
    sign of `targets` — the non-unitary counterpart of
    apply_parity_phase, elementwise over the same split view."""
    targets = tuple(int(t) for t in targets)
    dims, axis_of = A._split_view(n, targets, ())
    re = amps[0].reshape(dims)
    im = amps[1].reshape(dims)
    sign = A.parity_sign(len(dims), axis_of, targets, amps.dtype)
    f = jnp.exp(-jnp.asarray(w, amps.dtype) * sign)
    return jnp.stack([(re * f).reshape(-1), (im * f).reshape(-1)])


def _global_phase(amps, theta):
    """exp(-i theta) on the whole register (the identity terms)."""
    t = jnp.asarray(theta, amps.dtype)
    c, s = jnp.cos(t), jnp.sin(t)
    return jnp.stack([amps[0] * c + amps[1] * s,
                      amps[1] * c - amps[0] * s])


def _apply_group_traced(amps, n, cf, tau, plan: TrotterPlan, group,
                        scale: float, imag: bool):
    kind, payload = group
    if kind == "diag":
        for i in payload:
            w = cf[i] * tau * scale
            if imag:
                amps = _parity_decay(amps, n, plan.supports[i], w)
            else:
                amps = A.apply_parity_phase(amps, n, plan.supports[i],
                                            2.0 * w)
        return amps
    frame: _Frame = payload
    bands = _frame_band_ops(frame.axes, n)
    for ql, w_, fwd, _inv in bands:
        amps = A.apply_band(amps, n, fwd, ql, w_, ())
    for i in frame.terms:
        w = cf[i] * tau * scale
        if imag:
            amps = _parity_decay(amps, n, plan.supports[i], w)
        else:
            amps = A.apply_parity_phase(amps, n, plan.supports[i],
                                        2.0 * w)
    for ql, w_, _fwd, inv in bands:
        amps = A.apply_band(amps, n, inv, ql, w_, ())
    return amps


def step_schedule(plan: TrotterPlan, order: int):
    """The per-step (group, scale) splitting schedule: order 1 applies
    each group once; order 2 is the symmetric Strang arrangement with
    halved end groups. The ONE place the splitting lives — shared by
    the traced step below and by the adjoint engine
    (quest_tpu/adjoint.py), which replays the identical schedule
    gate-by-gate so its gradients differentiate exactly the program
    `evolve_planes` runs."""
    seq = plan.group_seq()
    if order == 1 or len(seq) <= 1:
        return tuple((g, 1.0) for g in seq)
    return tuple([(seq[0], 0.5)] + [(g, 0.5) for g in seq[1:-1]]
                 + [(seq[-1], 1.0)]
                 + [(g, 0.5) for g in reversed(seq[1:-1])]
                 + [(seq[0], 0.5)])


def _step_traced(amps, n, cf, tau, plan: TrotterPlan, order: int,
                 imag: bool, renorm: bool):
    for g, scale in step_schedule(plan, order):
        amps = _apply_group_traced(amps, n, cf, tau, plan, g, scale,
                                   imag)
    if plan.identity:
        tot = sum(cf[i] for i in plan.identity) * tau
        if imag:
            amps = amps * jnp.exp(-jnp.asarray(tot, amps.dtype))
        else:
            amps = _global_phase(amps, tot)
    if renorm:
        acc = precision.accum_dtype(amps.dtype)
        norm = jnp.sqrt(jnp.sum(amps.astype(acc) ** 2))
        amps = amps / jnp.maximum(norm, 1e-300).astype(amps.dtype)
    return amps


def evolve_planes(amps, n: int, coeffs, dt, plan: TrotterPlan, *,
                  steps: int = 1, order: int = 2,
                  imag_time: bool = False, renorm: bool = None):
    """The traced evolution core: `steps` Trotter steps over (2, 2^n)
    statevector planes with the COEFFICIENT VECTOR and dt as runtime
    operands — the plan (term structure) is the only static input, so
    an optimizer loop changing either retraces nothing, and `jax.grad`
    flows through every op (parity phases, band rotations, the
    imaginary-time decays and renormalization are all plain jnp).
    `renorm` defaults to `imag_time` (projection needs it; real time is
    unitary)."""
    cf = jnp.asarray(coeffs, amps.dtype)
    tau = jnp.asarray(dt, amps.dtype)
    renorm = imag_time if renorm is None else renorm
    for _ in range(int(steps)):
        amps = _step_traced(amps, n, cf, tau, plan, order, imag_time,
                            renorm)
    return amps


def trotter_ansatz(hamiltonian, *, num_qubits: int = None,
                   order: int = 2, steps: int = 1,
                   imag_time: bool = False) -> Callable:
    """Ansatz over the EVOLVED state for `variational.expectation`:
    returns `ansatz(amps, params)` with params = (coeffs, dt) — both
    runtime operands of one traced program. `hamiltonian` supplies the
    term STRUCTURE only (a PauliSum's coefficients are ignored here;
    the optimizer owns them through params). The returned callable
    carries `program_key`, the value identity `variational.expectation`
    and `variational.sweep` key their program caches on — a rebuilt
    ansatz with equal arguments hits the warm compiled program instead
    of retracing (the zero-retrace optimizer-loop contract, pinned in
    tests/test_evolution.py)."""
    if isinstance(hamiltonian, E.PauliSum):
        codes_key = hamiltonian.codes
        n = hamiltonian.num_qubits
    else:
        codes = np.asarray(hamiltonian)
        n = int(codes.shape[1]) if num_qubits is None else int(num_qubits)
        codes_key = E.parse_pauli_sum(codes, n)
    if order not in (1, 2):
        raise ValueError(f"order must be 1 or 2, got {order!r}")
    plan = _plan_trotter(codes_key)

    def ansatz(amps, params):
        coeffs, dt = params
        return evolve_planes(amps, n, coeffs, dt, plan, steps=steps,
                             order=order, imag_time=imag_time)

    ansatz.program_key = ("trotter_ansatz", codes_key, n, order,
                          int(steps), bool(imag_time))
    ansatz.num_qubits = n
    return ansatz


@functools.partial(jax.jit,
                   static_argnames=("n", "plan", "order", "chunk",
                                    "imag", "renorm"))
def _chunk_traced(amps, coeffs, dt, *, n, plan, order, chunk, imag,
                  renorm):
    def body(_, a):
        return _step_traced(a, n, coeffs, dt, plan, order, imag, renorm)
    return jax.lax.fori_loop(0, chunk, body, amps)


# ---------------------------------------------------------------------------
# run_evolution: the workload driver
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EvolutionResult:
    """What a quench returns: the final register, the energy track —
    `energies[k, j]` is observable j at step `energy_steps[k]`
    (row 0 is the initial state) — and the run's stats record."""
    state: Qureg
    energies: np.ndarray
    energy_steps: np.ndarray
    stats: dict


def _observable_plans(observables, spec, nq: int):
    specs = []
    for obs in observables:
        o = as_pauli_sum(obs, num_qubits=nq)
        if o.num_qubits != nq:
            raise ValueError(
                f"observable is over {o.num_qubits} qubits but the "
                f"evolution register has {nq}")
        specs.append(o)
    return specs


def _measure_energies(q: Qureg, amps, specs) -> List[float]:
    """Fused expec reductions on the DEVICE-RESIDENT planes: only the
    scalar expectations reach the host (calculations dispatches to the
    grouped engine, sharded registers take the per-shard psum path)."""
    from quest_tpu import calculations as C
    qq = q.replace_amps(amps)
    return [C.calc_expec_pauli_sum(qq, np.asarray(o.codes),
                                   np.asarray(o.coeffs)) for o in specs]


def _legacy_step(q: Qureg, plan: TrotterPlan, spec: E.PauliSum,
                 dt: float, order: int) -> Qureg:
    """One legacy per-term step through the EAGER workers — what a user
    writes against the gate API today: one flip-form full-state pass
    per term application (gates.multi_rotate_pauli), no pooling, no
    frames. The honest baseline QUEST_TROTTER_FUSION=0 restores."""
    from quest_tpu.ops import gates as G

    def apply_terms(q, idx, scale):
        for i in idx:
            row = spec.codes[i]
            targets = plan.supports[i]
            paulis = tuple(row[t] for t in targets)
            q = G.multi_rotate_pauli(
                q, targets, paulis,
                _zy_angle(spec.coeffs[i], dt, scale))
        return q

    seq = plan.group_seq()
    groups = [(g[1] if g[0] == "diag" else g[1].terms) for g in seq]
    if order == 1 or len(groups) <= 1:
        for idx in groups:
            q = apply_terms(q, idx, 1.0)
        return q
    for idx in groups[:-1]:
        q = apply_terms(q, idx, 0.5)
    q = apply_terms(q, groups[-1], 1.0)
    for idx in reversed(groups[:-1]):
        q = apply_terms(q, idx, 0.5)
    return q


def run_evolution(hamiltonian, dt, steps: int, *, state: Qureg,
                  coeffs=None, order: int = 2, observables=None,
                  energy_every: int = None, imag_time: bool = False,
                  engine: str = None, mesh=None, interpret: bool = False,
                  durable_dir: str = None, durable_every: int = None
                  ) -> EvolutionResult:
    """Run a `steps`-step Trotter quench of `state` under `hamiltonian`
    end-to-end (docs/EVOLUTION.md):

      * REAL TIME (default): the pooled circuit dispatches through the
        fused engine in chunks of `energy_every` steps
        (`compiled_fused(iters=...)` — sweep fusion merges across the
        unrolled steps), recording every observable in `observables`
        (PauliSum specs; default [hamiltonian]) through the fused expec
        reduction on the device-resident state after each chunk — no
        host round-trip per step, only scalars land.
      * IMAGINARY TIME (`imag_time=True`): exp(-dt H) steps with
        in-trace renormalization after every step — ground-state
        projection; runs the traced core under one jit per chunk
        (coefficients and dt stay runtime operands).
      * DURABLE (`durable_dir=`): the whole quench rides
        `resilience.durable.run_durable` — checkpoints at the engine's
        launch boundaries every `durable_every` (default
        QUEST_DURABLE_EVERY) with the Trotter descriptor validated in
        the cursor; a preempted quench resumes BIT-IDENTICAL to an
        uninterrupted one (tests/test_evolution.py). Incompatible with
        `energy_every` (the planes are the resume payload; observables
        evaluate on the final state).
      * `mesh=` runs the sharded engines (energy via the per-shard
        psum path); `engine` pins 'fused'/'banded' like run_durable.

    With QUEST_TROTTER_FUSION=0 the run is the honest legacy baseline:
    per-term eager dispatch, one flip-form pass per term application —
    the A/B the bench's evolution scenario measures."""
    spec = as_pauli_sum(hamiltonian, coeffs, num_qubits=None)
    if state.num_qubits != spec.num_qubits:
        raise ValueError(
            f"Hamiltonian is over {spec.num_qubits} qubits but the "
            f"register has {state.num_qubits}")
    steps = int(steps)
    if steps < 1:
        raise ValueError(f"steps must be >= 1, got {steps}")
    if order not in (1, 2):
        raise ValueError(f"order must be 1 or 2, got {order!r}")
    plan = _plan_trotter(spec.codes)
    nq = spec.num_qubits
    n = state.num_state_qubits
    density = state.is_density
    fused = fusion_enabled()
    if observables is None:
        observables = [spec]
    specs = _observable_plans(observables, spec, nq)

    if durable_dir is not None:
        if energy_every is not None:
            raise ValueError(
                "durable_dir= is incompatible with energy_every=: the "
                "durable executor owns the step loop and the planes are "
                "the resume payload; observables evaluate on the final "
                "state (docs/EVOLUTION.md)")
        if imag_time:
            raise ValueError(
                "durable imaginary-time evolution is not supported: "
                "the renormalizing step is not a Circuit the durable "
                "executor can cut (docs/EVOLUTION.md)")
        from quest_tpu.resilience.durable import run_durable
        circ = trotter_circuit(spec, dt, order=order, steps=steps)
        # the EvolutionResult contract (row 0 = initial state) holds on
        # the durable path too: measure before dispatch, final after
        initial = _measure_energies(state, state.amps, specs)
        out = run_durable(
            circ, state, durable_dir, every=durable_every,
            engine=engine, mesh=mesh, interpret=interpret,
            cursor_extra={
                "workload": "trotter",
                "trotter_steps": steps,
                "trotter_order": order,
                "trotter_dt": repr(float(dt)),
                "trotter_terms": len(spec.codes),
            })
        energies = np.asarray([initial,
                               _measure_energies(out, out.amps, specs)])
        return EvolutionResult(
            state=out, energies=energies,
            energy_steps=np.asarray([0, steps]),
            stats={"engine": "durable", "steps": steps, "order": order})

    chunk = steps if energy_every is None else int(energy_every)
    if chunk < 1:
        raise ValueError(f"energy_every must be >= 1, got {chunk}")
    record: List[List[float]] = [_measure_energies(state, state.amps,
                                                   specs)]
    rec_steps = [0]
    dispatches = 0

    if imag_time:
        if mesh is not None or density:
            raise ValueError(
                "imaginary-time evolution runs on single-mesh "
                "statevector registers (docs/EVOLUTION.md)")
        if engine is not None:
            raise ValueError(
                "imaginary-time evolution has no engine= choice: the "
                "renormalizing step runs as one traced XLA program "
                "(docs/EVOLUTION.md)")
        amps = state.amps.reshape(2, -1)
        cf = jnp.asarray(np.asarray(spec.coeffs), amps.dtype)
        tau = jnp.asarray(float(dt), amps.dtype)
        done = 0
        while done < steps:
            m = min(chunk, steps - done)
            amps = _chunk_traced(amps, cf, tau, n=n, plan=plan,
                                 order=order, chunk=m, imag=True,
                                 renorm=True)
            dispatches += 1
            done += m
            record.append(_measure_energies(state, amps, specs))
            rec_steps.append(done)
        q = state.replace_amps(amps)
        return EvolutionResult(
            state=q, energies=np.asarray(record),
            energy_steps=np.asarray(rec_steps),
            stats={"engine": "traced-imag", "steps": steps,
                   "order": order, "dispatches": dispatches})

    if not fused:
        if mesh is not None or engine is not None:
            raise ValueError(
                "QUEST_TROTTER_FUSION=0 runs the legacy per-term EAGER "
                "baseline on a single device — mesh= and engine= have "
                "no legacy counterpart; unset the knob for sharded or "
                "engine-pinned evolution (docs/EVOLUTION.md)")
        q = state
        done = 0
        while done < steps:
            m = min(chunk, steps - done)
            for _ in range(m):
                q = _legacy_step(q, plan, spec, float(dt), order)
            done += m
            dispatches += m
            record.append(_measure_energies(q, q.amps, specs))
            rec_steps.append(done)
        return EvolutionResult(
            state=q, energies=np.asarray(record),
            energy_steps=np.asarray(rec_steps),
            stats={"engine": "legacy-per-term", "steps": steps,
                   "order": order, "dispatches": dispatches})

    circ = trotter_circuit(spec, dt, order=order, steps=1)
    if engine not in (None, "fused", "banded"):
        raise ValueError(
            f"engine must be None, 'fused' or 'banded', got {engine!r}")
    if engine is None and mesh is None:
        # auto-resolve like the bench ladder: the Pallas fused engine
        # needs a kernel-tier f32 register AND a kernel-capable backend
        # (CPU runs Pallas only under interpret=True); everything else
        # rides the banded XLA program — same math, full-state passes
        from quest_tpu.ops import pallas_band as PB
        kernel_ok = (jax.devices()[0].platform == "tpu"
                     or interpret)
        if not (PB.usable(n) and state.amps.dtype == jnp.float32
                and kernel_ok):
            engine = "banded"

    def compiled_for(m: int):
        if mesh is not None:
            # engine= pins the per-shard engine exactly like run_durable:
            # 'fused' = the Pallas sharded kernel path, None/'banded' =
            # the shard_map banded XLA program (the CPU-safe default)
            if engine == "fused":
                inner = circ.compiled_sharded_fused(
                    n, density, mesh, donate=True, interpret=interpret)
            else:
                inner = circ.compiled_sharded_banded(n, density, mesh,
                                                     donate=True)

            def run(a, inner=inner, m=m):
                for _ in range(m):
                    a = inner(a)
                return a
            return run
        if engine == "banded":
            return circ.compiled_banded(n, density, donate=True,
                                        iters=m)
        return circ.compiled_fused(n, density, donate=True,
                                   interpret=interpret, iters=m)

    # fresh device buffer: the chunk programs donate their input, and
    # donating the CALLER's planes would delete the register they still
    # hold (state.clone's buffer-aliasing rule)
    from quest_tpu.state import _device_copy
    amps = _device_copy(state.amps)
    if mesh is not None:
        from quest_tpu.parallel.mesh import amp_sharding
        amps = jax.device_put(amps, amp_sharding(mesh))
    fns: Dict[int, Callable] = {}
    done = 0
    while done < steps:
        m = min(chunk, steps - done)
        fn = fns.get(m)
        if fn is None:
            fn = fns[m] = compiled_for(m)
        amps = fn(amps)
        dispatches += 1
        done += m
        record.append(_measure_energies(state, amps, specs))
        rec_steps.append(done)
    q = state.replace_amps(amps)
    return EvolutionResult(
        state=q, energies=np.asarray(record),
        energy_steps=np.asarray(rec_steps),
        stats={"engine": (f"sharded-{engine or 'banded'}"
                          if mesh is not None else engine or "fused"),
               "steps": steps, "order": order,
               "dispatches": dispatches})


def run_evolution_trajectories(hamiltonian, dt, steps: int, shots: int,
                               *, noise, key=None, coeffs=None,
                               order: int = 2, observable=None,
                               engine: str = None,
                               interpret: bool = False,
                               chunk: int = None,
                               durable_dir: str = None,
                               durable_every: int = None):
    """Noisy Trotter evolution through the EXISTING channel path:
    builds the per-step-noise circuit (`trotter_circuit(noise=)`) and
    unravels `shots` stochastic trajectories through
    `trajectories.run_batched` — or, with `durable_dir=`, through the
    durable trajectory executor (checkpointed shot chunks, resume
    bit-identical). Returns (planes, draws) exactly like run_batched;
    `observable=` accepts a PauliSum and reduces per shot on device."""
    spec = as_pauli_sum(hamiltonian, coeffs, num_qubits=None)
    circ = trotter_circuit(spec, dt, order=order, steps=steps,
                           noise=noise)
    if key is None:
        key = jax.random.key(0)
    if observable is not None and not callable(observable):
        observable = E.resolve_observable(observable, spec.num_qubits)
    if durable_dir is not None:
        if observable is not None:
            raise ValueError(
                "durable_dir= is incompatible with observable=: the "
                "planes are the resume payload (docs/RESILIENCE.md)")
        from quest_tpu.resilience.durable import run_durable_trajectories
        return run_durable_trajectories(
            circ, key, shots, durable_dir, every=durable_every,
            chunk=chunk, engine=engine, interpret=interpret)
    from quest_tpu import trajectories as T
    return T.run_batched(circ, key, shots, engine=engine,
                         interpret=interpret, chunk=chunk,
                         observable=observable)
