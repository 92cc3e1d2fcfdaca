"""Circuit abstraction: trace a whole gate sequence into ONE XLA program.

The reference dispatches each gate eagerly into a fresh kernel launch
(QuEST.c validate->dispatch per call). On TPU the idiomatic — and much
faster — shape is to trace the entire circuit under one jit so XLA fuses
adjacent elementwise/diagonal gates, keeps the state resident in HBM/VMEM,
and (with donation) updates it in place. This is a genuine capability the
reference architecture cannot express, and the main single-chip perf lever
(SURVEY.md section 7 step 8).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from quest_tpu import cplx
from quest_tpu import precision
from quest_tpu import profiling
from quest_tpu.ops import apply as A
from quest_tpu.ops import matrices as M
from quest_tpu.state import Qureg


@dataclasses.dataclass(frozen=True)
class GateOp:
    kind: str                 # 'matrix' | 'diagonal' | 'parity' | 'allones' | 'superop'
    targets: Tuple[int, ...]
    controls: Tuple[int, ...] = ()
    cstates: Tuple[int, ...] = ()
    operand: object = None    # matrix / diag vector / angle / phase term
    meta: object = None       # side-channel the engines may read but never
    # execute from: Circuit.kraus stores ("kraus", <raw operator tuple>)
    # so the trajectory unraveling (trajectories.run_batched) can recover
    # the channel's Kraus decomposition from the superoperator op


# op-count threshold above which the PER-GATE XLA engine (Circuit.apply
# / compiled / trace — one HLO op chain per gate) warns about its
# compile time: XLA-CPU compile of a ~100-op per-gate program measured
# PATHOLOGICALLY slow (minutes; observed on the PR-13 evolution
# circuits — the banded/fused engines compile the same circuit in
# seconds because band composition collapses the chain). 64 keeps the
# oracle path quiet for the small fuzz circuits the tests trace while
# catching every real workload-sized circuit.
PERGATE_COMPILE_WARN_OPS = 64

_pergate_warned = False


def _warn_pergate_compile_once(num_ops: int) -> None:
    """Once-per-process stderr nudge toward the fusing engines: the
    per-gate path is the semantic ORACLE, not the way to run a deep
    circuit (docs/SCHEDULER.md)."""
    global _pergate_warned
    if _pergate_warned:
        return
    _pergate_warned = True
    import sys
    print(f"[quest_tpu.circuit] compiling a {num_ops}-op circuit "
          f"through the PER-GATE XLA engine (Circuit.apply/compiled): "
          f"XLA compile time grows pathologically with per-gate op "
          f"chains (minutes at ~100 ops on XLA-CPU). Use "
          f"Circuit.apply_banded or compiled_fused — the fusing "
          f"engines compose the same circuit into band passes and "
          f"compile in seconds (threshold: "
          f"PERGATE_COMPILE_WARN_OPS={PERGATE_COMPILE_WARN_OPS}; "
          f"warned once per process)", file=sys.stderr, flush=True)


def dual_of(op: GateOp, shift: int):
    """The column-space dual of a gate on a density register: conjugated
    operand on targets/controls shifted by N (ref QuEST.c:8-10). The ONE
    place the dual rules live — used by the XLA path, the fused-engine
    expansion, and anything else that flattens density circuits.
    Superoperators already act on both spaces: no dual (returns None);
    measurements handle the density register directly (no dual)."""
    if op.kind in ("superop", "measure", "measure_dm", "classical"):
        return None
    if op.kind == "parity":
        return dataclasses.replace(
            op, targets=tuple(t + shift for t in op.targets),
            operand=-op.operand)
    parts = getattr(op, "parts", None)
    if parts:
        # a scheduler-shaped ComposedDiag (fusion.ComposedDiag) carries
        # its phase components in `parts` alongside the composed table;
        # the dual must conjugate BOTH representations — negating each
        # part's angle is exactly the conjugate of its phase factor —
        # or the Pallas MultiPhaseStage lowering (which reads parts)
        # would disagree with the conjugated operand
        return dataclasses.replace(
            op, targets=tuple(t + shift for t in op.targets),
            controls=tuple(c + shift for c in op.controls),
            operand=np.conj(op.operand),
            parts=tuple((kind, bits, -ang) for kind, bits, ang in parts))
    return dataclasses.replace(
        op, targets=tuple(t + shift for t in op.targets),
        controls=tuple(c + shift for c in op.controls),
        operand=np.conj(op.operand))


_LOOP_UNROLL_MAX = 32


def _engine_mode_key():
    """The trace-time mode flags every compiled-program cache key must
    carry, DERIVED from the knob registry (env.engine_mode_key): every
    keyed knob's effective value — matmul precision, the f64-MXU
    limb-scheme switch, the limb chunk size (all change what ops/apply
    traces), the gate-scheduler and fused-scan switches (change what
    the fusing engines plan) and the host-engine block size. Omitting
    any returns stale programs when a user flips the knob mid-process —
    the cache-key discipline of ADVICE r4 item 2 / review r5; the knob
    registry makes the list mechanical instead of hand-maintained
    (quest-lint QL001 checks read sites against it). The apply-layer
    subset is A.mode_key(), shared with the eager per-gate jit workers
    (ops/gates.py) whose cache needs the same discipline."""
    from quest_tpu.env import engine_mode_key
    return engine_mode_key()

# named-gate recovery for Circuit.to_qasm (the builder stores operands;
# the QASM recorder prefers gate names, like the eager API)
_NAMED_2x2 = (("h", M.HADAMARD), ("x", M.PAULI_X), ("y", M.PAULI_Y),
              ("z", M.PAULI_Z))


def _named_1q(u):
    """(gate name, params) of a stored 2x2 operand, or None: the fixed
    Cliffords by exact match, rx/ry by structural recovery of the angle
    (modulo the rotation's 4pi matrix period)."""
    for name, mat in _NAMED_2x2:
        if np.array_equal(u, mat):
            return (name, ())
    c, o = u[0, 0], u[0, 1]
    if (abs(c.imag) < 1e-14 and abs(o.real) < 1e-14
            and np.allclose(u, [[c, o], [o, c]])):
        th = 2.0 * np.arctan2(-o.imag, c.real)
        if np.allclose(u, M.rotation(th, (1.0, 0.0, 0.0))):
            return ("rx", (th,))
    if (np.allclose(u.imag, 0.0, atol=1e-14)
            and np.allclose(u, [[c, o], [-o, c]])):
        th = 2.0 * np.arctan2(-o.real, c.real)
        if np.allclose(u, M.rotation(th, (0.0, 1.0, 0.0))):
            return ("ry", (th,))
    return None


def _named_diag(d):
    """(gate name, params) of a stored (2,) diagonal operand, or None."""
    if np.array_equal(d, M.Z_DIAG):
        return ("z", ())
    if np.array_equal(d, M.S_DIAG):
        return ("s", ())
    if np.array_equal(d, M.T_DIAG):
        return ("t", ())
    if abs(d[0] - 1.0) < 1e-14 and abs(abs(d[1]) - 1.0) < 1e-14:
        return ("phase", (float(np.angle(d[1])),))
    return None


def as_rotation(op: GateOp):
    """(family, theta) of a parametric op, or None for a constant gate.

    The structural inverse of the builder emitters: every angle-taking
    Circuit method stores a dense operand (rx/ry -> 2x2 matrix, phase/
    cphase -> diagonal/allones term, rz/parity/multi_rotate_* -> parity
    angle), and the adjoint engine (quest_tpu/adjoint.py) needs the
    angle BACK to differentiate the gate. Families and their appliers:

      'parity'  exp(-i th/2 Z..Z)  theta = stored angle
      'rx'/'ry' M.rotation(th, x/y axis), recovered via arctan2 over
                the full 4pi matrix period (same recovery as _named_1q)
      'phase'   diagonal [1, e^{i th}] on one target
      'allones' phase e^{i th} on the all-ones subspace (cphase)

    EXACT constant gates (h/x/y/z, z/s/t diagonals, cz's -1 term) return
    None — they carry no parameter. The builder emitters never produce
    those exact constants from a generic angle (np.exp(1j*pi) retains a
    residual imaginary part), so round-tripping every parametric emitter
    is loss-free; pinned in tests/test_adjoint.py."""
    if op.kind == "parity":
        return ("parity", float(op.operand))
    if op.kind == "matrix":
        u = np.asarray(op.operand)
        if u.shape != (2, 2):
            return None
        for _, mat in _NAMED_2x2:
            if np.array_equal(u, mat):
                return None
        c, o = u[0, 0], u[0, 1]
        if (abs(c.imag) < 1e-14 and abs(o.real) < 1e-14
                and np.allclose(u, [[c, o], [o, c]])):
            th = 2.0 * np.arctan2(-o.imag, c.real)
            if np.allclose(u, M.rotation(th, (1.0, 0.0, 0.0))):
                return ("rx", float(th))
        if (np.allclose(u.imag, 0.0, atol=1e-14)
                and np.allclose(u, [[c, o], [-o, c]])):
            th = 2.0 * np.arctan2(-o.real, c.real)
            if np.allclose(u, M.rotation(th, (0.0, 1.0, 0.0))):
                return ("ry", float(th))
        return None
    if op.kind == "diagonal":
        d = np.asarray(op.operand)
        if d.shape != (2,):
            return None
        if (np.array_equal(d, M.Z_DIAG) or np.array_equal(d, M.S_DIAG)
                or np.array_equal(d, M.T_DIAG)):
            return None
        if abs(d[0] - 1.0) < 1e-14 and abs(abs(d[1]) - 1.0) < 1e-14:
            return ("phase", float(np.angle(d[1])))
        return None
    if op.kind == "allones":
        term = complex(op.operand)
        if abs(term + 1.0) < 1e-14:      # cz/ccz: exact constant
            return None
        if abs(abs(term) - 1.0) < 1e-14:
            return ("allones", float(np.angle(term)))
        return None
    return None


def inverse_op(op: GateOp) -> GateOp:
    """The adjoint of ONE GateOp (matrix -> U+, diagonal/allones ->
    conjugate, parity -> negated angle; controls preserved). The single
    place the per-op inverse rules live — Circuit.inverse reverses the
    stream through here, and the adjoint backward walk (adjoint.py)
    un-applies gates one at a time through the same rules. Raises on
    non-invertible kinds, naming the op."""
    if op.kind in ("superop", "measure", "measure_dm", "classical"):
        from quest_tpu.validation import QuESTError
        what = {"superop": "noise channels",
                "measure": "measurements",
                "measure_dm": "measurements",
                "classical": "classically-controlled gates"}
        raise QuESTError(
            f"Invalid operation: a circuit containing "
            f"{what[op.kind]} has no inverse.")
    if op.kind == "matrix":
        operand = np.asarray(op.operand).conj().T
    elif op.kind in ("diagonal", "allones"):
        operand = np.conj(op.operand)
    else:                      # parity: exp(-i a/2 Z..Z)
        operand = -op.operand
    parts = getattr(op, "parts", None)
    if parts:
        # ComposedDiag: keep the phase components in step with the
        # conjugated table (see dual_of)
        return dataclasses.replace(
            op, operand=operand,
            parts=tuple((k, b, -a) for k, b, a in parts))
    return dataclasses.replace(op, operand=operand)


def flatten_ops(ops, n: int, density: bool) -> List[GateOp]:
    """Expand density duals into a flat op list (ref QuEST.c:8-10);
    superops become explicit matrix ops on the doubled targets. The ONE
    place this expansion lives — every engine (XLA, banded, fused,
    sharded) flattens through here."""
    if not density and any(op.kind == "superop" for op in ops):
        from quest_tpu.validation import QuESTError
        raise QuESTError(
            "Invalid operation: noise channels require a density-matrix "
            "register")
    flat: List[GateOp] = []
    for op in ops:
        if op.kind == "superop":
            flat.append(dataclasses.replace(
                op, kind="matrix",
                targets=M.superop_targets(op.targets, n // 2)))
            continue
        if op.kind == "measure":
            # the measurement worker handles the density register itself
            # (diagonal probability + both-space collapse); tag it so the
            # flat executors, which otherwise run with density=False,
            # know which math to use. The tagged op CLAIMS both the qubit
            # and its column-space dual (targets[0] stays the logical
            # qubit): the fusion planner must not commute a later gate's
            # dual back across the collapse.
            if density:
                q0 = op.targets[0]
                flat.append(dataclasses.replace(
                    op, kind="measure_dm", targets=(q0, q0 + n // 2)))
            else:
                flat.append(op)
            continue
        if op.kind == "classical":
            inners, conds = op.operand
            if density:
                expanded, claim = [], []
                for g in inners:
                    expanded.append(g)
                    claim += list(g.targets) + list(g.controls)
                    d = dual_of(g, n // 2)
                    if d is not None:
                        expanded.append(d)
                        claim += list(d.targets) + list(d.controls)
                flat.append(dataclasses.replace(
                    op, targets=tuple(dict.fromkeys(claim)),
                    operand=(tuple(expanded), conds)))
            else:
                flat.append(op)
            continue
        flat.append(op)
        if density:
            dual = dual_of(op, n // 2)
            if dual is not None:
                flat.append(dual)
    return flat


def _loop(body, amps, iters: int):
    """Apply `body` to the state `iters` times inside one program, so deep
    repetition costs ONE dispatch. Small counts unroll (cheaper than
    lax.fori_loop's carry handling); large counts use fori_loop to bound
    program size."""
    if iters == 1:
        return body(amps)
    if iters <= _LOOP_UNROLL_MAX:
        for _ in range(iters):
            amps = body(amps)
        return amps
    from jax import lax
    return lax.fori_loop(0, iters, lambda _, a: body(a), amps)


def _apply_one(amps, n, op: GateOp):
    operand = op.operand
    if op.kind == "parity":
        return A.apply_parity_phase(amps, n, op.targets, operand)
    if op.kind == "allones":
        return A.apply_phase_on_all_ones(amps, n, op.targets,
                                         cplx.pack(operand))
    if op.kind == "superop":
        # channel superoperator on [targets, targets + N] of the doubled
        # register (ref QuEST_common.c:540-673)
        return A.apply_matrix(amps, n, cplx.pack(operand),
                              M.superop_targets(op.targets, n // 2))
    fn = A.apply_diagonal if op.kind == "diagonal" else A.apply_matrix
    return fn(amps, n, cplx.pack(operand), op.targets, op.controls,
              op.cstates)


def _apply_banded_items(amps, n, items):
    """Apply an already-computed band-fusion plan (loop-invariant: callers
    hoist the planning out of repeated bodies)."""
    from quest_tpu.ops import fusion as F
    for it in items:
        if isinstance(it, F.BandOp):
            amps = A.apply_band(amps, n, (it.gre, it.gim), it.ql, it.w,
                                it.preds)
        elif isinstance(it, F.DiagItem):
            amps = _apply_one(amps, n, it.op)
        else:
            amps = _apply_op(amps, n, False, it.op)
    return amps


def _apply_op(amps, n, density, op: GateOp):
    amps = _apply_one(amps, n, op)
    if density:
        dual = dual_of(op, n // 2)
        if dual is not None:
            amps = _apply_one(amps, n, dual)
    return amps


# Chip-generation cost-model table (VERDICT r4 item 7: the estimate must
# NAME its constants' provenance per chip instead of silently applying
# v5e numbers everywhere). Constants are ms at 30q (16 GiB state):
#   base_pass — one HBM read+write sweep (DMA floor)
#   sc / scb / b1_extra / pair / phase — per-stage compute adders (see
#   the v5e entry's notes; other generations scale them)
_COST_MODELS = {
    "v5e": {
        "provenance": "MEASURED on v5e (docs/KERNELS.md, r4 calibration; "
                      "re-derive: python -m quest_tpu.profiling --n 30)",
        # one HBM pass at the chip's REAL in-place 461 GB/s (56% of the
        # 819 GB/s datasheet rate)
        "base_pass": 34.7,
        # elementwise butterfly, VPU-bound: ~23 ms each when stacked
        # (7 stacked sc stages measured 160 ms; a lone one hides under
        # DMA)
        "sc": 23.0,
        # an scb's MXU time is ~FLAT in its dot dim — a small-M dot
        # idles most of the systolic array, so stage time follows
        # output size, not MACs (top/mid/bottom d=8 all ~40 ms alone vs
        # d=128's 42.6; the pre-r4 d-scaled model underestimated narrow
        # stacked stages 10x and motivated a Kron-split that measured
        # 3.8x SLOWER)
        "scb": 25.0,
        "b1_extra": 4.0,       # b1 frame relayout (data movement)
        "pair": 12.0,
        # phase/parity/diagvec: calibrated on QFT-30 (~5.5 ms per stage:
        # 14 passes of ~32 phases measured 3.11 s steady)
        "phase": 5.5,
    },
    "v5p": {
        "provenance": "PROJECTED from the v5e measurements: DMA terms x "
                      "461/1550 (datasheet 2765 GB/s x the 0.56 in-place "
                      "derate measured on v5e), compute terms x 394/918 "
                      "bf16-TFLOP ratio — no v5p has been measured "
                      "(docs/POD_PROJECTION.md)",
        "base_pass": 34.7 * (461.0 / 1550.0),
        "sc": 23.0 * (394.0 / 918.0),
        "scb": 25.0 * (394.0 / 918.0),
        "b1_extra": 4.0 * (461.0 / 1550.0),
        "pair": 12.0 * (394.0 / 918.0),
        "phase": 5.5 * (461.0 / 1550.0),
    },
}


def _cost_model_for(device_kind: str):
    """(model dict, matched bool) for a jax device_kind string; unknown
    generations fall back to the v5e constants WITH matched=False so
    explain() can caution instead of silently mis-scaling."""
    k = device_kind.lower()
    if "v5p" in k or "v5 p" in k:
        return _COST_MODELS["v5p"], True
    # v5e reports as 'TPU v5 lite' / 'v5e'; match THAT generation only —
    # a future 'v6 lite' must fall through to matched=False so explain()
    # cautions instead of claiming v5e-measured provenance
    if "v5e" in k or ("v5" in k and "lite" in k):
        return _COST_MODELS["v5e"], True
    return _COST_MODELS["v5e"], False


def _estimate_ms(parts, n, model=None):
    """(lo, hi) estimated steady-state ms per application, from the
    chip-keyed cost model (_COST_MODELS; default v5e — the measured
    entry). The pipeline overlaps compute with the DMA stream at depth
    (scripts/probe_stack.py), so the honest answer is the
    [max(DMA, compute), DMA + compute] range — the measured bench
    application (79.9 ms) sits AT its lo (79), and a lone mirrored
    scb-128 pass (42.6 ms) just above its 34.7 DMA floor."""
    from quest_tpu.ops import fusion as F
    from quest_tpu.ops import pallas_band as PB

    if model is None:
        model = _COST_MODELS["v5e"]
    scale = (1 << n) / (1 << 30)
    base = model["base_pass"]

    def compute_ms(st):
        if isinstance(st, PB.MatStage):
            if st.kind == "sc":
                return model["sc"]
            # real_only discounts only the MXU dot passes; the b1 frame
            # relayout is data movement
            return (model["scb"] * (2 / 3 if st.real_only else 1.0)
                    + (model["b1_extra"] if st.kind == "b1" else 0.0))
        if isinstance(st, PB.PairStage):
            return model["pair"]
        if isinstance(st, PB.MultiPhaseStage):
            # PROJECTED from the measured per-phase constant, not
            # calibrated: the v5e measured 33.7 ms (trig path) and
            # 15.2 ms (trig-free) for m=2 behind an scb128 stage, against
            # this 7.2 (docs/KERNELS.md); recalibration is ROADMAP D6
            return model["phase"] * (0.7 + 0.3 * len(st.forms))
        return model["phase"]

    lo = hi = 0.0
    for part in parts:
        if part[0] == "segment":
            comp = sum(compute_ms(st) for st in part[1])
            lo += max(base, comp)
            hi += base + comp
        else:
            # XLA band passthrough: 1.6-2x the state bytes
            it = part[1]
            mult = 1.8 if isinstance(it, F.BandOp) else 1.0
            lo += base * mult
            hi += base * mult
    return lo * scale, hi * scale


def _scan_partition(parts, scan_min: int):
    """Group maximal runs of >= scan_min consecutive kernel segments
    sharing ONE structure (identical stage tuple; operands differ) into
    ('scan', stages, [arrays, ...]) elements; everything else passes
    through as ('one', part). scan_min <= 0 disables grouping. Pure
    planning — unit-tested directly (tests/test_pallas.py), since the
    EXECUTED scan path is chip-only (interpret-mode Pallas inside a
    scan body explodes XLA-CPU compile, measured r4: >15 min for a
    4-segment program)."""
    out = []
    i = 0
    while i < len(parts):
        part = parts[i]
        if scan_min > 0 and part[0] == "segment":
            seg_key = tuple(part[1])
            j = i
            while (j < len(parts) and parts[j][0] == "segment"
                   and tuple(parts[j][1]) == seg_key):
                j += 1
            if j - i >= scan_min:
                out.append(("scan", part[1], [p[2] for p in parts[i:j]]))
                i = j
                continue
        out.append(("one", part))
        i += 1
    return out


def make_scan_applier(seg, arrays_run):
    """One lax.scan over a run of consecutive segments sharing ONE
    kernel structure (operands differ, stage tuple identical — QFT's
    repeated 32-phase mid-segments are the canonical case). The traced
    program carries the kernel call ONCE with stacked operands instead
    of len(run) inlined copies — the program-size lever for
    cold-start cost, which grew with program bytes on the old backend
    (ROADMAP S2). Opt-in via QUEST_FUSED_SCAN=1 until its steady-state
    cost is measured on chip. Interpret mode ignores
    the flag (compiled_fused passes scan_min=0): the Pallas
    interpreter's DMA emulation traced into a scan body explodes
    XLA-CPU compile time, so the executed scan path has only run on
    the chip; the grouping and operand stacking are unit-tested
    off-chip via _scan_partition and this function with a stub
    segment."""
    # numpy stack: operands stay HOST-side closure constants that
    # upload with the program, like the non-scan path (segment_plan's
    # host-side-operand design)
    stacked = tuple(
        np.stack([arrs[j] for arrs in arrays_run])
        for j in range(len(arrays_run[0])))

    def apply(amps, seg=seg, stacked=stacked):
        def body(a, xs):
            return seg(a, list(xs)), None
        out, _ = jax.lax.scan(body, amps, stacked)
        return out
    return apply


def _xla_part_applier(part, n):
    """Per-STATE applier (on the (2, rows, 128) kernel layout) for a
    non-segment plan part — the XLA passthrough path shared by
    compiled_fused and the batched engine, which jax.vmap's it over the
    leading batch axis (the kernel segments get a real batch grid
    dimension instead; quest_tpu/ops/pallas_band.py)."""
    from quest_tpu.ops import fusion as F

    it = part[1]
    if isinstance(it, F.BandOp):
        xla_fn = (lambda a, it=it: A.apply_band(
            a, n, (it.gre, it.gim), it.ql, it.w, it.preds))
    elif isinstance(it, F.DiagItem):
        xla_fn = lambda a, it=it: _apply_one(a, n, it.op)
    elif it.op.kind == "matrix":
        # matrix passthroughs (cross-band multi-target ops, channel
        # superops) stay in the (2, rows, 128) kernel layout — a flat
        # round-trip at this size costs a full-state layout copy (the
        # 8 GiB copy that OOMed the 30q density bench; see
        # apply_matrix_rows)
        op = it.op
        return (lambda amps, op=op: A.apply_matrix_rows(
            amps, n, cplx.pack(op.operand), op.targets,
            op.controls, op.cstates))
    else:
        xla_fn = lambda a, it=it: _apply_op(a, n, False, it.op)
    return (lambda amps, f=xla_fn:
            f(amps.reshape(2, -1)).reshape(amps.shape))


def _bucketed_wrapper(inner, bucket: int, api: str):
    """The bucketing calling convention, in ONE place (docs/BATCHING.md):
    wrap a bucket-shaped program so callers may pass ANY leading batch
    b <= bucket — zero-pad to the bucket (every engine op is a linear
    map, so pad states stay zero), run the one compiled program, slice
    back — and reject b > bucket loudly, naming the `api` to re-request.
    Shared by compiled_batched and compiled_sharded_batched so the
    contract cannot drift between engines."""
    def wrapper(amps_b):
        b = amps_b.shape[0]
        if b > bucket:
            raise ValueError(
                f"batch {b} exceeds this program's bucket {bucket}; "
                f"request {api}({b}) instead")
        shape = amps_b.shape
        flat_b = amps_b.reshape(b, 2, -1)
        if b < bucket:
            pad = jnp.zeros((bucket - b,) + flat_b.shape[1:],
                            flat_b.dtype)
            out = inner(jnp.concatenate([flat_b, pad], axis=0))
            return out[:b].reshape(shape)
        return inner(flat_b).reshape(shape)

    wrapper.bucket = bucket
    wrapper.inner = inner
    return wrapper


def _human_bytes(b: int) -> str:
    if b >= 2**29:
        return f"{b / 2**30:.2f} GiB"
    if b >= 2**19:
        return f"{b / 2**20:.2f} MiB"
    return f"{b / 2**10:.2f} KiB"


def _comm_plan_line(rec: dict) -> str:
    """The comm planner's line in explain_sharded: the PREDICTED
    exchange schedule (parallel/comm.py) and whether it matches what XLA
    actually lowered — 'MISMATCH' here means the predictor drifted from
    the engine and tests/test_comm.py would be red."""
    verdict = ("matches" if rec.get("comm_matches_hlo")
               else "MISMATCH vs")
    line = (f"  comm plan: {rec.get('comm_strategy', '?')} "
            f"(QUEST_COMM_PLAN={1 if rec.get('comm_plan_enabled') else 0})"
            f": {rec.get('comm_exchanges', 0)} exchange(s) = "
            f"{rec.get('comm_collective_permutes', 0)} collective-"
            f"permute(s) + {rec.get('comm_all_to_alls', 0)} "
            f"all-to-all(s), {_human_bytes(rec.get('comm_bytes', 0))} "
            f"ICI per device planned [{verdict} lowered StableHLO]")
    topo = rec.get("comm_topology") or {}
    if topo.get("hosts", 1) > 1:
        line += (f"\n  topology: {topo['hosts']} host(s), "
                 f"{rec.get('comm_dci_exchanges', 0)} DCI-crossing "
                 f"exchange(s), "
                 f"{_human_bytes(rec.get('comm_dci_bytes', 0))} DCI + "
                 f"{_human_bytes(rec.get('comm_ici_bytes', 0))} ICI "
                 f"per device (weights ici={topo['ici_weight']}, "
                 f"dci={topo['dci_weight']})")
    return line


class Circuit:
    """Builder for a fixed gate sequence over `num_qubits` qubits.

    Gate operands are baked into the compiled program as constants; the
    compiled function is cached per (num_state_qubits, density, dtype).
    """

    def __init__(self, num_qubits: int):
        self.num_qubits = num_qubits
        self.ops: List[GateOp] = []
        self._compiled = {}
        self._transpiled = {}   # transpile.transpile_cached memo —
        # separate from _compiled so planning-only surfaces (explain,
        # plan_stats) never make that cache non-empty

    # -- builders (chainable) ------------------------------------------------

    def _add(self, kind, targets, operand, controls=(), cstates=None,
             meta=None):
        targets = tuple(int(t) for t in targets)
        controls = tuple(int(c) for c in controls)
        cstates = tuple(cstates) if cstates is not None else (1,) * len(controls)
        for qb in targets + controls:
            if not (0 <= qb < self.num_qubits):
                raise ValueError(f"qubit {qb} out of range")
        if len(set(targets)) != len(targets):
            raise ValueError("target qubits must be unique")
        if len(set(controls)) != len(controls):
            raise ValueError("control qubits must be unique")
        if set(targets) & set(controls):
            raise ValueError("control and target qubits must be disjoint")
        self.ops.append(GateOp(kind, targets, controls, cstates, operand,
                               meta))
        self._compiled.clear()
        self._transpiled.clear()
        return self

    def gate(self, matrix, targets, controls=(), cstates=None):
        return self._add("matrix", targets, np.asarray(matrix, dtype=np.complex128),
                         controls, cstates)

    def h(self, t):
        return self._add("matrix", (t,), M.HADAMARD)

    def x(self, t, *controls):
        return self._add("matrix", (t,), M.PAULI_X, controls)

    def y(self, t):
        return self._add("matrix", (t,), M.PAULI_Y)

    def z(self, t):
        return self._add("diagonal", (t,), M.Z_DIAG)

    def s(self, t):
        return self._add("diagonal", (t,), M.S_DIAG)

    def t(self, tq):
        return self._add("diagonal", (tq,), M.T_DIAG)

    def phase(self, t, angle):
        return self._add("diagonal", (t,),
                         np.array([1.0, np.exp(1j * angle)]))

    def rx(self, t, angle):
        return self._add("matrix", (t,), np.asarray(M.rotation(angle, (1., 0., 0.))))

    def ry(self, t, angle):
        return self._add("matrix", (t,), np.asarray(M.rotation(angle, (0., 1., 0.))))

    def rz(self, t, angle):
        return self._add("parity", (t,), float(angle))

    def cnot(self, control, target):
        return self._add("matrix", (target,), M.PAULI_X, (control,))

    def cz(self, q1, q2):
        return self._add("allones", (q1, q2), -1.0 + 0.0j)

    def swap(self, q1, q2):
        return self._add("matrix", (q1, q2), M.SWAP)

    def multi_rotate_z(self, targets, angle):
        return self._add("parity", tuple(targets), float(angle))

    def measure(self, qubit):
        """MID-CIRCUIT measurement of `qubit` in the computational basis:
        the outcome is drawn inside the traced program (jax.random key,
        branchless collapse — quest_tpu.measurement._measure_traced) and
        returned as a device value. Circuits containing measurements run
        through compiled_measured / apply_measured, which take a PRNG key
        and return the outcome sequence alongside the state. The
        reference can only measure eagerly between kernel launches
        (statevec_measureWithStats, QuEST_common.c:360-366); here a
        dynamic circuit stays ONE compiled program."""
        return self._add("measure", (int(qubit),), None)

    def gate_if(self, matrix, targets, when, controls=(), cstates=None):
        """CLASSICALLY-CONTROLLED gate: apply `matrix` only when earlier
        mid-circuit measurement outcomes match `when` — a (measurement
        index, wanted bit) pair or a sequence of them (indices count
        measure() calls in program order). The condition is a traced
        predicate (branchless where-blend), so feedback stays inside the
        ONE compiled program — the reference must round-trip to the host
        for any feed-forward. Enables teleportation-class dynamic
        circuits (examples/teleportation.py)."""
        when = tuple(when)
        if when and all(hasattr(w, "__len__") for w in when):
            when = tuple(tuple(w) for w in when)
        else:
            when = (when,)
        if not all(len(w) == 2 for w in when) or not when:
            raise ValueError(
                "gate_if condition must be a (measurement index, wanted "
                "bit) pair or a non-empty sequence of such pairs")
        n_meas = self._measure_count()
        for idx, want in when:
            if not (0 <= int(idx) < n_meas):
                raise ValueError(
                    f"gate_if condition references measurement {idx}, but "
                    f"only {n_meas} measure() calls precede it")
            if int(want) not in (0, 1):
                raise ValueError("wanted outcome must be 0 or 1")
        inner = GateOp("matrix", tuple(int(t) for t in targets),
                       tuple(int(c) for c in controls),
                       tuple(cstates) if cstates is not None
                       else (1,) * len(controls),
                       np.asarray(matrix, dtype=np.complex128))
        return self._add(
            "classical", inner.targets + inner.controls,
            ((inner,), tuple((int(i), int(w)) for i, w in when)))

    def x_if(self, target, when):
        return self.gate_if(M.PAULI_X, (target,), when)

    def reset(self, qubit):
        """Reset `qubit` to |0> mid-circuit: measure it and flip on
        outcome 1 (the standard dynamic-circuit reset; destroys this
        qubit's coherences, preserves the rest of the register). The
        measurement outcome still appears in the returned sequence."""
        self.measure(qubit)
        return self.x_if(qubit, (self._measure_count() - 1, 1))

    def z_if(self, target, when):
        return self.gate_if(M.PAULI_Z, (target,), when)

    def _measure_count(self) -> int:
        return sum(1 for op in self.ops if op.kind == "measure")

    def _dynamic_count(self) -> int:
        return sum(1 for op in self.ops
                   if op.kind in ("measure", "classical"))

    def _reject_measure(self, what: str):
        if self._dynamic_count():
            from quest_tpu.validation import QuESTError
            raise QuESTError(
                f"Invalid operation: this circuit contains mid-circuit "
                f"measurements; use compiled_measured/apply_measured "
                f"instead of {what}.")

    def multi_rotate_pauli(self, targets, paulis, angle):
        """exp(-i angle/2 * P1 x P2 x ...) as basis rotations around a
        parity phase (ref statevec_multiRotatePauli,
        QuEST_common.c:410-447). In a traced circuit this decomposition
        is the right form: the 1q basis changes compose into the
        surrounding band operators and the parity core is
        communication-free on every engine (the eager gates path uses
        the one-pass flip-form instead, gates.multi_rotate_pauli)."""
        f = 1.0 / np.sqrt(2.0)
        to_z = {1: np.array([[f, f], [-f, f]]),          # Ry(-pi/2)
                2: np.array([[f, -1j * f], [-1j * f, f]])}  # Rx(pi/2)*
        z_targets = []
        for t, p in zip(targets, paulis):
            p = int(p)
            if p == 0:
                continue
            z_targets.append(int(t))
            if p in to_z:
                self._add("matrix", (int(t),), to_z[p])
        if z_targets:
            self._add("parity", tuple(z_targets), float(angle))
        for t, p in zip(targets, paulis):
            p = int(p)
            if p in to_z:
                self._add("matrix", (int(t),), to_z[p].conj().T)
        return self

    def sqrt_swap(self, q1, q2):
        return self._add("matrix", (q1, q2), M.SQRT_SWAP)

    # -- noise channels (density-matrix circuits only) -----------------------

    def kraus(self, targets, ops):
        """General Kraus map as a compiled circuit step (superoperator on
        the doubled register, ref QuEST_common.c:540-673). Validated at
        build time exactly like the eager mixKrausMap."""
        from quest_tpu import validation as val
        t = (targets,) if np.isscalar(targets) else tuple(targets)
        k = len(t)
        val.validate_kraus_ops(ops, k, max_ops=1 << (2 * k))
        # keep the raw (validated) Kraus decomposition next to the
        # composed superoperator: the density engines execute the
        # superop; the trajectory unraveling (trajectories.run_batched)
        # needs the branches — recovering them from the superoperator
        # would cost a Choi decomposition per channel
        raw = tuple(np.asarray(K, dtype=np.complex128) for K in ops)
        return self._add("superop", t, M.kraus_superoperator(ops),
                         meta=("kraus", raw))

    def damping(self, target, prob):
        from quest_tpu import validation as val
        p = float(prob)
        val.validate_one_qubit_damping_prob(p)
        return self.kraus(target, M.damping_kraus(p))

    def depolarising(self, target, prob):
        from quest_tpu import validation as val
        p = float(prob)
        val.validate_one_qubit_depol_prob(p)
        return self.kraus(target, M.depolarising_kraus(p))

    def dephasing(self, target, prob):
        from quest_tpu import validation as val
        p = float(prob)
        val.validate_one_qubit_dephase_prob(p)
        return self.kraus(target, M.dephasing_kraus(p))

    def cu(self, matrix, target, *controls, cstates=None):
        """Arbitrary single/multi-controlled k-qubit unitary."""
        t = (target,) if np.isscalar(target) else tuple(target)
        return self._add("matrix", t, np.asarray(matrix, dtype=np.complex128),
                         controls, cstates)

    def cphase(self, angle, *qubits):
        """Symmetric controlled phase e^{i angle} on all-ones of qubits."""
        return self._add("allones", tuple(qubits), np.exp(1j * float(angle)))

    def compiled_measured(self, n: int, density: bool, donate: bool = True,
                          engine: str = "banded"):
        """Compiled DYNAMIC circuit: returns fn(amps, key) ->
        (amps, outcomes) where outcomes is an int32 array of the
        mid-circuit measurement results in program order. The whole
        dynamic circuit — gates, outcome draws, branchless collapses —
        is ONE XLA program (the reference must come back to the host
        between measurements). engine: 'banded' (band-fusion between
        measurement barriers; the fusion planner treats a measurement
        as an opaque item that commutes only with disjoint-qubit ops)
        or 'xla' (per-gate)."""
        if engine not in ("banded", "xla"):
            raise ValueError(f"engine must be 'banded' or 'xla', got {engine!r}")
        if not self._measure_count():
            from quest_tpu.validation import QuESTError
            raise QuESTError(
                "Invalid operation: compiled_measured requires at least "
                "one mid-circuit measurement; use compiled() instead.")
        key_ = ("measured", engine, n, density, donate,
                _engine_mode_key())
        fn = self._compiled.get(key_)
        if fn is not None:
            return fn

        flat = flatten_ops(self.ops, n, density)

        def measure_item(amps, key, op):
            from quest_tpu import measurement as meas
            key, sub = jax.random.split(key)
            amps, outcome, _ = meas._measure_traced(
                amps, sub, n=n, qubit=op.targets[0],
                density=op.kind == "measure_dm")
            return amps, key, outcome.astype(jnp.int32)

        def classical_item(amps, outs, op):
            # feed-forward: branchless where-blend under a traced
            # predicate over earlier outcomes
            inners, conds = op.operand
            pred = None
            for idx, want in conds:
                p = outs[idx] == want
                pred = p if pred is None else pred & p
            new = amps
            for g in inners:
                new = _apply_one(new, n, g)
            return jnp.where(pred, new, amps)

        if engine == "banded":
            from quest_tpu.ops import fusion as F
            # the scheduler treats measure/classical ops as barriers, so
            # dynamic circuits reorder only within measurement-free
            # stretches
            items = F.plan(F.maybe_schedule(flat, n), n)

            def run(amps, key):
                outs = []
                for it in items:
                    if isinstance(it, F.BandOp):
                        amps = A.apply_band(amps, n, (it.gre, it.gim),
                                            it.ql, it.w, it.preds)
                    elif isinstance(it, F.DiagItem):
                        amps = _apply_one(amps, n, it.op)
                    elif it.op.kind in ("measure", "measure_dm"):
                        amps, key, oc = measure_item(amps, key, it.op)
                        outs.append(oc)
                    elif it.op.kind == "classical":
                        amps = classical_item(amps, outs, it.op)
                    else:
                        amps = _apply_op(amps, n, False, it.op)
                return amps, jnp.stack(outs)
        else:
            def run(amps, key):
                outs = []
                for op in flat:
                    if op.kind in ("measure", "measure_dm"):
                        amps, key, oc = measure_item(amps, key, op)
                        outs.append(oc)
                    elif op.kind == "classical":
                        amps = classical_item(amps, outs, op)
                    else:
                        amps = _apply_one(amps, n, op)
                return amps, jnp.stack(outs)

        fn = jax.jit(run, donate_argnums=(0,) if donate else ())
        self._compiled[key_] = fn
        return fn

    def apply_measured(self, q: Qureg, key, donate: bool = False,
                       engine: str = "banded"):
        """Apply a dynamic circuit: (new register, outcomes int32 array
        in program order). `key` is a jax.random key; identical keys
        reproduce identical trajectories."""
        if self.num_qubits != q.num_qubits:
            raise ValueError("circuit/register size mismatch")
        if not self._measure_count():
            from quest_tpu.validation import QuESTError
            raise QuESTError(
                "Invalid operation: apply_measured requires at least one "
                "mid-circuit measurement; use apply() instead.")
        fn = self.compiled_measured(q.num_state_qubits, q.is_density,
                                    donate, engine)
        amps, outcomes = fn(q.amps, key)
        return q.replace_amps(amps), outcomes

    def inverse(self) -> "Circuit":
        """The adjoint circuit: ops reversed, each operand conjugate-
        transposed (matrix -> U+, diagonal/allones -> conjugate, parity
        -> negated angle). Controls/control-states are preserved (the
        adjoint of a controlled U is the same-controlled U+). Circuits
        containing noise channels are not invertible and raise. No
        reference analogue (QuEST has no circuit object); enables
        uncomputation patterns like QPE's inverse QFT."""
        inv = Circuit(self.num_qubits)
        for op in reversed(self.ops):
            inv.ops.append(inverse_op(op))
        return inv

    @classmethod
    def from_qasm(cls, text: str, u_dialect: str | None = None,
                  transpile: bool | None = None) -> "Circuit":
        """Parse OPENQASM 2.0 text into a Circuit — the recorder's own
        dialect (Ctrl- prefixes, U(rz2, ry, rz1) lines) and standard
        qelib1 gates both load; see quest_tpu/qasm_import.py. The
        reference has no importer (its QASM support is write-only,
        QuEST_qasm.c). `u_dialect` ('spec' | 'recorder') pins the
        capital-U parameter convention when the marker heuristic can't.
        `transpile` (None follows QUEST_TRANSPILE) routes the imported
        stream through the circuit transpiler (docs/TRANSPILE.md)."""
        from quest_tpu.qasm_import import circuit_from_qasm
        return circuit_from_qasm(text, u_dialect=u_dialect,
                                 transpile=transpile)

    def to_qasm(self) -> str:
        """OPENQASM 2.0 text of this circuit, through the same logger the
        eager API records with (quest_tpu/qasm.py; ref QuEST_qasm.c).
        Named gates (h/x/y/z/s/t/rx/ry/rz/phase/swap/sqrtswap) are
        recovered from the stored operands and emitted by name like the
        eager recorder; general operands fall back to ZYZ U-lines; ops
        with no QASM equivalent degrade to comments. Phase/rotation
        angles are recovered from operands modulo their period (the
        recorder's restore lines keep the emitted unitary exact)."""
        from quest_tpu import qasm as Q

        log = Q.QASMLogger(self.num_qubits)
        log.is_logging = True
        for op in self.ops:
            targets, controls = op.targets, op.controls
            cstates = op.cstates or (1,) * len(controls)
            if op.kind == "measure":
                log.record_measurement(targets[0])
                continue
            if op.kind == "classical":
                log.record_comment(
                    "Here a classically-controlled gate was applied "
                    f"(conditions on measurements {list(op.operand[1])})")
                continue
            if op.kind == "parity":
                if len(targets) == 1 and not controls:
                    log.record_gate("rz", targets[0], (), (op.operand,))
                else:
                    log.record_comment(
                        f"Here a multiRotateZ of angle {op.operand:g} was "
                        f"applied to qubits {list(targets)}")
            elif op.kind == "allones":
                term = complex(op.operand)
                qubits = tuple(targets) + tuple(controls)
                if any(s == 0 for s in cstates):
                    # a control-on-0 all-ones phase is NOT symmetric in
                    # (targets, controls) — keep the control states and
                    # anchor the diag on a condition-on-1 TARGET qubit
                    log.record_multi_state_controlled_unitary(
                        np.diag([1.0, term]),
                        tuple(targets[:-1]) + tuple(controls),
                        (1,) * (len(targets) - 1) + tuple(cstates),
                        targets[-1])
                elif abs(term + 1.0) < 1e-14:
                    log.record_gate("z", qubits[-1], qubits[:-1])
                else:
                    log.record_gate("phase", qubits[-1], qubits[:-1],
                                    (float(np.angle(term)),))
            elif op.kind == "diagonal" and len(targets) == 1:
                d = np.asarray(op.operand).reshape(-1)
                named = _named_diag(d)
                if any(s == 0 for s in cstates):
                    log.record_multi_state_controlled_unitary(
                        np.diag(d), controls, cstates, targets[0])
                elif named is not None:
                    log.record_gate(named[0], targets[0], controls,
                                    named[1])
                else:
                    log.record_unitary(np.diag(d), targets[0], controls)
            elif op.kind == "matrix" and len(targets) == 1:
                u = np.asarray(op.operand)
                named = _named_1q(u)
                if any(s == 0 for s in cstates):
                    log.record_multi_state_controlled_unitary(
                        u, controls, cstates, targets[0])
                elif named is not None:
                    log.record_gate(named[0], targets[0], controls,
                                    named[1])
                else:
                    log.record_unitary(u, targets[0], controls)
            elif (op.kind == "matrix" and len(targets) == 2
                  and not controls):
                u = np.asarray(op.operand)
                if np.array_equal(u, M.SWAP):
                    log.record_gate("swap", targets[1], (targets[0],))
                elif np.allclose(u, M.SQRT_SWAP):
                    log.record_gate("sqrtswap", targets[1], (targets[0],))
                else:
                    log.record_comment("Here a multi-qubit gate was "
                                       "applied (no QASM equivalent)")
            else:
                log.record_comment("Here a multi-qubit gate was applied "
                                   "(no QASM equivalent)")
        return log.recorded()

    # -- compilation & execution --------------------------------------------

    def trace(self, amps, n: int, density: bool):
        """Apply all ops to raw amplitudes inside an existing trace."""
        self._reject_measure("trace")
        if not density and any(op.kind == "superop" for op in self.ops):
            from quest_tpu.validation import QuESTError
            raise QuESTError(
                "Invalid operation: noise channels require a density-matrix "
                "register")
        for op in self.ops:
            amps = _apply_op(amps, n, density, op)
        return amps

    def compiled(self, n: int, density: bool, donate: bool = True,
                 iters: int = 1):
        self._reject_measure("compiled")
        # compiled-program size, not work: past _LOOP_UNROLL_MAX the
        # iteration rides ONE fori_loop whose body traces len(ops) HLO
        # ops (_loop), so only an UNROLLED iters multiplies what XLA
        # must compile
        unroll = iters if 1 <= iters <= _LOOP_UNROLL_MAX else 1
        emitted = len(self.ops) * unroll
        if emitted > PERGATE_COMPILE_WARN_OPS:
            _warn_pergate_compile_once(emitted)
        key = (n, density, donate, iters,
               _engine_mode_key())
        fn = self._compiled.get(key)
        if fn is None:
            def run(amps):
                return _loop(lambda a: self.trace(a, n, density), amps, iters)
            fn = jax.jit(run, donate_argnums=(0,) if donate else ())
            self._compiled[key] = fn
        return fn

    def apply(self, q: Qureg, donate: bool = False) -> Qureg:
        """Apply the circuit to a register (donate=True invalidates q).

        Above PERGATE_COMPILE_WARN_OPS ops the dispatch auto-routes
        through the banded engine (QUEST_APPLY_AUTOROUTE, default on):
        the per-gate XLA chain compiles pathologically slowly there —
        minutes at ~100 ops on XLA-CPU — while the banded program
        compiles in seconds and applies the same unitaries
        (eps-identical in general, BIT-identical for permutation/phase
        gates at HIGHEST — tests/test_plan.py pins both). 0 restores
        the legacy warn-only per-gate dispatch (docs/PLANNING.md)."""
        n = q.num_state_qubits
        if self.num_qubits != q.num_qubits:
            raise ValueError("circuit/register size mismatch")
        if (len(self.ops) > PERGATE_COMPILE_WARN_OPS
                and not self._dynamic_count()
                and not any(op.kind == "superop" for op in self.ops)):
            from quest_tpu.env import knob_value
            if knob_value("QUEST_APPLY_AUTOROUTE"):
                return self.apply_banded(q, donate)
        return q.replace_amps(self.compiled(n, q.is_density, donate)(q.amps))

    def _flat_ops(self, n: int, density: bool) -> List[GateOp]:
        return flatten_ops(self.ops, n, density)

    def _planned_flat(self, n: int, density: bool) -> List[GateOp]:
        """The flat op list the FUSING engines plan from: flattened,
        then reordered/composed by the commutation-aware scheduler
        (quest_tpu.ops.fusion.schedule, QUEST_SCHEDULE knob). The
        per-gate XLA engine (compiled / trace) deliberately stays
        unscheduled — it is the semantic oracle the scheduled engines
        are fuzzed against (tests/test_scheduler.py)."""
        from quest_tpu.ops import fusion as F
        return F.maybe_schedule(self._flat_ops(n, density), n)

    def compiled_banded(self, n: int, density: bool, donate: bool = True,
                        iters: int = 1):
        """Compiled program using the band-fusion engine
        (quest_tpu.ops.fusion): runs of commuting gates compose into one
        operator per 7-qubit band, each applied as a single MXU axis
        contraction (apply_band). Diagonal/parity ops stay elementwise and
        XLA fuses them into the neighbouring passes. A layer of n
        single-qubit gates costs ~ceil(n/7) memory passes instead of n."""
        self._reject_measure("compiled_banded")
        key = ("banded", n, density, donate, iters,
               _engine_mode_key())
        fn = self._compiled.get(key)
        if fn is not None:
            return fn

        from quest_tpu.ops import fusion as F
        items = F.plan(self._planned_flat(n, density), n)

        def run(amps):
            return _loop(lambda a: _apply_banded_items(a, n, items), amps,
                         iters)

        fn = jax.jit(run, donate_argnums=(0,) if donate else ())
        self._compiled[key] = fn
        return fn

    def compiled_host(self, n: int, density: bool, iters: int = 1):
        """Compiled program on the NATIVE HOST engine (quest_tpu.host):
        cache-blocked C++ kernels applying whole gate groups per
        L2-resident block — the CPU-backend counterpart of the
        reference's per-gate sweeps (QuEST_cpu.c:1656-1713), used by the
        bench fallback ladder when no TPU is reachable. Returns
        step(state)->state over numpy (2, 2^n) planes (jax host arrays
        convert on first call); ALWAYS updates writable numpy input in
        place (callers wanting a pristine input pass a copy — see
        apply_host). Raises host.HostEngineUnsupported on dynamic ops /
        traced operands so callers fall back loudly."""
        self._reject_measure("compiled_host")
        from quest_tpu import host as H
        # QUEST_HOST_BLOCK is read at encode time; it is a keyed knob in
        # the registry, so _engine_mode_key() covers it — flipping it
        # mid-process can't return a stale program (the cache-key
        # discipline from ADVICE r4 item 2)
        key = ("host", n, density, iters, _engine_mode_key())
        fn = self._compiled.get(key)
        if fn is None:
            fn = H.compile_circuit_host(self.ops, n, density, iters)
            self._compiled[key] = fn
        return fn

    def apply_host(self, q: Qureg, donate: bool = False) -> Qureg:
        """Apply via the native host engine (numpy planes). donate=False
        copies first so q's buffer survives (the engine itself is
        in-place). Donation only takes effect for registers backed by a
        writable numpy array: jax device buffers are immutable, so a
        jax-backed q.amps costs exactly one host copy either way (the
        engine's _as_planes makes it when the view is read-only)."""
        if self.num_qubits != q.num_qubits:
            raise ValueError("circuit/register size mismatch")
        fn = self.compiled_host(q.num_state_qubits, q.is_density)
        import numpy as _np
        amps = _np.array(q.amps) if not donate else q.amps
        return q.replace_amps(jnp.asarray(fn(amps)))

    def compiled_host_measured(self, n: int, density: bool = False):
        """DYNAMIC circuit on the NATIVE HOST engine: step(state,
        draws=None) -> (planes, outcomes). Measurement-free stretches
        run blocked native kernels; measurements collapse natively;
        default draws come from the reference-exact MT19937 — the same
        stream the eager API uses, so identically-seeded host and eager
        trajectories match outcome-for-outcome (quest_tpu/host.py
        compile_circuit_host_measured); density registers collapse
        both spaces natively."""
        from quest_tpu import host as H
        key = ("host-measured", n, density, _engine_mode_key())
        fn = self._compiled.get(key)
        if fn is None:
            fn = H.compile_circuit_host_measured(self.ops, n, density)
            self._compiled[key] = fn
        return fn

    def banded_trace(self, amps, n: int, density: bool):
        """Apply the band-fusion plan to raw amplitudes inside an existing
        trace (the un-jitted core of compiled_banded)."""
        self._reject_measure("banded_trace")
        from quest_tpu.ops import fusion as F
        items = F.plan(self._planned_flat(n, density), n)
        return _apply_banded_items(amps, n, items)

    def apply_banded(self, q: Qureg, donate: bool = False) -> Qureg:
        """Apply via the band-fusion engine."""
        if self.num_qubits != q.num_qubits:
            raise ValueError("circuit/register size mismatch")
        fn = self.compiled_banded(q.num_state_qubits, q.is_density, donate)
        return q.replace_amps(fn(q.amps))

    def compiled_fused(self, n: int, density: bool, donate: bool = True,
                       interpret: bool = False, iters: int = 1):
        """Compiled program using the Pallas band-segment engine
        (quest_tpu.ops.pallas_band): each segment of band operators,
        diagonals and parity phases executes in ONE kernel launch / one
        HBM pass; band ops above the block top and cross-band unitaries
        run through the XLA band path between segments. `interpret=True`
        runs the kernels in the Pallas interpreter (for CPU testing)."""
        self._reject_measure("compiled_fused")
        from quest_tpu.ops import fusion as F
        from quest_tpu.ops import pallas_band as PB
        from quest_tpu.env import knob_value
        scan_flag = knob_value("QUEST_FUSED_SCAN")
        # scan_flag is a keyed registry knob, so _engine_mode_key()
        # already carries it in the cache key below
        key = ("fused", n, density, donate, interpret, iters,
               _engine_mode_key())
        fn = self._compiled.get(key)
        if fn is not None:
            profiling.count("quest.fused_cache_hit")
            return fn
        if not PB.usable(n):
            fn = self.compiled_banded(n, density, donate, iters=iters)
            self._compiled[key] = fn
            return fn

        with profiling.annotate("quest.plan"):
            flat = self._planned_flat(n, density)
            # PB.plan_bands now matches fusion's default 7-wide layout, so
            # the same plan serves both the kernel segmentation and the
            # f64 XLA band path
            items = F.plan(flat, n, bands=PB.plan_bands(n))
            parts = PB.segment_plan(items, n)
            # sweep fusion (QUEST_SWEEP_FUSION, keyed — _engine_mode_key
            # carries it): merge geometry-compatible consecutive segments
            # into single-launch HBM sweeps, INCLUDING across the unrolled
            # iterations of this program — a repeated block-resident
            # circuit (the bench's headline/chain steps) collapses from
            # `iters` kernel launches per dispatch to ~iters/k, each
            # streaming the state once (quest_tpu/ops/pallas_band.py
            # sweep_plan, docs/SWEEPS.md). Unrolling the parts list here
            # replaces _loop's own unroll for the same iteration range,
            # so program size is unchanged when nothing merges.
            unroll = iters if 1 < iters <= _LOOP_UNROLL_MAX else 1
            if PB.sweep_enabled():
                parts = PB.sweep_plan(parts * unroll, n)
            else:
                unroll = 1
            PB.record_vmem(items, n, parts, unroll)
        loop_iters = iters // unroll
        seg_cache = {}  # identical-structure segments share one kernel

        def make_applier(part):
            # segment appliers work on (2, rows, 128); XLA passthroughs
            # flatten and restore around their op (_xla_part_applier)
            if part[0] == "segment":
                _, stages, arrays = part
                seg = PB.compile_segment_cached(seg_cache, stages, n,
                                                interpret=interpret)
                return lambda amps, seg=seg, arrays=arrays: seg(amps, arrays)
            return _xla_part_applier(part, n)

        scan_min = 3 if (scan_flag and not interpret) else 0
        appliers = []
        for grp in _scan_partition(parts, scan_min):
            if grp[0] == "scan":
                seg = PB.compile_segment_cached(
                    seg_cache, grp[1], n, interpret=interpret)
                appliers.append(make_scan_applier(seg, grp[2]))
            else:
                appliers.append(make_applier(grp[1]))

        def run(amps):
            # the Pallas kernels are f32-only; f64 registers keep their
            # precision on the XLA band path
            if amps.dtype != jnp.float32:
                flat_in = amps.reshape(2, -1)
                out = _loop(lambda a: _apply_banded_items(a, n, items),
                            flat_in, iters)
                return out.reshape(amps.shape)
            shape = amps.shape

            def body(a):
                # each sweep's position in the plan reaches the op
                # metadata of its kernel (or XLA ops) in the trace
                for i, f in enumerate(appliers):
                    with jax.named_scope(f"quest.sweep{i:02d}"):
                        a = f(a)
                return a
            out = _loop(body, amps.reshape(2, -1, PB.LANES), loop_iters)
            return out.reshape(shape)

        fn = jax.jit(run, donate_argnums=(0,) if donate else ())
        self._compiled[key] = fn
        return fn

    def apply_fused(self, q: Qureg, donate: bool = False,
                    interpret: bool = False) -> Qureg:
        """Apply via the Pallas fused-segment engine."""
        if self.num_qubits != q.num_qubits:
            raise ValueError("circuit/register size mismatch")
        fn = self.compiled_fused(q.num_state_qubits, q.is_density, donate,
                                 interpret)
        return q.replace_amps(fn(q.amps))

    def compiled_batched(self, batch: int, density: bool = False,
                         donate: bool = True, interpret: bool = False,
                         engine: str = None):
        """BATCHED fused engine: ONE compiled program applying this
        circuit to a whole batch of states — (B, 2, 2^n) planes in, same
        out. Each kernel sweep carries a leading batch grid dimension
        and streams the bucket's states through HBM back-to-back with
        the same stage list (quest_tpu/ops/pallas_band.py), so the
        LAUNCH COUNT of a B-shot workload does not scale with B — the
        throughput shape trajectories, multi-shot sampling and parameter
        sweeps want (docs/BATCHING.md; Q-GEAR's batched-circuit win,
        arXiv:2504.03967). f64 registers and registers below the kernel
        tier ride a vmapped banded-XLA program instead (full precision /
        no Pallas), still one compiled dispatch for the whole batch.

        Batch-size BUCKETING: the compiled size is
        env.batch_bucket(batch) — B rounds up to the next power of two
        under QUEST_BATCH_BUCKET=pow2 (default) — and the returned
        wrapper accepts ANY leading batch b <= bucket, zero-padding to
        the bucket and slicing back (every engine op is a linear map, so
        padding states stay zero and cost only their share of the
        launch). Calls whose batches share a bucket return the SAME
        wrapper object: serving mixed batch sizes hits one persistent
        compile-cache entry instead of retracing per size
        (tests/test_batched.py pins this with the CompileAuditor).

        `engine` pins the program family instead of auto-resolving:
        None (default) rides the Pallas kernels when the register
        reaches the kernel tier, 'banded' FORCES the vmapped banded-XLA
        program (the serve degradation ladder's fallback rung — it
        must stay dispatchable when the fused compile is the thing
        that's broken, docs/RESILIENCE.md), 'fused' demands the kernel
        path and raises below the kernel tier."""
        self._reject_measure("compiled_batched")
        if engine not in (None, "fused", "banded"):
            raise ValueError(
                f"engine must be None, 'fused' or 'banded', got {engine!r}")
        from quest_tpu.env import batch_bucket
        n = self.num_qubits * 2 if density else self.num_qubits
        bucket = batch_bucket(batch)
        key = ("batched", n, density, donate, interpret, bucket, engine,
               _engine_mode_key())
        fn = self._compiled.get(key)
        if fn is not None:
            return fn

        from quest_tpu.ops import fusion as F
        from quest_tpu.ops import pallas_band as PB

        if engine == "fused" and not PB.usable(n):
            raise ValueError(
                f"engine='fused' requires the kernel tier; a {n}-qubit "
                f"register rides the banded program (engine='banded' or "
                f"None)")
        flat = self._planned_flat(n, density)
        use_kernels = engine != "banded" and PB.usable(n)
        if use_kernels:
            items = F.plan(flat, n, bands=PB.plan_bands(n))
            parts = PB.maybe_sweep(PB.segment_plan(items, n), n)
        else:
            items = F.plan(flat, n)
            parts = None
        seg_cache = {}

        def make_appliers():
            appliers = []
            for part in parts:
                if part[0] == "segment":
                    seg = PB.compile_segment_cached(
                        seg_cache, tuple(part[1]), n,
                        interpret=interpret, batch=bucket)
                    appliers.append(
                        lambda a, seg=seg, arrays=part[2]: seg(a, arrays))
                else:
                    appliers.append(jax.vmap(_xla_part_applier(part, n)))
            return appliers

        appliers = make_appliers() if use_kernels else None

        def run(amps_b):
            flat_b = amps_b.reshape(bucket, 2, -1)
            if appliers is None or amps_b.dtype != jnp.float32:
                # vmapped banded program: f64 keeps the limb-scheme
                # precision; sub-kernel-tier registers skip Pallas
                return jax.vmap(
                    lambda a: _apply_banded_items(a, n, items))(flat_b)
            a = flat_b.reshape(bucket, 2, -1, PB.LANES)
            for f in appliers:
                a = f(a)
            return a.reshape(bucket, 2, -1)

        inner = jax.jit(run, donate_argnums=(0,) if donate else ())
        wrapper = _bucketed_wrapper(inner, bucket, "compiled_batched")
        self._compiled[key] = wrapper
        return wrapper

    def program_key(self, density: bool = False, interpret: bool = False,
                    dtype=np.float32) -> Tuple:
        """Hashable PROGRAM IDENTITY of the batched-engine program
        family this circuit resolves to — the serving layer's
        batch-compatibility rule (quest_tpu.serve, docs/SERVING.md):
        two requests may share one `compiled_batched` launch iff their
        program keys are EQUAL. The key carries the circuit object
        itself (op lists are compared by identity, not value — holding
        the object also pins its id, so a GC'd-then-reused id can never
        alias two circuits, the id(mesh) bug class of VERDICT r3), the
        op count (a circuit mutated after submit forms a new family),
        the register kind/size, the plane dtype (f32 rides the kernels,
        f64 the banded fallback — different programs), the interpret
        flag, and `engine_mode_key()` (a keyed-knob flip changes which
        program a batched call resolves to). Bucket size is NOT part of
        the identity: all buckets of one family share the planner and
        the per-bucket wrapper cache (docs/BATCHING.md)."""
        n = self.num_qubits * 2 if density else self.num_qubits
        return ("batched", self, len(self.ops), n, density, interpret,
                np.dtype(dtype).str, _engine_mode_key())

    def apply_batched(self, amps_b, density: bool = False,
                      donate: bool = False, interpret: bool = False):
        """Apply this circuit to a (B, 2, 2^n) batch of raw amplitude
        planes through the batched fused engine (compiled_batched)."""
        fn = self.compiled_batched(int(amps_b.shape[0]), density=density,
                                   donate=donate, interpret=interpret)
        return fn(amps_b)

    def plan_stats(self, density: bool = False,
                   batch: int = None, devices: int = None) -> dict:
        """Hardware-independent plan statistics — the pass-count metric
        the commutation-aware scheduler is judged by, assertable on CPU
        (no compile, no chip): 'banded' is fusion.plan_stats's model
        (BandOps + PassOps + maximal DiagItem runs, each one full-state
        HBM pass on the banded XLA engine); 'fused' — when the register
        reaches the kernel tier — counts the Pallas engine's segments +
        passthroughs (each one HBM pass per application), plus the
        scheduler's own counters. Computed under the CURRENT
        QUEST_SCHEDULE setting; toggle the knob and diff to see what
        scheduling buys (docs/SCHEDULER.md, tests/test_scheduler.py).
        `batch` adds a 'batched' record (batch, bucket,
        states_per_sweep, hbm_sweeps) describing what compiled_batched
        would execute for that many states — its hbm_sweeps equals the
        unbatched fused plan's by construction: launches do not scale
        with B (docs/BATCHING.md; scripts/check_batch_golden.py).
        `devices` adds a 'comm' record — the comm planner's PREDICTED
        collective schedule for the banded/fused sharded engines over
        that many devices (strategy, exchange counts, per-device ICI
        bytes at the session dtype) — pure host math, no mesh: a
        40q/256-device schedule prices on a laptop
        (docs/DISTRIBUTED.md; scripts/check_comm_golden.py holds the
        goldens and tests/test_comm.py pins it equal to the lowered
        StableHLO accounting).

        Since PR 16 this dict is a VIEW of the ProgramPlan IR
        (quest_tpu/plan.py builds one object, this method re-emits its
        historical shape bit-for-bit — docs/PLANNING.md); query
        plan.build_plan / plan.autotune for the typed structure."""
        self._reject_measure("plan_stats")
        from quest_tpu import plan as P
        return P.build_plan(self, density=density, batch=batch,
                            devices=devices).stats()

    def transpiled(self, exact_only: bool = False) -> "Circuit":
        """An equivalent circuit rewritten by the transpiler
        (quest_tpu/transpile.py, docs/TRANSPILE.md): peephole
        cancellation through commuting separators, rotation folding,
        1q-run merging and cost-model-priced 2q KAK resynthesis.
        Returns self when no pass fires. `exact_only` restricts to the
        bit-identical subset (exact inverse pairs / exact identities
        only). The rewrite report rides on the result as
        `_transpile_report`; memoized until this circuit mutates."""
        from quest_tpu import transpile as T
        return T.transpile_cached(self, exact_only=exact_only)[0]

    def _comm_plan_stats(self, n: int, density: bool, devices: int) -> dict:
        """The plan_stats 'comm' record: predicted collective schedule
        of the banded/fused sharded engines over `devices`, through the
        SAME policy home they execute (parallel.sharded.comm_plan_record
        wraps engine_flat + the comm predictor) so it cannot drift from
        the lowered program."""
        from quest_tpu.parallel import sharded as S
        return S.comm_plan_record(self.ops, n, density, devices)

    def explain(self, density: bool = False, batch: int = None) -> str:
        """Human-readable fused-engine schedule: what compiled_fused will
        actually execute, WITHOUT paying a compile — one line per part
        (kernel segment with its stage mix, or XLA passthrough), then
        totals: segments, distinct Mosaic kernels, HBM passes and the
        estimated bytes one application moves. Performance introspection
        the reference cannot offer (it executes gate by gate; there is
        no schedule to explain)."""
        self._reject_measure("explain")
        from quest_tpu.ops import fusion as F
        from quest_tpu.ops import pallas_band as PB

        n = self.num_qubits * 2 if density else self.num_qubits
        pass_bytes = 2 * 4 * (1 << n) * 2   # r+w of both f32 planes
        lines = [f"fused schedule for {len(self.ops)} ops on "
                 f"{self.num_qubits} qubits"
                 + (f" (density: {n}-qubit register)" if density else "")]
        flat = self._flat_ops(n, density)
        # ONE scheduler run serves both the stats line and the plan below
        sched_ops, sched = F.schedule(flat, n)
        enabled = F._schedule_enabled()
        if enabled:
            lines.append(
                f"  scheduler: on (QUEST_SCHEDULE=1): "
                f"{sched['delayed']} diagonal op(s) delayed, "
                f"{sched['hoisted']} hoisted, {sched['fused_ops']} "
                f"composed into {sched['fused_groups']} group(s)")
        else:
            lines.append(
                f"  scheduler: OFF (QUEST_SCHEDULE=0); on, it would "
                f"compose {sched['fused_ops']} diagonal op(s) into "
                f"{sched['fused_groups']} group(s)")

        def host_line():
            # the CPU-fallback story: what the native host engine would
            # do with this circuit (the bench ladder's first off-chip
            # rung) — omitted when the native library or an op's host
            # kernel is unavailable, never fatal to explain()
            try:
                from quest_tpu import host as H
                if H.available():
                    lines.append("  cpu fallback "
                                 + H.plan_summary(flat, n))
            except Exception:
                pass

        def plan_line():
            # the one unified plan line (docs/PLANNING.md): the priced
            # autotuner's verdict for this circuit — chosen engine,
            # estimated ms/application, incumbent and candidate count.
            # Searched fresh (persist=False: explain never reads or
            # writes the plan cache); omitted, never fatal, when a
            # subsystem cannot price (traced operands)
            try:
                from quest_tpu import plan as P
                lines.append("  " + P.autotune(
                    self, state_kind="density" if density else "pure",
                    batch=batch, persist=False).line())
            except Exception:
                pass

        def transpile_line():
            # the transpile axis's verdict for this stream
            # (docs/TRANSPILE.md): what the rewriter buys under the
            # current knob — omitted on dynamic streams, never fatal
            try:
                from quest_tpu.env import knob_value
                knob = knob_value("QUEST_TRANSPILE")
                if knob == "0":
                    lines.append("  transpile: off (QUEST_TRANSPILE=0)")
                    return
                from quest_tpu import transpile as T
                tc, rep = T.transpile_cached(self)
                if not rep["changed"]:
                    lines.append(
                        f"  transpile: no rewrite ({rep['ops_in']} op(s) "
                        f"already minimal under the pass catalog; "
                        f"QUEST_TRANSPILE={knob})")
                    return
                attr = ", ".join(f"{k}={v}"
                                 for k, v in rep["passes"].items() if v)
                lines.append(
                    f"  transpile: {rep['ops_in']} -> {rep['ops_out']} "
                    f"op(s) [{attr}] (QUEST_TRANSPILE={knob}; "
                    f"docs/TRANSPILE.md)")
            except Exception:
                pass

        if not PB.usable(n):
            lines.append(f"  register below the kernel tier's minimum "
                         f"({PB.LANE_QUBITS + 3} qubits): the banded XLA "
                         f"engine runs instead")
            transpile_line()
            plan_line()
            host_line()
            return "\n".join(lines)

        # the plan compiled_fused will actually execute: scheduled when
        # the knob is on (host_line above deliberately keeps the raw
        # flat list — the host engine consumes Circuit.ops directly)
        items = F.plan(sched_ops if enabled else flat, n,
                       bands=PB.plan_bands(n))
        parts = PB.segment_plan(items, n)
        # sweep fusion: report the plan compiled_fused will execute for
        # ONE application (cross-iteration merging depends on iters,
        # which explain() doesn't take); the hypothetical count rides
        # along when the knob is off, mirroring the scheduler line
        swept = PB.sweep_plan(parts, n)
        nseg = sum(1 for p in parts if p[0] == "segment")
        nsw = sum(1 for p in swept if p[0] == "segment")
        if PB.sweep_enabled():
            lines.append(
                f"  sweep fusion: on (QUEST_SWEEP_FUSION=1): {nseg} "
                f"kernel segment(s) -> {nsw} sweep(s), {len(swept)} HBM "
                f"pass(es) per application")
            parts = swept
        else:
            lines.append(
                f"  sweep fusion: OFF (QUEST_SWEEP_FUSION=0); on, it "
                f"would merge {nseg} segment(s) into {nsw} sweep(s)")
        kernels = set()
        passes = 0
        for i, part in enumerate(parts):
            if part[0] == "segment":
                _, stages, _arrays = part
                kernels.add(tuple(stages))
                passes += 1
                mix = {}
                for st in stages:
                    name = type(st).__name__.removesuffix("Stage").lower()
                    if hasattr(st, "kind"):
                        name = f"{name}:{st.kind}"
                    mix[name] = mix.get(name, 0) + 1
                desc = " ".join(f"{k}x{v}" if v > 1 else k
                                for k, v in mix.items())
                lines.append(f"  [{i}] kernel segment  "
                             f"{len(stages)} stages  ({desc})")
            else:
                it = part[1]
                passes += 1
                what = (f"band q{it.ql}..q{it.ql + it.w - 1}"
                        if isinstance(it, F.BandOp) else
                        "diagonal" if isinstance(it, F.DiagItem)
                        else f"op {getattr(it.op, 'kind', '?')}")
                lines.append(f"  [{i}] XLA passthrough  {what}")
        moved = passes * pass_bytes
        lines.append(
            f"  total: {passes} HBM pass{'es' if passes != 1 else ''} "
            f"({_human_bytes(moved)} moved per application at {n}q), "
            f"{sum(1 for p in parts if p[0] == 'segment')} segments, "
            f"{len(kernels)} distinct kernels")
        if batch is not None:
            from quest_tpu.env import batch_bucket
            bucket = batch_bucket(batch)
            lines.append(
                f"  batched: B={batch} -> bucket {bucket} states per "
                f"launch (QUEST_BATCH_BUCKET); {passes} launch(es) per "
                f"application independent of B — "
                f"{_human_bytes(moved * bucket)} moved for the bucket")
        # chip-keyed constants (_COST_MODELS): each generation's entry
        # NAMES its provenance — v5e measured, v5p projected from
        # datasheet x measured derate; an unrecognized chip falls back
        # to v5e numbers WITH a caution (VERDICT r4 item 7). Only
        # consult the device when this process has ALREADY committed to
        # a backend: explain() is pure host math, and asking for
        # jax.devices() here would initialize the backend (and take the
        # chip) as a side effect of a planning call.
        kind = "?"
        try:
            # backends_are_initialized() is the named API for "has this
            # process committed to a backend" (pinned by
            # tests/test_docs.py::test_backend_probe_api so a JAX
            # upgrade that renames it fails loudly instead of silently
            # dropping the wrong-chip caution — ADVICE r4 item 3)
            from jax._src import xla_bridge as _xb
            if _xb.backends_are_initialized():
                kind = str(getattr(jax.devices()[0], "device_kind", "?"))
        except Exception:               # pragma: no cover - no backend
            pass
        model, matched = _cost_model_for(kind)
        lo, hi = _estimate_ms(parts, n, model)
        chip = "v5p" if model is _COST_MODELS["v5p"] else "v5e"
        tag = ("" if matched or kind == "?" else
               f" [CAUTION: no cost model for {kind!r} — using v5e "
               f"constants; treat as relative, not absolute]")
        lines.append(
            f"  estimated steady state on one {chip}: {lo:.1f}-{hi:.1f} "
            f"ms per application at HIGHEST "
            f"(constants: {model['provenance']}){tag}")
        transpile_line()
        plan_line()
        host_line()
        return "\n".join(lines)

    def explain_sharded(self, mesh, density: bool = False,
                        engine: str = "banded",
                        batch: int = None) -> str:
        """The distributed counterpart of explain(): lower (not compile)
        the sharded program for `mesh` and report the communication
        schedule XLA actually emitted — collective exchanges and their
        per-device ICI bytes, psum reductions, local band passes — plus
        the shard geometry. Derived from the lowered StableHLO, so it
        cannot drift from the engine (quest_tpu.parallel.introspect).
        The reference's exchange schedule is implicit in C control flow
        (QuEST_cpu_distributed.c:481-509) and cannot be asked for.

        DYNAMIC circuits (mid-circuit measurements / feedback) report
        through the measured engine's planner instead: per-stretch
        relabel events, kernel segments, and the psum-per-measurement
        schedule (parallel.introspect.sharded_measured_schedule)."""
        n = self.num_qubits * 2 if density else self.num_qubits
        if self._measure_count():
            from quest_tpu.parallel.introspect import (
                sharded_measured_schedule)
            # the static engines call the per-gate schedule 'pergate';
            # the dynamic compiler calls it 'xla' — accept both here
            dyn_engine = {"pergate": "xla"}.get(engine, engine)
            rec = sharded_measured_schedule(self.ops, n, density, mesh,
                                            engine=dyn_engine)
            return "\n".join([
                f"sharded DYNAMIC ({rec['engine']}) schedule for "
                f"{len(self.ops)} ops on {self.num_qubits} qubits over "
                f"{rec['devices']} devices"
                + (f" (density: {n}-qubit register)" if density else ""),
                f"  shard geometry: {rec['local_qubits']} local + "
                f"{rec['global_qubits']} device qubits, "
                f"{_human_bytes(rec['chunk_bytes'])} chunk per device",
                f"  {rec['measurements']} measurement(s) + "
                f"{rec['classical_ops']} feedback op(s) splitting "
                f"{rec['stretches']} static stretch(es)",
                f"  local band passes: {rec['local_band_passes']}"
                + (f" ({rec['kernel_segments']} kernel segments)"
                   if rec['kernel_segments'] else ""),
                f"  relabel events: {rec['relabel_events']}",
                _comm_plan_line(rec),
                f"  collective exchanges: {rec['collective_exchanges']} "
                f"({_human_bytes(rec['ici_bytes_per_device'])} ICI per "
                f"device per application)",
                f"  psum reductions: {rec['all_reduces']}",
            ])
        from quest_tpu.parallel.introspect import sharded_schedule

        rec = sharded_schedule(self.ops, n, density, mesh, engine=engine)
        if engine == "pergate":
            plan_lines = [f"  local ops: {rec['local_ops']}",
                          f"  device-qubit ops: {rec['global_ops']}"]
        else:
            sch = rec.get("scheduler", {})
            if sch.get("enabled"):
                sch_line = (f"  scheduler: on "
                            f"({sch.get('fused_ops', 0)} diagonal op(s) "
                            f"composed into {sch.get('fused_groups', 0)} "
                            f"group(s), {sch.get('hoisted', 0)} hoisted)")
            else:
                # the plan below is UNSCHEDULED — report the dry-run
                # counts as hypothetical, like explain() does
                sch_line = (f"  scheduler: OFF (QUEST_SCHEDULE=0); on, "
                            f"it would compose {sch.get('fused_ops', 0)} "
                            f"diagonal op(s) into "
                            f"{sch.get('fused_groups', 0)} group(s)")
            plan_lines = [
                sch_line,
                f"  local band passes: {rec['local_band_passes']}",
                f"  global-qubit items: {rec['global_qubit_items']}"]
            if "kernel_sweeps" in rec:
                plan_lines.append(
                    f"  local kernel sweeps: {rec['kernel_sweeps']} per "
                    f"device (from {rec['kernel_segments']} segment(s); "
                    f"QUEST_SWEEP_FUSION)")
            if batch is not None and "hbm_sweeps" in rec:
                from quest_tpu.env import AMP_AXIS, batch_bucket
                bucket = batch_bucket(batch)
                plan_lines.append(
                    f"  batched: B={batch} -> bucket {bucket} states "
                    f"ride each per-shard sweep; the batch axis stays "
                    f"LOCAL to the amplitude mesh (sharding "
                    f"P(None, None, {AMP_AXIS!r}) — no batch "
                    f"collectives), {rec['hbm_sweeps']} per-shard "
                    f"launch(es) independent of B")
        return "\n".join([
            f"sharded ({engine}) schedule for {len(self.ops)} ops on "
            f"{self.num_qubits} qubits over {rec['devices']} devices"
            + (f" (density: {n}-qubit register)" if density else ""),
            f"  shard geometry: {rec['local_qubits']} local + "
            f"{rec['global_qubits']} device qubits, "
            f"{_human_bytes(rec['chunk_bytes'])} chunk per device",
            *plan_lines,
            _comm_plan_line(rec),
            f"  collective exchanges: {rec['collective_exchanges']} "
            f"({_human_bytes(rec['ici_bytes_per_device'])} ICI per device "
            f"per application)",
            *([f"  of which relabel all-to-alls: {rec['all_to_alls']}"]
              if rec.get("all_to_alls") else []),
            f"  psum reductions: {rec['all_reduces']}",
        ])

    def compiled_sharded(self, n: int, density: bool, mesh, donate: bool = True):
        """Compiled explicit-distribution program (one shard_map over the
        whole circuit, reference-style ppermute schedule — see
        quest_tpu.parallel.sharded)."""
        self._reject_measure("compiled_sharded")
        from quest_tpu.parallel import sharded as S
        # the Mesh itself keys the cache: jax Mesh equality is by VALUE
        # (axis names/types, device shape + identity), so a rebuilt Mesh
        # over the same devices hits, while a same-shape Mesh over
        # different devices — or a GC'd-then-reused object id — never
        # aliases (the id(mesh) bug, VERDICT r3 weak item 2)
        key = ("sharded", n, density, mesh,
               donate, _engine_mode_key())
        fn = self._compiled.get(key)
        if fn is None:
            fn = S.compile_circuit_sharded(self.ops, n, density, mesh, donate)
            self._compiled[key] = fn
        return fn

    def compiled_sharded_banded(self, n: int, density: bool, mesh,
                                donate: bool = True):
        """Band-fusion engine over the device mesh (one shard_map program;
        see quest_tpu.parallel.sharded.compile_circuit_sharded_banded)."""
        self._reject_measure("compiled_sharded_banded")
        from quest_tpu.parallel import sharded as S
        key = ("sharded-banded", n, density, mesh, donate,
               _engine_mode_key())
        fn = self._compiled.get(key)
        if fn is None:
            fn = S.compile_circuit_sharded_banded(self.ops, n, density, mesh,
                                                  donate)
            self._compiled[key] = fn
        return fn

    def compiled_sharded_fused(self, n: int, density: bool, mesh,
                               donate: bool = True,
                               interpret: bool = False):
        """Pallas band-segment engine over the device mesh (local fused
        mega-kernel segments between explicit ppermute exchanges; see
        quest_tpu.parallel.sharded.compile_circuit_sharded_fused)."""
        from quest_tpu.parallel import sharded as S
        self._reject_measure("compiled_sharded_fused")
        key = ("sharded-fused", n, density, mesh, donate, interpret,
               _engine_mode_key())
        fn = self._compiled.get(key)
        if fn is None:
            fn = S.compile_circuit_sharded_fused(self.ops, n, density, mesh,
                                                 donate, interpret)
            self._compiled[key] = fn
        return fn

    def compiled_sharded_batched(self, batch: int, mesh,
                                 density: bool = False,
                                 donate: bool = True,
                                 interpret: bool = False):
        """BATCHED fused engine over the device mesh: one shard_map
        program applying this circuit to (B, 2, 2^n) planes whose
        AMPLITUDE axis is sharded and whose batch axis is kept LOCAL to
        every device (parallel.sharded.compile_circuit_sharded_fused_
        batched) — per-shard sweeps stream the whole bucket per launch,
        collectives vmap over the batch. Buckets and pads exactly like
        compiled_batched: calls sharing a bucket return the SAME
        wrapper (one compiled program per bucket)."""
        self._reject_measure("compiled_sharded_batched")
        from quest_tpu.env import batch_bucket
        from quest_tpu.parallel import sharded as S
        n = self.num_qubits * 2 if density else self.num_qubits
        bucket = batch_bucket(batch)
        key = ("sharded-batched", n, density, mesh, donate, interpret,
               bucket, _engine_mode_key())
        fn = self._compiled.get(key)
        if fn is not None:
            return fn
        inner = S.compile_circuit_sharded_fused_batched(
            self.ops, n, density, mesh, bucket, donate, interpret)
        wrapper = _bucketed_wrapper(inner, bucket,
                                    "compiled_sharded_batched")
        self._compiled[key] = wrapper
        return wrapper

    def apply_sharded_fused(self, q: Qureg, mesh, donate: bool = False,
                            interpret: bool = False) -> Qureg:
        """Apply via the Pallas fused shard_map engine."""
        if self.num_qubits != q.num_qubits:
            raise ValueError("circuit/register size mismatch")
        from quest_tpu.parallel import mesh as MM
        fn = self.compiled_sharded_fused(q.num_state_qubits, q.is_density,
                                         mesh, donate, interpret)
        amps = jax.device_put(q.amps, MM.amp_sharding(mesh))
        return q.replace_amps(fn(amps))

    def apply_sharded_banded(self, q: Qureg, mesh,
                             donate: bool = False) -> Qureg:
        """Apply via the band-fusion shard_map engine."""
        if self.num_qubits != q.num_qubits:
            raise ValueError("circuit/register size mismatch")
        from quest_tpu.parallel import mesh as MM
        fn = self.compiled_sharded_banded(q.num_state_qubits, q.is_density,
                                          mesh, donate)
        amps = jax.device_put(q.amps, MM.amp_sharding(mesh))
        return q.replace_amps(fn(amps))

    def compiled_sharded_measured(self, n: int, density: bool, mesh,
                                  donate: bool = True, engine: str = None,
                                  relabel: bool = None,
                                  interpret: bool = False):
        """Cached compile of the dynamic sharded program (see
        quest_tpu.parallel.sharded.compile_circuit_sharded_measured).
        engine: 'xla' (default) | 'banded' | 'fused'; relabel (default
        on for banded/fused) runs the layer-amortized relabel pass per
        measurement-free stretch."""
        from quest_tpu.parallel import sharded as S
        # the compiler's own defaulting, so equivalent calls share one
        # compiled program
        engine, relabel = S.resolve_measured_engine(engine, relabel)
        key_ = ("sharded-measured", n, density, mesh, donate, engine,
                relabel, interpret, _engine_mode_key())
        fn = self._compiled.get(key_)
        if fn is None:
            fn = S.compile_circuit_sharded_measured(
                self.ops, n, density, mesh, donate, engine=engine,
                relabel=relabel, interpret=interpret)
            self._compiled[key_] = fn
        return fn

    def apply_sharded_measured(self, q: Qureg, key, mesh,
                               donate: bool = False, engine: str = None,
                               relabel: bool = None,
                               interpret: bool = False):
        """Dynamic circuit over the device mesh: (register, outcomes).
        Mid-circuit measurement (psum probabilities, identical draws on
        every device) and classical feedback inside ONE shard_map
        program; measurement-free stretches relabel and fuse like the
        static engines (engine='banded'/'fused')."""
        from quest_tpu.parallel.mesh import amp_sharding
        if self.num_qubits != q.num_qubits:
            raise ValueError("circuit/register size mismatch")
        fn = self.compiled_sharded_measured(q.num_state_qubits,
                                            q.is_density, mesh, donate,
                                            engine, relabel, interpret)
        amps = jax.device_put(q.amps, amp_sharding(mesh))
        amps, outcomes = fn(amps, key)
        return q.replace_amps(amps), outcomes

    def apply_sharded(self, q: Qureg, mesh, donate: bool = False) -> Qureg:
        """Apply via the explicit shard_map engine on a mesh-sharded register."""
        if self.num_qubits != q.num_qubits:
            raise ValueError("circuit/register size mismatch")
        from quest_tpu.parallel import mesh as MM
        fn = self.compiled_sharded(q.num_state_qubits, q.is_density, mesh, donate)
        amps = jax.device_put(q.amps, MM.amp_sharding(mesh))
        return q.replace_amps(fn(amps))


# ---------------------------------------------------------------------------
# Benchmark circuit generators
# ---------------------------------------------------------------------------


def random_circuit(num_qubits: int, depth: int, seed: int = 0,
                   entangler: str = "cz") -> Circuit:
    """RCS-style benchmark circuit: layers of random single-qubit rotations
    followed by a brick pattern of entangling gates (BASELINE.json config
    '30-qubit random-circuit-sampling statevector')."""
    rng = np.random.default_rng(seed)
    c = Circuit(num_qubits)
    for d in range(depth):
        for q in range(num_qubits):
            angle = float(rng.uniform(0, 2 * np.pi))
            kind = rng.integers(0, 3)
            if kind == 0:
                c.rx(q, angle)
            elif kind == 1:
                c.ry(q, angle)
            else:
                c.rz(q, angle)
        start = d % 2
        for q in range(start, num_qubits - 1, 2):
            if entangler == "cz":
                c.cz(q, q + 1)
            else:
                c.cnot(q, q + 1)
    return c


def qft_circuit(num_qubits: int) -> Circuit:
    """Quantum Fourier transform (BASELINE.json config 'distributed QFT')."""
    c = Circuit(num_qubits)
    for q in reversed(range(num_qubits)):
        c.h(q)
        for j in range(q):
            angle = np.pi / (1 << (q - j))
            c._add("allones", (j, q), np.exp(1j * angle))
    for q in range(num_qubits // 2):
        c.swap(q, num_qubits - 1 - q)
    return c
