"""Pallas TPU mega-kernel over band-fusion plans: many gates per HBM pass.

The XLA band engine (quest_tpu/ops/fusion.py + apply_band) costs one full
memory pass per band contraction — and for bands whose bits are not the
minor axis, XLA inserts full-state transposes around the matmul (measured:
those bands access 1.6-2x the state bytes; scripts/probe_band_hlo.py).
This kernel runs a whole SEGMENT of operators in one pass; relayout inside
the block costs VPU/XLU shuffles instead of HBM traffic. It is the
TPU-native analogue of the reference's single-pass OpenMP/CUDA per-gate
kernels (QuEST_cpu.c:1656-3620, QuEST_gpu.cu) — except one pass covers
MANY gates.

Block geometry. The (2, 2^n) split re/im planes are viewed as
(2, ...row axes..., 128): qubits 0..6 are the lane axis; row bits make up
the rest. Each grid step's block holds:

  inner rows   the lowest `inner_bits` row bits, contiguous —
               qubits 7..7+inner_bits-1
  scattered    up to SCATTER_MAX individual HIGH row bits, each exposed
               as its own size-2 axis of the view so the block contains
               BOTH butterfly halves of that qubit (the BlockSpec gathers
               the strips in one DMA) — this is how gates on ARBITRARY
               high qubits stay fused, the on-chip analogue of the
               reference's pair-rank exchange (getChunkPairId,
               QuEST_cpu_distributed.c:303-312)

Stages inside the block:
  b0   composed 128x128 operator on the lane band: X @ G^T on the MXU
  b1   composed operator on the sublane band (qubits 7..13): cheap
       (A,d,l)->(A*l,d) tile relayout, one LARGE-M MXU dot X @ G^T, undo
       (the (d,A*l) small-m orientation measured +17 ms/pass vs +4)
  scb  composed 2^w x 2^w operator on a HIGH band (qubits 14+): ONE MXU
       dot over the band's w merged scattered axes — a whole layer of
       gates on qubits 14..20 costs one dot instead of 7 serial VPU
       butterflies (measured 4x on those bands at 29q)
  sc   composed 2x2 on one scattered qubit (width-1 remainder bands):
       elementwise butterfly
  diagonal / all-ones / parity phases on ANY qubits (global row ids from
       the grid indices) — these never break a segment
  controls anywhere become lane/global-row-id masks

Operator matrices ride along as kernel INPUTS, not baked constants, so
segments with identical structure but different angles compile to the
same kernel (layer reuse across RCS depth).

A segment ends when the next stage's scattered row bits would exceed
SCATTER_MAX, or when the in-block row bits (sublane floor from b1/pair
stages + scattered axes) would exceed MAX_BLOCK_ROW_BITS — the VMEM
budget; a b1 stage and a full 7-bit scb therefore land in separate
segments. Ops the kernel cannot host at all (>=3-target cross-band
unitaries, oversized single stages under a caller-shrunk scatter budget)
run as XLA band passthroughs between segments (quest_tpu/circuit.py
compiled_fused).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import os
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from quest_tpu import precision, profiling
from quest_tpu.ops import fusion as F


LANE_QUBITS = 7
LANES = 1 << LANE_QUBITS
SUBLANE_TOP = 2 * LANE_QUBITS  # first qubit above the sublane band
TILE_QUBITS = LANE_QUBITS + 3  # qubits inside one (8, 128) f32 memory tile
ROWS_EFF_BITS = 12    # log2 of rows held per block (scattered x inner):
# (2, 4096, 128) f32 = 4 MiB per block buffer; with Pallas double-buffering
# and stage temporaries this stays within VMEM_LIMIT_BYTES
SCATTER_MAX = 7       # scattered row bits per segment: enough for one
# full high band as an scb stage
MAX_BLOCK_ROW_BITS = 13  # cap on in-block row bits (sublane floor +
# scattered axes) under the GRID driver: a 2^13-row block is
# 2 x 8192 x 128 f32 = 8 MiB; the automatic pipeline holds it
# double-buffered in+out plus stage temporaries (measured: 2^14 rows hit
# 118 MiB of scoped VMEM and failed to compile)
PIPELINED_MAX_BLOCK_ROW_BITS = 13  # the pipelined driver's in-place
# slots halve BLOCK buffer memory, but 2^14-row blocks still fail on
# chip: Mosaic's register allocator spills ~96 MiB of block-sized SSA
# values for the stage chain (measured r4: 144.12 MiB total vs the
# 128 MiB physical VMEM; chunking the b1 contraction did not move it —
# the spills are chain-wide, not per-stage). A b1 stage and a full
# 7-bit scb therefore stay in separate passes on EVERY driver; do not
# retry without evidence the spill behavior changed.
MAX_SEGMENT_STAGES = 32  # stages per kernel launch: operand blocks are
# resident in VMEM (a 128x128 operator pair is 131 KiB), so unbounded
# deep circuits at small n — where few flushes happen naturally — would
# otherwise accumulate hundreds of operands per segment
VMEM_LIMIT_BYTES = 100 * (1 << 20)  # v5e has 128 MiB VMEM; the default
# 16 MiB scoped limit rejects multi-stage kernels (measured round 1/2)
MULTIPHASE_TRIGFREE_MAX = 3  # MultiPhaseStage terms up to which the
# stage selects among products of unit factors instead of taking cos/sin
# of a per-element angle sum (_apply_multiphase_stage). Measured on v5e at
# 30q behind an scb128 stage (docs/KERNELS.md): at m = 3 the select tree
# is 15 ms (allones) and 9 ms (parity) cheaper; at m = 4 parity groups
# are 8 ms dearer, and at m = 8 its temporaries overflow scoped VMEM


def plan_bands(n: int) -> List[Tuple[int, int]]:
    """Band layout matching the kernel's reach: 7-qubit bands everywhere.
    The lane band contracts on the lane axis, the sublane band on the
    sublane axis, and each HIGH band becomes one MXU contraction over its
    merged scattered axes (an 'scb' stage) — so a whole layer of gates on
    qubits 14..20 costs ONE dot instead of 7 serial VPU butterflies
    (measured 4x on those bands at 29q). Width-1 remainders stay
    scattered-axis butterflies."""
    bands = []
    ql = 0
    while ql < n:
        w = min(LANE_QUBITS, n - ql)
        bands.append((ql, w))
        ql += w
    return bands


# ---------------------------------------------------------------------------
# stage descriptors (structure only — matrices are kernel inputs)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MatStage:
    kind: str                  # 'b0' | 'b1' | 'sc' | 'scb'
    dim: int                   # operator dimension D
    real_only: bool
    lane_preds: Tuple[Tuple[int, int], ...]   # (lane bit, want)
    row_preds: Tuple[Tuple[int, int], ...]    # (GLOBAL row bit, want)
    bit: int = -1              # 'sc': the GLOBAL row bit this acts on;
    # 'scb': the LOWEST of the log2(dim) contiguous row bits the composed
    # high-band operator contracts over (each a scattered block axis)


@dataclasses.dataclass(frozen=True)
class PhaseStage:
    """allones phase: multiply amplitudes whose condition bits match by
    (tre + i*tim). The stage carries NO structure at all — the phase
    value AND the bit predicates ride in one (1, 8) kernel input
    [tre, tim, lane_mask, lane_want, row_mask_lo, row_mask_hi,
    row_want_lo, row_want_hi] (row masks split at bit 15 so each half
    is an exact integer in f32). Every phase stage in a program
    therefore shares ONE compiled kernel structure: QFT-30's 435
    distinct controlled-phase qubit pairs cost one Mosaic compile, not
    one per pair (measured: 14 -> 8 distinct kernels for the whole
    QFT-30 schedule)."""


@dataclasses.dataclass(frozen=True)
class ParityStage:
    """exp(-i angle/2 Z...Z); like PhaseStage, carries no structure:
    the (1, 8) kernel input is [cos, sin, lane_mask, row_mask_lo,
    row_mask_hi, 0, 0, 0] of the half angle and the target-bit masks
    (parity computed in-kernel by XOR-folding the masked index bits)."""


@dataclasses.dataclass(frozen=True)
class PairStage:
    """General (possibly non-unitary) 2-qubit matrix on (q_op, q_sliced):
    the sliced qubit's two halves select 2x2 blocks M[r][c], each applied
    on the op-side qubit — out_r = sum_c M_rc x_c. This is how Kraus
    superoperators on the doubled density register (targets (t, t+N),
    ref QuEST_common.c:540-673) stay fused at any register size.

    op_kind: 'lane' (M_rc embedded 128x128, right-matmul) |
             'b1'   (M_rc embedded 128x128 on the sublane axis) |
             'sc'   (M_rc 2x2 scalars; q_op has its own scattered axis)
    sliced_kind: 'scat' (own scattered axis) | 'sub' (sublane bit; only
             valid when op_kind == 'lane')."""
    op_kind: str
    op_dim: int                               # 128 or 2
    op_bit: int                               # 'sc': GLOBAL row bit
    sliced_kind: str
    sliced_bit: int                           # GLOBAL row bit
    real_only: bool
    lane_preds: Tuple[Tuple[int, int], ...]
    row_preds: Tuple[Tuple[int, int], ...]


@dataclasses.dataclass(frozen=True)
class MultiPhaseStage:
    """A scheduler-composed GROUP of unit phases in ONE stage: each row
    contributes an angle (an allones row adds its theta where all masked
    bits are 1; a parity row adds -half*(-1)^par), and the group costs
    ONE stage against MAX_SEGMENT_STAGES instead of m. Up to
    MULTIPHASE_TRIGFREE_MAX rows the kernel selects each element's
    product of the rows' unit factors from the 2^m products (cos/sin
    taken of the m angles alone); wider groups accumulate
    the angle per element and pay one full-tile cos/sin (the chip's
    crossover, docs/KERNELS.md). The (m, 8) operand rows are
    [angle, lane_mask, row_mask_lo, row_mask_hi, 0, 0, 0, 0] (row masks
    split at bit 15 so each half is exact in f32); `forms` carries the
    static per-row interpretation: 'a' = allones, 'p' = parity."""
    forms: Tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class BatchSelStage:
    """Per-STATE 2x2 operator on one GLOBAL qubit — the batched
    trajectory engine's channel stage (docs/BATCHING.md). The operand is
    a (batch, 8) f32 table of rows [g00re, g00im, g01re, g01im, g10re,
    g10im, g11re, g11im]: each state's drawn Kraus branch (with the
    1/sqrt(p) renormalization folded in) rides as its own row, and the
    kernel selects row `batch index` — a per-state one-hot select inside
    the sweep instead of a vmap of eager per-gate workers. The 2x2 stays
    UNEMBEDDED whatever the qubit position (scattered bits butterfly on
    per-state scalars; lane/sublane bits build their embedded operator
    in-kernel from the 8 scalars via iota masks, _batchsel_embed), so
    the operand is batch x 32 bytes for ANY qubit — a host-side
    embedding would cost batch x 128 KiB of VMEM for lane qubits.

    `index` is the channel ordinal in the program: plan-time operand
    arrays are ZERO PLACEHOLDERS sized (batch, 8) — they thread the
    batch through the sweep operand-byte budget — and the engines
    substitute the traced per-state operand for slot `index` at call
    time. `barrier` marks operands that depend on the PRE-channel state
    (general Kraus: Born probabilities need the state), which pins the
    stage to the FRONT of its launch: segment_plan flushes before it and
    sweep_plan never merges its segment into an earlier one. Unitary
    mixtures (state-independent probabilities) set barrier=False and
    fuse anywhere."""
    qubit: int
    index: int
    barrier: bool = True


@dataclasses.dataclass(frozen=True)
class ChannelItem:
    """Plan-stream marker for a batched per-state channel on GLOBAL
    qubit `qubit` (trajectories.run_batched interleaves these with the
    fusion plan's items); segment_plan turns each into a BatchSelStage
    with a (batch, 8) placeholder operand."""
    qubit: int
    index: int
    barrier: bool = True

    def qubits(self):
        return (self.qubit,)


@dataclasses.dataclass(frozen=True)
class DiagVecStage:
    """General k-qubit diagonal: multiply each amplitude by the entry
    selected by its target-bit pattern (identity where controls unmet).
    Entry index bit j corresponds to targets[j]; the (2, 2^k) re/im
    entry table rides as a kernel input."""
    targets: Tuple[int, ...]                  # GLOBAL qubits
    lane_preds: Tuple[Tuple[int, int], ...]
    row_preds: Tuple[Tuple[int, int], ...]


# ---------------------------------------------------------------------------
# segmentation of a fusion plan
# ---------------------------------------------------------------------------


def _split_preds(preds):
    lane_p, row_p = [], []
    for q, s in preds:
        if q < LANE_QUBITS:
            lane_p.append((q, s))
        else:
            row_p.append((q - LANE_QUBITS, s))
    return tuple(lane_p), tuple(row_p)


def stage_requirements(stages) -> Tuple[set, int]:
    """(scattered GLOBAL row bits, sublane floor) a stage list needs
    resident in one block — the block-geometry contract shared by
    compile_segment (which sizes the block from it) and the sweep-fusion
    layer (which merges segments only when the UNION still fits the
    budgets). One accounting, two consumers, so the merge rule cannot
    drift from what the kernel actually allocates."""
    scat: set = set()
    floor = 0
    for st in stages:
        if isinstance(st, MatStage):
            if st.kind == "sc":
                scat.add(st.bit)
            elif st.kind == "scb":
                scat |= set(range(st.bit, st.bit + st.dim.bit_length() - 1))
            elif st.kind == "b1":
                floor = max(floor, st.dim.bit_length() - 1)
        elif isinstance(st, PairStage):
            if st.sliced_kind == "scat":
                scat.add(st.sliced_bit)
            if st.op_kind == "sc":
                scat.add(st.op_bit)
            if st.op_kind == "b1":
                floor = max(floor, LANE_QUBITS)
            if st.sliced_kind == "sub":
                floor = max(floor, st.sliced_bit + 1)
        elif isinstance(st, BatchSelStage):
            if st.qubit >= SUBLANE_TOP:
                scat.add(st.qubit - LANE_QUBITS)
            elif st.qubit >= LANE_QUBITS:
                # sublane bit j contracts the lowest j+1 row bits
                floor = max(floor, st.qubit - LANE_QUBITS + 1)
    return scat, floor


def max_block_row_bits() -> int:
    """The in-block row-bit budget for the ACTIVE kernel driver. Both
    budgets are currently 13 — the pipelined driver's in-place slots
    were expected to afford a 14th bit but measured out on chain-wide
    register spills (see PIPELINED_MAX_BLOCK_ROW_BITS) — but planning
    keeps asking per driver so a future driver with a real memory edge
    changes ONE constant, not the planner."""
    return (PIPELINED_MAX_BLOCK_ROW_BITS
            if _driver_override() == "pipelined" else MAX_BLOCK_ROW_BITS)


def segment_plan(items: Sequence, n: int, scatter_max: int = SCATTER_MAX,
                 batch: int = 1, attr: Optional[list] = None,
                 vmem_budget: Optional[int] = None):
    """Split fusion-plan items into kernel segments and XLA passthroughs.
    Returns a list of ("segment", [stages], [op_arrays]) and
    ("xla", item) entries, in program order. `batch` sizes the
    (batch, 8) zero-placeholder operands of ChannelItem stages (batched
    trajectory channels) — the one place the batch enters the plan's
    operand-byte accounting; all other stage operands are shared across
    the batch and stay batch-independent. `attr`, when a list, receives
    one tuple of input ITEM indices per emitted part (the durable
    executor's elastic cut-boundary attribution, composed with
    fusion.plan's per-item op attribution — docs/RESILIENCE.md
    §elastic). A segment also flushes before a stage that would take its
    VMEM price past `vmem_budget` (vmem_fits; default
    VMEM_LIMIT_BYTES)."""
    parts: List = []
    part_src: List[tuple] = []      # item indices per emitted part
    seg_src: List[int] = []         # item indices in the open segment
    cur_item = -1
    stages: List = []
    arrays: List = []
    scat_bits: set = set()
    b1_floor = 0    # in-block sublane bits forced by b1/pair stages
    row_budget = max_block_row_bits()

    def flush():
        nonlocal stages, arrays, scat_bits, b1_floor, seg_src
        if stages:
            parts.append(("segment", stages, arrays))
            part_src.append(tuple(seg_src))
            stages, arrays = [], []
        seg_src = []
        scat_bits = set()
        b1_floor = 0

    def push(stage, arr):
        nonlocal scat_bits, b1_floor
        if stages and not vmem_fits(stages + [stage], n, vmem_budget,
                                    batch):
            flush()
            scat, floor = stage_requirements([stage])
            scat_bits, b1_floor = set(scat), floor
        stages.append(stage)
        seg_src.append(cur_item)
        arrays.append(arr)

    def emit_xla(it):
        parts.append(("xla", it))
        part_src.append((cur_item,))

    def reserve(bits=frozenset(), floor=0):
        """Claim scattered row bits / a sublane-floor for the next stage,
        flushing first if the block would outgrow its VMEM budget
        (MAX_BLOCK_ROW_BITS rows — the kernel stack holds the block
        double-buffered in+out plus stage temporaries) or its scattered-
        axis budget. Returns False — claiming nothing — when the stage's
        OWN requirement exceeds the budgets even in a fresh segment (the
        caller must fall back to an XLA passthrough)."""
        nonlocal scat_bits, b1_floor
        if (len(set(bits)) > scatter_max
                or floor + len(set(bits)) > row_budget):
            return False
        new_scat = scat_bits | set(bits)
        new_floor = max(b1_floor, floor)
        if (len(new_scat) > scatter_max
                or new_floor + len(new_scat) > row_budget):
            flush()
            new_scat = set(bits)
            new_floor = floor
        scat_bits = new_scat
        b1_floor = new_floor
        return True

    for cur_item, it in enumerate(items):
        if len(stages) >= MAX_SEGMENT_STAGES:
            flush()
        if isinstance(it, ChannelItem):
            # batched per-state channel: a barrier channel's operand is
            # computed from the state BETWEEN launches, so the running
            # segment flushes first and the stage opens a fresh one
            # (following stages still fuse in after it); a mixture
            # channel's operand depends only on the per-state keys and
            # fuses like any stage
            if it.barrier:
                flush()
            q = it.qubit

            def reserve_channel():
                if q >= SUBLANE_TOP:
                    return reserve(bits=(q - LANE_QUBITS,))
                if q >= LANE_QUBITS:
                    return reserve(floor=q - LANE_QUBITS + 1)
                return True
            if not reserve_channel():
                # only reachable under a caller-shrunk scatter budget;
                # a single channel's bit/floor always fits a fresh
                # segment, so a failed retry means the budget cannot
                # hold ANY channel stage — refuse loudly (a real raise,
                # not an assert: appending an unreserved stage would
                # silently corrupt the block geometry under python -O)
                flush()
                if not reserve_channel():
                    raise ValueError(
                        f"channel qubit {q} does not fit an empty "
                        f"segment under the caller's scatter budget")
            push(BatchSelStage(q, it.index, it.barrier),
                 np.zeros((batch, 8), dtype=np.float32))
            continue
        if isinstance(it, F.BandOp):
            lane_p, row_p = _split_preds(it.preds)
            if it.ql == 0:
                kind, bit = "b0", -1
                g = it.gre.T + 1j * it.gim.T       # X @ G^T form
            elif it.ql == LANE_QUBITS:
                kind, bit = "b1", -1
                # X @ G^T form, pre-transposed on the host like b0's —
                # the kernel never pays a per-block gate transpose
                g = (it.gre + 1j * it.gim).T
                reserve(floor=it.w)
            elif it.w == 1:
                kind, bit = "sc", it.ql - LANE_QUBITS
                g = it.gre + 1j * it.gim
                if not reserve(bits=(bit,)):
                    flush()
                    emit_xla(it)
                    continue
            else:                  # high band: one MXU dot over its
                kind = "scb"       # merged scattered axes
                bit = it.ql - LANE_QUBITS
                g = it.gre + 1j * it.gim
                w = it.w
                # a run that only mixed SOME of the band's qubits (QFT's
                # per-qubit Hadamards, sparse circuits) is often an exact
                # embedding over a narrower sub-range: contract only the
                # spanning sub-band — a 2x2 butterfly instead of a padded
                # 128-dot for a lone gate, fewer scattered axes always
                nd = sorted(q - it.ql for q in it.nondiag
                            if it.ql <= q < it.ql + it.w)
                if nd and (nd[0] > 0 or nd[-1] < it.w - 1):
                    j0, w2 = nd[0], nd[-1] - nd[0] + 1
                    idx = [x << j0 for x in range(1 << w2)]
                    sub = g[np.ix_(idx, idx)]
                    if np.allclose(g, F.embed_operator(
                            sub, list(range(j0, j0 + w2)), [], [], it.w)):
                        kind = "scb" if w2 > 1 else "sc"
                        bit = bit + j0
                        g = sub
                        w = w2
                if not reserve(bits=range(bit, bit + w)):
                    flush()
                    emit_xla(it)
                    continue
                # do NOT Kron-split a factorizable band operator into
                # narrow per-factor dots: measured r4, a narrow scb's
                # MXU time is ~flat in d (~40 ms/stage at 30q — a
                # small-M dot idles most of the systolic array, so time
                # scales with output size, not MACs), and splitting one
                # 42.6 ms d=128 stage into d4+d4+d8 measured 161 ms.
                # The single wide dot is already the cheapest form.
            real_only = bool(np.all(g.imag == 0.0))
            if kind == "scb" and g.shape[0] == LANES:
                # X @ G^T form for the full-width band, matching the
                # kernel's large-d mirrored frame (small d keeps the
                # left-dot: its dot is cheap and the 8<->128 tile swaps
                # of the mirror are not — measured 538 ms/application
                # when applied to a d=8 stage)
                g = g.T
            # keep operator arrays HOST-side (numpy): as closure
            # constants they upload with the program instead of occupying
            # HBM and round-tripping device->host at trace time
            push(MatStage(kind, g.shape[0], real_only, lane_p, row_p, bit),
                 np.stack([g.real, g.imag]).astype(np.float32))
            continue
        if isinstance(it, F.DiagItem):
            op = it.op
            targets = tuple(op.targets)
            if op.kind == "parity":
                half = float(op.operand) / 2.0
                lm = sum(1 << q for q in targets if q < LANE_QUBITS)
                rm = sum(1 << (q - LANE_QUBITS) for q in targets
                         if q >= LANE_QUBITS)
                push(ParityStage(), np.array(
                    [[np.cos(half), np.sin(half), lm,
                      rm & 0x7FFF, rm >> 15, 0, 0, 0]], dtype=np.float32))
                continue
            if op.kind == "diagonal":
                parts_rel = getattr(op, "parts", ())
                if parts_rel:
                    # scheduler-composed phase group (fusion.ComposedDiag
                    # with target-relative parts): one additive
                    # MultiPhaseStage instead of a 2^k select chain
                    rows, forms = [], []
                    for form, bits, val in parts_rel:
                        qs = [targets[b] for b in bits]
                        lm = sum(1 << q for q in qs if q < LANE_QUBITS)
                        rm = sum(1 << (q - LANE_QUBITS) for q in qs
                                 if q >= LANE_QUBITS)
                        ang = val if form == "allones" else -val / 2.0
                        rows.append([ang, lm, rm & 0x7FFF, rm >> 15,
                                     0, 0, 0, 0])
                        forms.append("a" if form == "allones" else "p")
                    push(MultiPhaseStage(tuple(forms)),
                         np.array(rows, dtype=np.float32))
                    continue
                d = np.asarray(op.operand, dtype=np.complex128).reshape(-1)
                lane_p, row_p = _split_preds(
                    tuple(zip(op.controls, op.cstates or
                              (1,) * len(op.controls))))
                push(DiagVecStage(targets, lane_p, row_p),
                     np.stack([d.real, d.imag]).astype(np.float32))
                continue
            if op.kind == "allones" and isinstance(
                    op.operand, (int, float, complex)):
                bits = targets + tuple(op.controls)
                want = (1,) * len(targets) + (tuple(op.cstates) or
                                              (1,) * len(op.controls))
                lm = lw = rm = rw = 0
                for q, s in zip(bits, want):
                    if q < LANE_QUBITS:
                        lm |= 1 << q
                        lw |= s << q
                    else:
                        rm |= 1 << (q - LANE_QUBITS)
                        rw |= s << (q - LANE_QUBITS)
                t = complex(op.operand)
                push(PhaseStage(), np.array(
                    [[t.real, t.imag, lm, lw, rm & 0x7FFF, rm >> 15,
                      rw & 0x7FFF, rw >> 15]], dtype=np.float32))
                continue
            flush()
            emit_xla(it)
            continue
        if isinstance(it, F.PassOp):
            st = _try_pair_stage(it, scatter_max)
            if st is not None:
                stage, arr, new_scat = st
                floor = 0
                if stage.op_kind == "b1":
                    floor = LANE_QUBITS
                if stage.sliced_kind == "sub":
                    floor = max(floor, stage.sliced_bit + 1)
                if reserve(bits=new_scat or frozenset(), floor=floor):
                    push(stage, arr)
                    continue
        flush()
        emit_xla(it)
    flush()
    if attr is not None:
        attr.extend(part_src)
    return parts


def _try_pair_stage(it, scatter_max):
    """PassOp -> (PairStage, operand array, scat bits needed) when the op
    is an uncontrolled 2-target matrix whose qubits the kernel can reach;
    None otherwise."""
    op = it.op
    if op.kind != "matrix" or len(op.targets) != 2 or op.controls:
        return None
    m = np.asarray(op.operand)
    if m.shape != (4, 4) or not np.issubdtype(m.dtype, np.number):
        return None
    qa, qb = op.targets           # matrix bit 0 = qa, bit 1 = qb

    def locate(q):
        if q < LANE_QUBITS:
            return "lane"
        if q < SUBLANE_TOP:
            return "sub"
        return "scat"

    la, lb = locate(qa), locate(qb)
    # pick the sliced qubit: prefer a scattered one; a sublane qubit may
    # only be sliced when the op side is a lane qubit
    if lb == "scat":
        q_op, q_sl, bit_op = qa, qb, 0
    elif la == "scat":
        q_op, q_sl, bit_op = qb, qa, 1
    elif la == "lane" and lb == "sub":
        q_op, q_sl, bit_op = qa, qb, 0
    elif lb == "lane" and la == "sub":
        q_op, q_sl, bit_op = qb, qa, 1
    else:
        return None               # same-band pairs are composed upstream
    op_loc = locate(q_op)
    sliced_kind = "scat" if locate(q_sl) == "scat" else "sub"

    need = set()
    if sliced_kind == "scat":
        need.add(q_sl - LANE_QUBITS)
    if op_loc == "scat":
        need.add(q_op - LANE_QUBITS)
    if len(need) > scatter_max:
        return None

    m = m.astype(np.complex128)
    blocks = np.empty((2, 4), dtype=object)
    for r in range(2):
        for c in range(2):
            sub = np.empty((2, 2), dtype=np.complex128)
            for ao in range(2):
                for ai in range(2):
                    row = (ao << bit_op) | (r << (1 - bit_op))
                    col = (ai << bit_op) | (c << (1 - bit_op))
                    sub[ao, ai] = m[row, col]
            if op_loc == "lane":
                emb = _embed_2x2(sub, q_op).T            # X @ G^T form
            elif op_loc == "sub":
                emb = _embed_2x2(sub, q_op - LANE_QUBITS).T  # X @ G^T form
            else:
                emb = sub
            blocks[0, r * 2 + c] = emb.real.astype(np.float32)
            blocks[1, r * 2 + c] = emb.imag.astype(np.float32)
    d = blocks[0, 0].shape[0]
    arr = np.stack([np.stack(list(blocks[p])) for p in range(2)])
    kind = {"lane": "lane", "sub": "b1", "scat": "sc"}[op_loc]
    real_only = bool(np.all(m.imag == 0.0))
    st = PairStage(kind, d, q_op - LANE_QUBITS if op_loc == "scat" else -1,
                   sliced_kind, q_sl - LANE_QUBITS, real_only, (), ())
    return st, arr, (need if need else None)


def _embed_2x2(sub, pos):
    """Embed a 2x2 at bit `pos` of a 7-bit space (lane or sublane)."""
    return F.embed_operator(sub, [pos], [], [], LANE_QUBITS)


# ---------------------------------------------------------------------------
# sweep fusion: many segments per HBM pass
# ---------------------------------------------------------------------------
#
# segment_plan flushes a segment whenever the NEXT stage's block
# requirement would outgrow the running budget — a greedy, forward-only
# split. Two split causes are recoverable after the fact:
#
#   * the MAX_SEGMENT_STAGES cap (a VMEM-operand-residency guard sized
#     for the worst case of 32 dense 128x128 operators — most stages'
#     operands are a few hundred bytes);
#   * the per-APPLICATION boundary: Circuit engines repeat the whole
#     part list `iters` times per dispatch, and the last segment of one
#     application is usually block-compatible with the first segment of
#     the next (the fusion-resistant chain benchmark is the extreme
#     case — every application is ONE segment, so consecutive
#     applications always merge until a sweep budget binds).
#
# sweep_plan re-merges CONSECUTIVE segment parts whose combined stage
# list still fits one block geometry: scattered-bit UNION within the
# scatter budget, sublane floor + scattered axes within the row budget
# (stage_requirements — the same accounting compile_segment sizes the
# block from), bounded stage count, and an explicit operand-byte budget
# replacing the blunt per-segment stage cap (operand arrays are
# whole-array VMEM-resident for the duration of a launch, next to the
# NBUF in-place block slots of the pipelined driver). Any non-segment
# part (an XLA passthrough) is a barrier. The merged kernel streams
# each state block HBM->VMEM ONCE, applies the whole stage sequence,
# and writes back — with the pipelined driver's double-buffered
# make_async_copy schedule overlapping the next block's DMA-in and the
# previous block's DMA-out with compute (docs/SWEEPS.md).

MAX_SWEEP_STAGES = 64   # stages per merged sweep: twice the per-segment
# cap. NOT validated on silicon — Mosaic register pressure grows with
# the stage chain (the 2^14-row spills of PIPELINED_MAX_BLOCK_ROW_BITS
# were chain-wide), so the first on-chip run should A/B this against
# QUEST_SWEEP_FUSION=0 before trusting deep sweeps.
SWEEP_OPERAND_BYTES = 48 * (1 << 20)  # VMEM operand budget per sweep
# under the LEGACY in-place slot driver: 100 MiB scoped limit minus
# NBUF (3) 8 MiB block slots and headroom for stage temporaries. 48 MiB
# holds ~380 dense 128x128 operator pairs — the stage cap binds first
# on real plans.
PIPELINE_IN_SLOTS = 2   # decoupled pipeline: VMEM slots per DMA ring.
PIPELINE_OUT_SLOTS = 2  # 2 in + 2 out = the read stream one full step
# ahead of compute and the write stream one full step behind, each on
# its OWN semaphore chain — in(s+1) never waits for out(s+1-nbuf) to
# drain (the in-place coupling that made nbuf=2 stall a full out-DMA
# per step: measured 23.8 vs 20.5 ms on the 28q bench).
PIPELINE_SWEEP_OPERAND_BYTES = 40 * (1 << 20)  # the decoupled rings
# hold 4 block slots (32 MiB at the 2^13-row cap) where the legacy
# driver held 3 (24 MiB); the operand budget gives the extra slot back
# so slots + operands + headroom still fit the 100 MiB scoped limit —
# the same stage_requirements()-anchored accounting, one more slot.


def pipeline_enabled() -> bool:
    """QUEST_FUSED_PIPELINE knob: '1' (default) runs the decoupled
    multi-buffer pipeline in the manually pipelined driver; '0' keeps
    the legacy in-place NBUF slot schedule (the silicon A/B control).
    Keyed in the registry, so every compiled-program cache key carries
    it (env.engine_mode_key; flip-audited in tests/test_lint.py)."""
    from quest_tpu.env import knob_value
    return knob_value("QUEST_FUSED_PIPELINE")


def decoupled_active() -> bool:
    """Whether compiled segments will run the decoupled pipeline: the
    manual slot driver is selected AND the pipeline knob is on. The ONE
    predicate shared by compile_segment (driver pick), sweep_plan's
    operand budget and pipeline_stats, so the planner, the budget and
    the introspection can never disagree about the active schedule."""
    return _driver_override() == "pipelined" and pipeline_enabled()


def sweep_operand_budget() -> int:
    """Effective per-sweep VMEM operand budget for the ACTIVE kernel
    schedule: the decoupled pipeline's 4 block slots leave
    PIPELINE_SWEEP_OPERAND_BYTES; the legacy in-place driver (knob off,
    or the grid driver) keeps the original SWEEP_OPERAND_BYTES —
    bit-for-bit the old plans when QUEST_FUSED_PIPELINE=0."""
    if decoupled_active():
        return PIPELINE_SWEEP_OPERAND_BYTES
    return SWEEP_OPERAND_BYTES


def sweep_plan(parts, n: int, *, scatter_max: int = SCATTER_MAX,
               row_budget: int = None, max_stages: int = MAX_SWEEP_STAGES,
               operand_bytes: int = None, attr: Optional[list] = None,
               part_attrs: Optional[Sequence] = None,
               vmem_budget: Optional[int] = None):
    """Merge consecutive ("segment", stages, arrays) parts of a
    segment_plan (or a concatenation of several applications' plans)
    into maximal single-launch sweeps, preserving program order.
    Returns the same part format, so every downstream consumer
    (compile_segment, _scan_partition, the sharded compilers) is
    unchanged. A merge must also keep the sweep's VMEM price within
    `vmem_budget` (vmem_fits; default VMEM_LIMIT_BYTES). `attr`, when a
    list, receives one tuple of attribution entries per OUTPUT part,
    merged from `part_attrs` (one tuple per input part, e.g.
    segment_plan's item attribution; defaults to each input part's own
    index) — the durable elastic layer's cut-boundary bookkeeping
    (docs/RESILIENCE.md §elastic)."""
    if row_budget is None:
        row_budget = max_block_row_bits()
    if operand_bytes is None:
        operand_bytes = sweep_operand_budget()
    if part_attrs is None:
        part_attrs = [(i,) for i in range(len(parts))]
    out = []
    out_attr: List[tuple] = []
    cur_scat: set = set()
    cur_floor = 0
    cur_bytes = 0
    for pi, part in enumerate(parts):
        src = tuple(part_attrs[pi])
        if part[0] != "segment":
            out.append(part)            # XLA passthrough: a sweep barrier
            out_attr.append(src)
            cur_scat, cur_floor, cur_bytes = set(), 0, 0
            continue
        stages, arrays = list(part[1]), list(part[2])
        scat, floor = stage_requirements(stages)
        nbytes = sum(a.nbytes for a in arrays)
        # a barrier BatchSelStage (general-Kraus channel) reads the
        # state as it stands at ITS launch boundary — segment_plan put
        # it first in its segment, and merging that segment into an
        # earlier one would slide stages in front of it. Batched operand
        # bytes (the (batch, 8) placeholders) already ride `nbytes`.
        barrier = any(isinstance(st, BatchSelStage) and st.barrier
                      for st in stages)
        if out and out[-1][0] == "segment" and not barrier:
            u_scat = cur_scat | scat
            u_floor = max(cur_floor, floor)
            prev = out[-1]
            if (len(prev[1]) + len(stages) <= max_stages
                    and len(u_scat) <= scatter_max
                    and u_floor + len(u_scat) <= row_budget
                    and cur_bytes + nbytes <= operand_bytes):
                if vmem_fits(prev[1] + stages, n, vmem_budget,
                             _batch_of(prev[1] + stages, prev[2] + arrays)):
                    out[-1] = ("segment", prev[1] + stages,
                               prev[2] + arrays)
                    out_attr[-1] = out_attr[-1] + src
                    cur_scat, cur_floor = u_scat, u_floor
                    cur_bytes += nbytes
                    continue
        out.append(("segment", stages, arrays))
        out_attr.append(src)
        cur_scat, cur_floor, cur_bytes = set(scat), floor, nbytes
    if attr is not None:
        attr.extend(out_attr)
    return out


def _batch_of(stages, arrays) -> int:
    """The batch a part's BatchSelStage placeholders are sized for."""
    return max((a.shape[0] for st, a in zip(stages, arrays)
                if isinstance(st, BatchSelStage)), default=1)


def record_vmem(items, n: int, parts, unroll: int = 1) -> None:
    """Into an active profiling recording: `quest.sweep_vmem_cut`, the
    launches the VMEM price adds to `parts` (the plan of `items` over
    `unroll` applications, swept if sweep fusion is on), against the
    same plan unpriced; and `quest.sweep_vmem_peak_mib`, the largest
    priced launch of `parts`."""
    if not profiling.active():
        return
    free = segment_plan(items, n, vmem_budget=np.inf) * unroll
    if sweep_enabled():
        free = sweep_plan(free, n, vmem_budget=np.inf)
    profiling.count("quest.sweep_vmem_cut", max(0, len(parts) - len(free)))
    profiling.peak("quest.sweep_vmem_peak_mib", max(
        (sweep_vmem_bytes(p[1], p[2], n)["total_bytes"]
         for p in parts if p[0] == "segment"),
        default=0) / (1 << 20))


def sweep_enabled() -> bool:
    """QUEST_SWEEP_FUSION knob: '1' (default) runs sweep fusion behind
    every fused-engine planner; '0' executes the raw segment plan.
    Keyed in the registry, so every compiled-program cache key carries
    it (env.engine_mode_key; flip-audited in tests/test_lint.py)."""
    from quest_tpu.env import knob_value
    return knob_value("QUEST_SWEEP_FUSION")


def maybe_sweep(parts, n: int):
    """sweep_plan honoring the QUEST_SWEEP_FUSION knob — the engines'
    entry point (stats consumers call sweep_plan/sweep_stats)."""
    if not sweep_enabled():
        return list(parts)
    return sweep_plan(parts, n)


def sweep_stats(parts) -> dict:
    """CPU-assertable sweep statistics of a (possibly swept) part list:
    every part — kernel sweep or XLA passthrough — is one full-state
    HBM pass per application, so `hbm_sweeps` is THE fused-engine
    memory-traffic metric (Circuit.plan_stats reports it next to the
    per-stage pass counts it undercuts)."""
    segs = [p for p in parts if p[0] == "segment"]
    return {
        "hbm_sweeps": len(parts),
        "kernel_sweeps": len(segs),
        "xla_passthroughs": len(parts) - len(segs),
        "sweep_stages": [len(p[1]) for p in segs],
    }


def batched_stats(parts, batch: int, bucket: int = None) -> dict:
    """CPU-assertable batched-plan statistics of a (swept) part list:
    every state in the bucket rides every sweep of the SAME part list,
    so `hbm_sweeps` (launches per application) does NOT scale with the
    batch — the whole point of the batched engine: a B-shot workload
    pays the unbatched plan's launch count once, with B states streamed
    back-to-back per launch (`states_per_sweep`). Surfaced through
    Circuit.plan_stats()["batched"] and trajectories.plan_stats; the
    B-independence golden lives in scripts/check_batch_golden.py."""
    sw = sweep_stats(parts)
    bucket = int(batch) if bucket is None else int(bucket)
    return {
        "batch": int(batch),
        "bucket": bucket,
        "states_per_sweep": bucket,
        "hbm_sweeps": sw["hbm_sweeps"],
        "kernel_sweeps": sw["kernel_sweeps"],
        "batched_stages": sum(
            1 for p in parts if p[0] == "segment"
            for st in p[1] if isinstance(st, BatchSelStage)),
    }


def sweep_steps(stages, n: int, batch: int = 1) -> int:
    """Grid steps one compiled sweep walks (blocks per state x batch)
    — from segment_geometry, the SAME resolution compile_segment sizes
    the kernel with, so the CPU-side schedule numbers below cannot
    drift from the lowered program."""
    geo = segment_geometry(stages, n)
    steps = 1
    for (lo, w) in geo.gaps:
        steps *= 1 << w
    return steps * int(batch)


def pipeline_stats(parts, n: int, batch: int = 1) -> dict:
    """CPU-assertable schedule of the decoupled sweep pipeline over a
    (swept) part list — pipeline_in_slots / pipeline_out_slots /
    pipeline_overlap_steps, the plan_stats()['fused'] keys
    scripts/check_sweep_golden.py gates without a chip.

    `pipeline_overlap_steps` is the MINIMUM read-ahead depth across the
    plan's kernel sweeps: steps the HBM read stream runs ahead of
    compute (in_slots - 1, clamped by the sweep's step count — a
    single-block sweep has nothing to read ahead). >= 1 on the
    headline plan means every launch overlaps the next block's DMA
    under the current block's stage loop.

    Returns {} when the decoupled pipeline is not the active schedule
    (QUEST_FUSED_PIPELINE=0 or the grid driver) — the knob-off fused
    record stays bit-for-bit the legacy one."""
    if not decoupled_active():
        return {}
    overlaps = []
    for p in parts:
        if p[0] != "segment":
            continue
        steps = sweep_steps(p[1], n, batch)
        overlaps.append(min(PIPELINE_IN_SLOTS, steps) - 1)
    return {
        "pipeline_in_slots": PIPELINE_IN_SLOTS,
        "pipeline_out_slots": PIPELINE_OUT_SLOTS,
        "pipeline_overlap_steps": min(overlaps) if overlaps else 0,
    }


def fused_record(parts, swept, n: int) -> dict:
    """The plan IR's 'fused' record — the fused engine's CPU-assertable
    geometry in ONE home (quest_tpu/plan.py builds it, Circuit.plan_stats
    re-emits it bit-for-bit): segment/passthrough counts and stage mix
    from the RAW segment plan `parts`, HBM sweep counts from the SWEPT
    plan, plus the decoupled pipeline's slot schedule
    (scripts/check_sweep_golden.py gates these keys)."""
    segs = sum(1 for p in parts if p[0] == "segment")
    sw = sweep_stats(swept)
    rec = {
        "kernel_segments": segs,
        "xla_passthroughs": len(parts) - segs,
        "full_state_passes": len(parts),
        "stages": sum(len(p[1]) for p in parts if p[0] == "segment"),
        "sweeps_enabled": sweep_enabled(),
        "hbm_sweeps": sw["hbm_sweeps"],
        "sweep_stages": sw["sweep_stages"],
    }
    rec.update(pipeline_stats(swept, n))
    return rec


# ---------------------------------------------------------------------------
# the VMEM price of a sweep
# ---------------------------------------------------------------------------
#
# A (rows_eff, 128) f32 value is rows_eff / 8 vregs, far more than the
# register file holds, so Mosaic keeps every block-sized value the stage
# chain has live in scoped VMEM, beside the slot buffers and the
# operands. The price counts those values in PLANES, one f32 plane of the
# block (rows_eff x 128 x 4 bytes): a base for the block as loaded and as
# stored, the largest working set one stage needs while it runs, and
# every stage's HELD planes, values that depend only on its operand and
# the row ids. Mosaic may compute those ahead of the chain and keep them
# all until their stages run: QFT-30's [scb128, 21 phase groups of 6-10
# terms, phase] asks 125.58M at 2^12 rows, 55 planes, where 16 groups
# ask 9 planes. The plane counts are calibrated against the scoped
# allocation Mosaic reports when compiling for a v5e (docs/KERNELS.md,
# "VMEM price", has every point); the price reads at or above it on
# each, and a stage chain whose price passes VMEM_LIMIT_BYTES is cut.

CHAIN_BASE_PLANES = 4.0   # the block's re/im as loaded and as stored


def _stage_planes(st) -> Tuple[float, float]:
    """(work, held) planes of one stage: `work` is live while it runs,
    `held` depends only on its operand and the row ids."""
    mask = 1.0 if (getattr(st, "lane_preds", ()) or
                   getattr(st, "row_preds", ())) else 0.0
    if isinstance(st, MultiPhaseStage):
        m = len(st.forms)
        if multiphase_trigfree(st):
            # the terms' bits and the select tree's widest level; the
            # factor it selects is held
            return m + (1 << m) + 2.0, 2.0
        # one term's mask and angle, the products; the angle sum and its
        # cos/sin are held
        return 5.0, 3.0
    if isinstance(st, PhaseStage):
        return 4.0, 0.5          # the products; the match mask
    if isinstance(st, ParityStage):
        return 4.0, 1.0          # the products; the signed sine
    if isinstance(st, DiagVecStage):
        return 5.0 + mask, 2.0   # the select, products; the factor
    # MatStage, PairStage, BatchSelStage: the Gauss products, the
    # contraction's frame in and out, and the mask of a controlled one
    return 8.0 + mask, 0.0


BUTTERFLY_PAIR_PLANES = 48.0  # two unmasked 'sc' butterflies on one
# bit back to back (a merge across applications of one circuit; fusion
# composes them within one): Mosaic asks 52 planes for the pair, at 2^10
# and at 2^11 rows, where either alone asks 8


def _unmasked_butterfly(st):
    return (isinstance(st, MatStage) and st.kind == "sc"
            and not st.lane_preds and not st.row_preds)


def chain_planes(stages) -> float:
    """Block planes the stage chain keeps live: the base, the largest
    working set of one stage and every stage's held planes."""
    planes = [_stage_planes(st) for st in stages]
    pair = any(_unmasked_butterfly(a) and _unmasked_butterfly(b)
               and a.bit == b.bit for a, b in zip(stages, stages[1:]))
    return (CHAIN_BASE_PLANES + max((w for w, _ in planes), default=0.0)
            + sum(h for _, h in planes)
            + (BUTTERFLY_PAIR_PLANES if pair else 0.0))


def _vmem_array_bytes(shape) -> int:
    """Bytes of an f32 array in VMEM, its last two dims padded to the
    (8, 128) tile."""
    *lead, a, b = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    return (int(np.prod(lead, dtype=np.int64)) * (-(-a // 8) * 8)
            * (-(-b // LANES) * LANES) * 4)


def _operand_shape(st, batch: int):
    """The shape of a stage's operand (see the stage dataclasses)."""
    if isinstance(st, MatStage):
        return (2, st.dim, st.dim)
    if isinstance(st, PairStage):
        return (2, 4, st.op_dim, st.op_dim)
    if isinstance(st, MultiPhaseStage):
        return (len(st.forms), 8)
    if isinstance(st, DiagVecStage):
        return (2, 1 << len(st.targets))
    if isinstance(st, BatchSelStage):
        return (batch, 8)
    return (1, 8)


def _slot_count(steps: int) -> int:
    if decoupled_active():
        return (min(PIPELINE_IN_SLOTS, steps)
                + min(PIPELINE_OUT_SLOTS, steps))
    if _driver_override() == "pipelined":
        return min(NBUF, steps)
    return 2                     # the grid driver's double buffering


def _vmem_price(stages, geo: "_Geometry", batch: int = 1) -> dict:
    steps = batch
    for (_, w) in geo.gaps:
        steps <<= w
    plane_bytes = geo.rows_eff * LANES * 4
    slots = _slot_count(steps)
    operand_bytes = sum(_vmem_array_bytes(_operand_shape(st, batch))
                        for st in stages)
    chain_bytes = int(chain_planes(stages) * plane_bytes)
    return {
        "block_bytes": 2 * plane_bytes,                  # f32 planes
        "slots": slots,
        "slot_bytes": slots * 2 * plane_bytes,
        "operand_bytes": operand_bytes,
        "chain_bytes": chain_bytes,
        "total_bytes": slots * 2 * plane_bytes + operand_bytes + chain_bytes,
        "budget_bytes": VMEM_LIMIT_BYTES,
    }


def vmem_fits(stages, n: int, budget: int = None, batch: int = 1) -> bool:
    """Whether one launch of `stages` prices within `budget` (default
    VMEM_LIMIT_BYTES) at the rows a block holds by default: the merge and
    flush rule of sweep_plan and segment_plan. A chain that does not fit
    is cut rather than run in smaller blocks: on a v5e, QFT-30's former
    sweep 2 took 927.7 ms as two sweeps of 2^12 rows and 1,627.4 ms as
    one of 2^11, where Mosaic keeps its wide phase groups' factor planes
    (docs/KERNELS.md, "VMEM price")."""
    price = _vmem_price(stages, segment_geometry(stages, n), batch)
    return price["total_bytes"] <= (VMEM_LIMIT_BYTES if budget is None
                                    else budget)


def sweep_vmem_bytes(stages, arrays, n: int,
                     rows_eff_bits: Optional[int] = None) -> dict:
    """CPU-assertable VMEM price of ONE compiled sweep launch, `stages`
    with their operand `arrays`, at the geometry compile_segment gives it
    (or at `rows_eff_bits`): slot buffers (the in/out rings of the
    decoupled pipeline, or the legacy NBUF in-place slots), operands
    padded to the (8, 128) tile, and the stage chain's block planes
    (chain_planes). `total_bytes <= budget_bytes` holds for every launch
    the planner makes (tests/test_sweeps.py), which is what lets
    sweep_plan merge on byte budgets instead of compiling to find out."""
    return _vmem_price(stages, segment_geometry(stages, n, rows_eff_bits),
                       _batch_of(stages, arrays))


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _Geometry:
    """Block/row geometry of one compiled segment."""
    n: int
    scat: Tuple[int, ...]       # scattered GLOBAL row bits, descending
    inner_bits: int
    gaps: Tuple[Tuple[int, int], ...]  # grid dims as (lo_bit, width_bits),
    # outermost first — one per gap above/between scattered axes plus the
    # gap between the lowest scattered bit and the inner rows

    @property
    def rows_eff(self) -> int:
        return 1 << (len(self.scat) + self.inner_bits)

    def view_dims(self):
        """Row-space view dims (outer->inner) and the block-shape entry
        per dim (1 for grid axes, full extent otherwise)."""
        dims, blocks = [], []
        for (lo, width) in self.gaps[:-1]:
            dims.append(1 << width)
            blocks.append(1)
            dims.append(2)
            blocks.append(2)
        lo, width = self.gaps[-1]
        dims.append(1 << width)
        blocks.append(1)
        dims.append(1 << self.inner_bits)
        blocks.append(1 << self.inner_bits)
        return tuple(dims), tuple(blocks)


def _geometry(n: int, scat_bits, rows_eff_bits: int) -> _Geometry:
    total_row_bits = n - LANE_QUBITS
    scat = tuple(sorted(scat_bits, reverse=True))
    h = len(scat)
    inner_bits = min(rows_eff_bits - h,
                     scat[-1] if scat else total_row_bits,
                     total_row_bits)
    # grid dims: the bit gaps (top .. scat[0]), (scat[a] .. scat[a+1]),
    # ..., (scat[-1] .. inner) — possibly zero-width (size-1 grid dims)
    gaps = []
    hi = total_row_bits
    for s in scat:
        gaps.append((s + 1, hi - s - 1))
        hi = s
    gaps.append((inner_bits, hi - inner_bits))
    return _Geometry(n, scat, inner_bits, tuple(gaps))


def _row_ids(geo: _Geometry, pids):
    """(rows_eff, 1) int32 GLOBAL row id of each block row."""
    base = 0
    for (lo, _), pid in zip(geo.gaps, pids):
        base = base + pid * (1 << lo)
    j = jax.lax.broadcasted_iota(jnp.int32, (geo.rows_eff, 1), 0)
    ids = base + (j & ((1 << geo.inner_bits) - 1))
    h = len(geo.scat)
    for a, s in enumerate(geo.scat):
        bit = (j >> (geo.inner_bits + h - 1 - a)) & 1
        ids = ids + (bit << s)
    return ids


def _lane_iota():
    return jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)


def _mask_of(row_ids, lane_preds, row_preds):
    mask = None
    if lane_preds:
        ids = _lane_iota()
        for bit, want in lane_preds:
            m = ((ids >> bit) & 1) == want
            mask = m if mask is None else (mask & m)
    if row_preds:
        for bit, want in row_preds:
            m = ((row_ids >> bit) & 1) == want
            mask = m if mask is None else (mask & m)
    return mask


def _cdot(contract, re, im, gre, gim, real_only):
    """Complex 'contract' of state planes with operator planes, via the
    Gauss 3-multiplication identity (3 MXU passes instead of 4):
      t1 = Gre x_re, t2 = Gim x_im, t3 = (Gre+Gim)(x_re+x_im)
      out_re = t1 - t2, out_im = t3 - t1 - t2."""
    if real_only:
        return contract(gre, re), contract(gre, im)
    t1 = contract(gre, re)
    t2 = contract(gim, im)
    t3 = contract(gre + gim, re + im)
    return t1 - t2, t3 - t1 - t2


def _mxu_dot_general(a, b, dnums):
    """State-amplitude dot at the session precision knob.

    HIGHEST (default): one f32 dot = 6 bf16 MXU passes, ~3e-7 relative
    error — full f32, matches the reference's PRECISION=1 envelope.
    HIGH: the double-bf16 3-pass scheme (a = a_hi + a_lo split by
    integer mantissa masking, keep the three highest-order products,
    f32 accumulation) — HALF the MXU passes of HIGHEST at ~2.3e-5
    relative error per 128-dot (measured ON CHIP against an f64
    oracle; docs/PRECISION.md). Mosaic does not
    lower Precision.HIGH, so the split is done explicitly here; XLA's
    own bf16_3x does the same thing on the banded/per-gate paths.
    DEFAULT: one bf16 pass, ~1e-3 — exposed but not recommended."""
    p = precision.matmul_precision()
    f32 = jnp.float32
    if p == jax.lax.Precision.HIGH:
        # Two hard-won ON-CHIP lessons in this scheme (both invisible to
        # interpret mode, caught by test_high_precision_tier_on_chip):
        #   1. operands must STAY f32 — explicit bfloat16 inputs make
        #      Mosaic accumulate the dot in bf16 as well, and a 128-term
        #      bf16 accumulator costs ~sqrt(128)*2^-8 ~ 4e-2 relative
        #      (measured 4.3e-2). A DEFAULT-precision f32 dot truncates
        #      the INPUTS to bf16 in the MXU but accumulates f32.
        #   2. the hi part is derived via integer mantissa masking, not
        #      x.astype(bf16).astype(f32), which Mosaic folds to the
        #      identity — zeroing the residual and collapsing the scheme
        #      to one plain bf16 pass (measured 9.3e-3).
        # hi is exactly bf16-representable so its truncation is lossless;
        # the residual rounds to bf16 at the MXU input, keeping ~16
        # mantissa bits overall (~1e-5 per 128-dot vs the f64 oracle).
        def split(x):
            xi = jax.lax.bitcast_convert_type(x, jnp.int32)
            hi = jax.lax.bitcast_convert_type(
                xi & jnp.int32(-65536), f32)       # 0xFFFF0000
            return hi, x - hi

        ah, al = split(a)
        bh, bl = split(b)

        def mm(x, y):
            return jax.lax.dot_general(
                x, y, dnums, preferred_element_type=f32,
                precision=jax.lax.Precision.DEFAULT)
        return mm(ah, bh) + mm(ah, bl) + mm(al, bh)
    return jax.lax.dot_general(a, b, dnums, preferred_element_type=f32,
                               precision=p)


_DN_2D = (((1,), (0,)), ((), ()))   # plain 2-D matmul dimension numbers


def _sublane_contract(d):
    """Contraction over the lowest log2(d) row bits of an (R, LANES)
    block, in the b0-SHAPED frame: (A, d, l) -> (A*l, d) via the cheap
    (0,2,1) tile transpose, one LARGE-M MXU dot x @ G^T, undo. The
    (d, A*l) small-m orientation costs ~30% of a whole pass in MXU
    inefficiency (measured 49.9 -> 38.5 ms/pass at 30q for b1).
    Expects gg PRE-TRANSPOSED (X @ G^T form, packed host-side).
    Used by the b1-op PairStage path (Kraus superoperators)."""
    def contract(gg, x):
        rows = x.size // LANES
        a = rows // d
        xt = (x.reshape(a, d, LANES).transpose(0, 2, 1)
              .reshape(a * LANES, d))
        out = _mxu_dot_general(xt, gg, _DN_2D)
        return (out.reshape(a, LANES, d).transpose(0, 2, 1)
                .reshape(x.shape))
    return contract


def _framed_cdot(to_frame, from_frame, re, im, gre, gim, real_only,
                 right=False):
    """Hoist the contraction frame change OUT of the Gauss trick: _cdot
    invokes its contraction three times (t1, t2, t3), so a
    frame-changing contract would pay its relayouts per invocation.
    One frame change in, three plain MXU dots, one frame change out.
    right=True contracts as X @ G (the caller passes G pre-transposed)
    — the large-m orientation the MXU wants."""
    fre, fim = to_frame(re), to_frame(im)

    if right:
        def contract(gg, xt):
            return _mxu_dot_general(xt, gg, _DN_2D)
    else:
        def contract(gg, xt):
            return _mxu_dot_general(gg, xt, _DN_2D)

    nre, nim = _cdot(contract, fre, fim, gre, gim, real_only)
    return from_frame(nre), from_frame(nim)


def _apply_mat_stage(re, im, st: MatStage, gref, geo: _Geometry, row_ids):
    g = gref[...]
    gre, gim = g[0], g[1]
    rows = geo.rows_eff

    if st.kind == "b0":
        def contract(gg, x):     # x (rows, LANES) @ G^T (LANES, LANES)
            return _mxu_dot_general(x, gg, _DN_2D)
        nre, nim = _cdot(contract, re, im, gre, gim, st.real_only)
    elif st.kind == "b1":
        # contract in the b0-SHAPED frame (large-m dot (a*l, d) @ G^T):
        # the (d, a*l) orientation costs ~30% of a whole pass in MXU
        # inefficiency (measured 49.9 -> 38.5 ms/pass at 30q — the
        # lane<->sublane tile transpose is cheap, the small-m dot is not)
        d = st.dim
        a = rows // d

        def to_frame(x):
            return (x.reshape(a, d, LANES).transpose(0, 2, 1)
                    .reshape(a * LANES, d))

        def from_frame(x):
            return (x.reshape(a, LANES, d).transpose(0, 2, 1)
                    .reshape(rows, LANES))
        nre, nim = _framed_cdot(to_frame, from_frame, re, im,
                                gre, gim, st.real_only, right=True)
    elif st.kind == "scb":
        # composed high-band operator: ONE dot over the merged scattered
        # axes (they are adjacent row dims of the block — the scat tuple
        # is bit-descending, so the merged index's MSB is the band's top
        # qubit, matching the operator's index convention).
        d = st.dim
        w = d.bit_length() - 1
        p = geo.scat.index(st.bit + w - 1)
        assert geo.scat[p:p + w] == tuple(
            range(st.bit + w - 1, st.bit - 1, -1)), \
            (geo.scat, st.bit, w)
        pre = 1 << p
        post = rows >> (p + w)

        if d == LANES:
            # full-width band: contract in the b0-shaped LARGE-M frame,
            # reached by TWO cheap-class transposes — a row-only swap
            # then a sublane<->lane tile swap. The direct (d, rest*l)
            # small-m dot measured 46.3 ms/pass at 30q and the
            # single-permutation mirror 61.4 (the fused lane<->leading
            # transpose is the expensive kind); the two-step route runs
            # at the 34.0 ms pass baseline. Operand arrives
            # pre-transposed (X @ G^T form).
            def to_frame(x):
                v = x.reshape(pre, d, post, LANES)
                v = v.transpose(0, 2, 1, 3)    # row-only swap
                v = v.transpose(0, 1, 3, 2)    # sublane<->lane tile swap
                return v.reshape(pre * post * LANES, d)

            def from_frame(x):
                v = x.reshape(pre, post, LANES, d)
                v = v.transpose(0, 1, 3, 2)
                v = v.transpose(0, 2, 1, 3)
                return v.reshape(rows, LANES)
            nre, nim = _framed_cdot(to_frame, from_frame, re, im,
                                    gre, gim, st.real_only, right=True)
        else:
            # narrow band: the left-dot is already cheap (cost scales
            # with d) and the mirror's d<->128 tile swaps are NOT
            # (measured: 538 ms/application on a d=8 stage, padding-
            # heavy relayouts); keep the transpose-free frame
            if pre == 1:
                def to_frame(x):
                    return x.reshape(d, post * LANES)

                def from_frame(x):
                    return x.reshape(rows, LANES)
            else:
                def to_frame(x):
                    return (x.reshape(pre, d, post * LANES)
                            .transpose(1, 0, 2).reshape(d, -1))

                def from_frame(x):
                    return (x.reshape(d, pre, post * LANES)
                            .transpose(1, 0, 2).reshape(rows, LANES))
            nre, nim = _framed_cdot(to_frame, from_frame, re, im,
                                    gre, gim, st.real_only)
    else:                        # 'sc': butterfly on one scattered axis
        a = geo.scat.index(st.bit)
        pre = 1 << a
        post = (rows >> (a + 1)) * LANES

        def halves(x):
            v = x.reshape(pre, 2, post)
            return v[:, 0, :], v[:, 1, :]

        r0, r1 = halves(re)
        i0, i1 = halves(im)

        def cmul(cr, ci, xr, xi):
            return cr * xr - ci * xi, cr * xi + ci * xr

        a0r, a0i = cmul(gre[0, 0], gim[0, 0], r0, i0)
        b0r, b0i = cmul(gre[0, 1], gim[0, 1], r1, i1)
        a1r, a1i = cmul(gre[1, 0], gim[1, 0], r0, i0)
        b1r, b1i = cmul(gre[1, 1], gim[1, 1], r1, i1)
        nre = jnp.stack([a0r + b0r, a1r + b1r], axis=1).reshape(rows, LANES)
        nim = jnp.stack([a0i + b0i, a1i + b1i], axis=1).reshape(rows, LANES)

    mask = _mask_of(row_ids, st.lane_preds, st.row_preds)
    if mask is not None:
        nre = jnp.where(mask, nre, re)
        nim = jnp.where(mask, nim, im)
    return nre, nim


def _row_halves(lo, hi):
    """Recombine a row mask split at bit 15 (each half exact in f32)."""
    return lo.astype(jnp.int32) | (hi.astype(jnp.int32) << 15)


def _xor_fold(x, top_shift):
    """Parity bit of each element's set bits: XOR-fold down to bit 0."""
    s = top_shift
    while s >= 1:
        x = x ^ (x >> s)
        s //= 2
    return x & 1


def _apply_phase_stage(re, im, st: PhaseStage, gref, row_ids):
    # (1, 8) operand: [tre, tim, lane_mask, lane_want,
    #                  row_mask_lo, row_mask_hi, row_want_lo, row_want_hi]
    # — predicates are DATA, so every phase stage shares one kernel
    g = gref[...]
    tre, tim = g[0, 0], g[0, 1]
    lm = g[0, 2].astype(jnp.int32)
    lw = g[0, 3].astype(jnp.int32)
    rm = _row_halves(g[0, 4], g[0, 5])
    rw = _row_halves(g[0, 6], g[0, 7])
    mask = (((_lane_iota() & lm) == lw)
            & ((row_ids & rm) == rw))   # empty masks: all-true
    nre = re * tre - im * tim
    nim = re * tim + im * tre
    return jnp.where(mask, nre, re), jnp.where(mask, nim, im)


def _apply_parity_stage(re, im, st: ParityStage, gref, row_ids):
    # (1, 8) operand: [cos, sin, lane_mask, row_mask_lo, row_mask_hi,
    #                  0, 0, 0] of the half angle and target-bit masks
    g = gref[...]
    lm = g[0, 2].astype(jnp.int32)
    rm = _row_halves(g[0, 3], g[0, 4])
    par = (_xor_fold(_lane_iota() & lm, 4)
           ^ _xor_fold(row_ids & rm, 16))
    sign = 1.0 - 2.0 * par.astype(jnp.float32)
    cosf = g[0, 0]
    sinf = g[0, 1] * sign
    nre = re * cosf + im * sinf
    nim = im * cosf - re * sinf
    return nre, nim


def multiphase_trigfree(st: MultiPhaseStage) -> bool:
    """Whether `st` runs on the trig-free path: a static choice on its
    term count, the one shape the kernel sees."""
    return len(st.forms) <= MULTIPHASE_TRIGFREE_MAX


def _apply_multiphase_stage(re, im, st: MultiPhaseStage, gref, row_ids):
    # (m, 8) operand rows: [angle, lane_mask, row_mask_lo, row_mask_hi,
    # 0, 0, 0, 0]; st.forms[r] picks the static interpretation. Lane
    # predicates stay (1, 128) and row predicates (rows, 1) until the
    # one broadcast that combines them.
    g = gref[...]
    lane = _lane_iota()

    def term(r, form):
        # allones: the match mask; parity: the parity bit
        lm = g[r, 1].astype(jnp.int32)
        rm = _row_halves(g[r, 2], g[r, 3])
        if form == "a":
            return ((lane & lm) == lm) & ((row_ids & rm) == rm)
        return _xor_fold(lane & lm, 4) ^ _xor_fold(row_ids & rm, 16)

    if not multiphase_trigfree(st):
        # wide groups: accumulate the total angle per element, then ONE
        # cos/sin + complex multiply (the select tree doubles per term)
        tot = None
        for r, form in enumerate(st.forms):
            ang, t = g[r, 0], term(r, form)
            if form == "a":
                contrib = jnp.where(t, ang, 0.0)
            else:
                contrib = ang * (1.0 - 2.0 * t.astype(jnp.float32))
            tot = contrib if tot is None else tot + contrib
        fre, fim = jnp.cos(tot), jnp.sin(tot)
    else:
        # each term picks one of two constants by one bit per element:
        # allones 1 or e^{i ang} by its match, parity e^{i ang} or
        # e^{-i ang} by its parity. cos/sin of the m angles only (one
        # vreg); the 2^m products of the constants are scalars, and a
        # select tree (2^m - 1 selects a plane) picks each element's.
        ang = g[:, 0:1]
        cos_a, sin_a = jnp.cos(ang), jnp.sin(ang)
        bits = []
        table = [(1.0, 0.0)]     # entry j: the product for bit pattern j
        for r, form in enumerate(st.forms):
            c, s, t = cos_a[r, 0], sin_a[r, 0], term(r, form)
            bits.append(t if form == "a" else t != 0)
            pair = ((1.0, 0.0), (c, s)) if form == "a" else ((c, s), (c, -s))
            table = [(tr * ur - ti * ui, tr * ui + ti * ur)
                     for ur, ui in pair for tr, ti in table]
        for bit in reversed(bits):
            half = len(table) // 2
            table = [(jnp.where(bit, hi[0], lo[0]),
                      jnp.where(bit, hi[1], lo[1]))
                     for lo, hi in zip(table[:half], table[half:])]
        (fre, fim), = table
    nre = re * fre - im * fim
    nim = re * fim + im * fre
    return nre, nim


def _bit_of(q, row_ids):
    """(broadcastable) value of bit `q` of each amplitude's global index."""
    if q < LANE_QUBITS:
        return (_lane_iota() >> q) & 1
    return (row_ids >> (q - LANE_QUBITS)) & 1


def _apply_diagvec_stage(re, im, st: DiagVecStage, gref, row_ids):
    g = gref[...]               # (2, 2^k) re/im entry table
    k = len(st.targets)
    fre = g[0, 0].reshape(1, 1)
    fim = g[1, 0].reshape(1, 1)
    for b in range(1, 1 << k):
        sel = None
        for j, q in enumerate(st.targets):
            m = _bit_of(q, row_ids) == ((b >> j) & 1)
            sel = m if sel is None else (sel & m)
        fre = jnp.where(sel, g[0, b], fre)
        fim = jnp.where(sel, g[1, b], fim)
    nre = re * fre - im * fim
    nim = re * fim + im * fre
    mask = _mask_of(row_ids, st.lane_preds, st.row_preds)
    if mask is not None:
        nre = jnp.where(mask, nre, re)
        nim = jnp.where(mask, nim, im)
    return nre, nim


def _batchsel_embed(v, bit, width, transpose=False):
    """Embed one state's 2x2 (8 scalars [g00re, g00im, g01re, g01im,
    g10re, g10im, g11re, g11im]) at `bit` of a 2^width space, built
    IN-KERNEL from iota masks: emb[r, c] = G[r_bit, c_bit] where the
    non-target bits of r and c agree, else 0. Keeps BatchSelStage
    operands at (batch, 8) bytes for lane/sublane qubits — a host-side
    embedding would ship batch x 128 KiB to VMEM at d=128.
    transpose=True returns G^T (the X @ G^T frame of the dot paths)."""
    d = 1 << width
    ri = jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (d, d), 1)
    other = ((ri ^ ci) & jnp.int32((d - 1) & ~(1 << bit))) == 0
    sel = other.astype(jnp.float32)
    br = ((ri >> bit) & 1).astype(jnp.float32)
    bc = ((ci >> bit) & 1).astype(jnp.float32)
    if transpose:
        br, bc = bc, br

    def emb(v00, v01, v10, v11):
        return sel * ((1.0 - br) * (1.0 - bc) * v00
                      + (1.0 - br) * bc * v01
                      + br * (1.0 - bc) * v10
                      + br * bc * v11)
    return (emb(v[0], v[2], v[4], v[6]), emb(v[1], v[3], v[5], v[7]))


def _apply_batchsel_stage(re, im, st: BatchSelStage, gref,
                          geo: _Geometry, row_ids, bsel):
    """Apply the CURRENT state's row of a (batch, 8) per-state operand
    table: the one-hot-selected (renormalized) Kraus branch of a batched
    trajectory channel, applied inside the sweep. `bsel` is the i32
    batch index (the leading grid dimension / the pipelined driver's
    unraveled step quotient)."""
    g = gref[pl.ds(bsel, 1), :]                       # (1, 8)
    v = [g[0, j] for j in range(8)]
    q = st.qubit
    rows = geo.rows_eff

    if q >= SUBLANE_TOP:
        # scattered axis: elementwise butterfly on per-state scalars
        # (the 'sc' MatStage math with traced matrix entries)
        a = geo.scat.index(q - LANE_QUBITS)
        pre = 1 << a
        post = (rows >> (a + 1)) * LANES

        def halves(x):
            t = x.reshape(pre, 2, post)
            return t[:, 0, :], t[:, 1, :]

        r0, r1 = halves(re)
        i0, i1 = halves(im)

        def cmul(cr, ci_, xr, xi):
            return cr * xr - ci_ * xi, cr * xi + ci_ * xr

        a0r, a0i = cmul(v[0], v[1], r0, i0)
        b0r, b0i = cmul(v[2], v[3], r1, i1)
        a1r, a1i = cmul(v[4], v[5], r0, i0)
        b1r, b1i = cmul(v[6], v[7], r1, i1)
        nre = jnp.stack([a0r + b0r, a1r + b1r], axis=1).reshape(rows, LANES)
        nim = jnp.stack([a0i + b0i, a1i + b1i], axis=1).reshape(rows, LANES)
        return nre, nim

    if q >= LANE_QUBITS:
        # sublane bit j: contract the lowest j+1 row bits in the b1
        # large-M frame (X @ G^T; the embedded operator is built
        # pre-transposed so the kernel pays no per-block transpose)
        j = q - LANE_QUBITS
        d = 1 << (j + 1)
        gre, gim = _batchsel_embed(v, j, j + 1, transpose=True)
        a = rows // d

        def to_frame(x):
            return (x.reshape(a, d, LANES).transpose(0, 2, 1)
                    .reshape(a * LANES, d))

        def from_frame(x):
            return (x.reshape(a, LANES, d).transpose(0, 2, 1)
                    .reshape(rows, LANES))
        return _framed_cdot(to_frame, from_frame, re, im, gre, gim,
                            False, right=True)

    # lane bit: embedded 128x128, one b0-style dot X @ G^T
    gre, gim = _batchsel_embed(v, q, LANE_QUBITS, transpose=True)

    def contract(gg, x):
        return _mxu_dot_general(x, gg, _DN_2D)
    return _cdot(contract, re, im, gre, gim, False)


def _apply_pair_stage(re, im, st: PairStage, gref, geo: _Geometry,
                      row_ids):
    g = gref[...]                 # (2, 4, D, D) block operators
    rows = geo.rows_eff

    if st.op_kind == "sc":
        # both qubits on scattered axes: 4 input slices, 16 scalar cmuls
        a_sl = geo.scat.index(st.sliced_bit)
        a_op = geo.scat.index(st.op_bit)
        ax1, ax2 = sorted((a_sl, a_op))
        p1 = 1 << ax1
        p2 = 1 << (ax2 - ax1 - 1)
        p3 = (rows >> (ax2 + 1)) * LANES

        def split(x):
            v = x.reshape(p1, 2, p2, 2, p3)
            return {(b1, b2): v[:, b1, :, b2, :]
                    for b1 in range(2) for b2 in range(2)}

        def bits(b1, b2):       # -> (sliced value, op value)
            return (b1, b2) if a_sl == ax1 else (b2, b1)

        xr, xi = split(re), split(im)
        outr, outi = {}, {}
        for b1 in range(2):
            for b2 in range(2):
                r, ao = bits(b1, b2)
                nr = ni = None
                for c in range(2):
                    for ai in range(2):
                        gre = g[0, r * 2 + c, ao, ai]
                        sb1, sb2 = (c, ai) if a_sl == ax1 else (ai, c)
                        if st.real_only:
                            tr = gre * xr[(sb1, sb2)]
                            ti = gre * xi[(sb1, sb2)]
                        else:
                            gim = g[1, r * 2 + c, ao, ai]
                            tr = gre * xr[(sb1, sb2)] - gim * xi[(sb1, sb2)]
                            ti = gre * xi[(sb1, sb2)] + gim * xr[(sb1, sb2)]
                        nr = tr if nr is None else nr + tr
                        ni = ti if ni is None else ni + ti
                outr[(b1, b2)], outi[(b1, b2)] = nr, ni

        def join(d):
            rows_of = [jnp.stack([d[(b1, 0)], d[(b1, 1)]], axis=2)
                       for b1 in range(2)]
            return jnp.stack(rows_of, axis=1).reshape(rows, LANES)
        nre, nim = join(outr), join(outi)
    else:
        # sliced qubit halves select embedded 128-dim block operators
        if st.sliced_kind == "scat":
            a = geo.scat.index(st.sliced_bit)
            pre = 1 << a
            post = (rows >> (a + 1)) * LANES
        else:                     # sublane bit (op side is the lane space)
            j = st.sliced_bit
            pre = rows >> (j + 1)
            post = (1 << j) * LANES

        def halves(x):
            v = x.reshape(pre, 2, post)
            return v[:, 0, :], v[:, 1, :]

        def rejoin(x0, x1):
            return jnp.stack([x0, x1], axis=1).reshape(rows, LANES)

        if st.op_kind == "lane":
            def block(gg, x):     # g packed pre-transposed: X @ G^T
                return _mxu_dot_general(
                    x.reshape(-1, LANES), gg, _DN_2D).reshape(x.shape)
        else:                     # 'b1': sublane-axis contraction
            block = _sublane_contract(LANES)

        xr, xi = halves(re), halves(im)
        outs = []
        for r in range(2):
            nr = ni = None
            for c in range(2):
                tr, ti = _cdot(block, xr[c], xi[c], g[0, r * 2 + c],
                               g[1, r * 2 + c], st.real_only)
                nr = tr if nr is None else nr + tr
                ni = ti if ni is None else ni + ti
            outs.append((nr, ni))
        nre = rejoin(outs[0][0], outs[1][0])
        nim = rejoin(outs[0][1], outs[1][1])

    mask = _mask_of(row_ids, st.lane_preds, st.row_preds)
    if mask is not None:
        nre = jnp.where(mask, nre, re)
        nim = jnp.where(mask, nim, im)
    return nre, nim


def _apply_stages(re, im, stages, mat_refs, geo: _Geometry, row_ids,
                  bsel=None):
    """The stage chain shared by both kernel drivers. `bsel` is the i32
    batch index under the batched grid (None: unbatched — BatchSelStage
    operands then hold a single row)."""
    for st, ref in zip(stages, mat_refs):
        if isinstance(st, MatStage):
            re, im = _apply_mat_stage(re, im, st, ref, geo, row_ids)
        elif isinstance(st, PairStage):
            re, im = _apply_pair_stage(re, im, st, ref, geo, row_ids)
        elif isinstance(st, BatchSelStage):
            re, im = _apply_batchsel_stage(
                re, im, st, ref, geo, row_ids,
                jnp.int32(0) if bsel is None else bsel)
        elif isinstance(st, PhaseStage):
            re, im = _apply_phase_stage(re, im, st, ref, row_ids)
        elif isinstance(st, MultiPhaseStage):
            re, im = _apply_multiphase_stage(re, im, st, ref, row_ids)
        elif isinstance(st, DiagVecStage):
            re, im = _apply_diagvec_stage(re, im, st, ref, row_ids)
        else:
            re, im = _apply_parity_stage(re, im, st, ref, row_ids)
    return re, im


def _segment_kernel(in_ref, *rest, stages, geo: _Geometry,
                    batched: bool = False):
    mat_refs = rest[:len(stages)]   # one operand ref per stage
    out_ref = rest[len(stages)]
    # the batch rides as the OUTERMOST grid dimension: program_id(0) is
    # the i32 state index (dtype-pinned by Pallas itself), row grids
    # shift up by one
    off = 1 if batched else 0
    bsel = pl.program_id(0) if batched else None
    pids = [pl.program_id(off + d) for d in range(len(geo.gaps))]
    row_ids = _row_ids(geo, pids)
    blk = in_ref[...].reshape(2, geo.rows_eff, LANES)
    re = blk[0]
    im = blk[1]
    re, im = _apply_stages(re, im, stages, mat_refs, geo, row_ids, bsel)
    shape = out_ref.shape
    out_ref[...] = jnp.stack([re, im]).reshape(shape)


def _nbuf_override() -> int:
    """QUEST_FUSED_NBUF experiment knob: VMEM slots in the manually
    pipelined driver. Slot buffers are IN-PLACE (one buffer is DMA-in
    target, compute scratch and DMA-out source), which couples the two
    DMA directions — in(s+1) may only start once out(s+1-nbuf) drained —
    so nbuf=2 stalls a full out-DMA per step (measured 23.8 vs 20.5 ms
    on the 28q bench) and nbuf < 2 would wait on an out-DMA that has
    not started. nbuf=3 gives the drain a whole step of slack at 3
    block buffers of VMEM. Malformed/out-of-range values fall back to
    the default, loudly (same discipline as _rows_eff_override)."""
    from quest_tpu.env import KNOBS, knob_value
    try:
        return knob_value("QUEST_FUSED_NBUF")
    except ValueError as e:
        import sys
        print(f"[pallas_band] ignoring QUEST_FUSED_NBUF: {e}",
              file=sys.stderr)
        return KNOBS["QUEST_FUSED_NBUF"].default


NBUF = _nbuf_override()


def _step_index(grid, block_shape, batched):
    """idx_of(step) -> (index tuple, pids, batch id) for the manual
    slot drivers: the index tuple selecting step's block in the state
    view, derived from the BLOCK SHAPE exactly like the grid driver's
    index_map (block entry 1 = a grid axis taking the unraveled step
    id, anything else rides whole) — one layout convention, not two.
    A size-1 inner axis also has block 1; the default 0 indexes it,
    mirroring index_map's zip-shortest behavior. Batched: the step
    space is (nbatch, *grid) with the batch SLOWEST, so each state's
    blocks stream back-to-back — the quotient left after dividing out
    the row grid is the i32 batch index (the drivers pin their loop
    counters int32, so every derived pid stays 32-bit). Shared by the
    legacy in-place driver and the decoupled pipeline so the two
    schedules can never disagree about which block a step touches."""
    def idx_of(step):
        pids = []
        rem = step
        for g in reversed(grid):
            pids.append(rem % g)
            rem = rem // g
        pids = pids[::-1]
        b = rem                              # batch index (0 unbatched)
        it = iter(pids)
        idx = [pl.ds(b, 1)] if batched else []   # leading batch axis
        idx.append(slice(None))              # plane axis
        for blk in block_shape[1:-1]:        # row-view axes
            idx.append(pl.ds(next(it, 0), 1) if blk == 1
                       else slice(None))
        idx.append(slice(None))              # lane axis
        return tuple(idx), pids, b
    return idx_of


class _BlockDMA:
    """One block transfer between the HBM state view and a VMEM slot,
    issued as one DMA per (re, im) plane on a shared semaphore. A single
    DMA over both planes strides its plane axis by a whole plane, and on
    the chip that stride wraps at 2^32 bytes: from 30 qubits on (4 GiB
    planes) such a DMA reads and writes the wrong plane, while per-plane
    DMAs land right at every offset (measured on a v5e, libtpu 0.0.34,
    PR 21). `idx` indexes the HBM view with its plane entry at
    `plane_axis`; the slot holds one block in the same order."""

    def __init__(self, hbm, idx, slot, sem, *, to_hbm, plane_axis):
        self.copies = []
        for p in range(2):
            hbm_p = hbm.at[idx[:plane_axis] + (p,) + idx[plane_axis + 1:]]
            slot_p = slot.at[(slice(None),) * plane_axis + (p,)]
            src, dst = (slot_p, hbm_p) if to_hbm else (hbm_p, slot_p)
            self.copies.append(pltpu.make_async_copy(src, dst, sem))

    def start(self):
        for c in self.copies:
            c.start()

    def wait(self):
        for c in self.copies:
            c.wait()


def _pipelined_kernel(in_hbm, *rest, stages, geo: _Geometry, grid,
                      block_shape, nbuf, nbatch=1, batched=None):
    """LEGACY manually pipelined segment driver (QUEST_FUSED_PIPELINE=0
    — the silicon A/B control): the state stays in HBM
    (memory_space=ANY); the kernel walks the same step space as the grid
    driver with `nbuf` IN-PLACE VMEM slot buffers — DMA step s+1 in and
    step s-1 out while the stage chain computes step s. In-place slots
    couple the two DMA directions: in(s+1) may only start once
    out(s+1-nbuf) drained from the same buffer (the serialization the
    decoupled driver below removes).

    Measured r4 (scripts/probe_stack.py, docs/KERNELS.md round-4
    findings): PARITY with the automatic BlockSpec pipeline on the
    bench step (79.7 vs 79.9 ms) and the best RCS 30q d20 number
    (2.097 vs 2.153 s) — the default driver on that margin. The hoped
    second win did NOT materialize: in-place slots halve block-buffer
    VMEM, but 2^14-row blocks still fail on ~96 MiB of chain-wide
    register-allocator spills (see PIPELINED_MAX_BLOCK_ROW_BITS), so
    the row-bit budget stays 13 on both drivers."""
    mat_refs = rest[:len(stages)]
    out_hbm = rest[len(stages)]
    if batched is None:          # legacy callers key batched-ness on B
        batched = nbatch > 1
    steps = int(np.prod(grid)) * nbatch
    nbuf = min(nbuf, steps)
    idx_of = _step_index(grid, block_shape, batched)
    slot_shape = (1, *block_shape) if batched else block_shape

    plane_axis = 1 if batched else 0

    def body(scratch, in_sems, out_sems):
        def get_in(step, slot):
            idx, _, _ = idx_of(step)
            return _BlockDMA(in_hbm, idx, scratch.at[slot], in_sems.at[slot],
                             to_hbm=False, plane_axis=plane_axis)

        def get_out(step, slot):
            idx, _, _ = idx_of(step)
            return _BlockDMA(out_hbm, idx, scratch.at[slot],
                             out_sems.at[slot], to_hbm=True,
                             plane_axis=plane_axis)

        get_in(0, 0).start()

        def step_body(s, _):
            # explicit i32 operands: under jax_enable_x64 a Python-int
            # operand traces as i64, and a mixed-dtype rem fails to
            # lower (interpret mode) or legalize (Mosaic)
            slot = jax.lax.rem(s, jnp.int32(nbuf))
            nslot = jax.lax.rem(s + 1, jnp.int32(nbuf))

            @pl.when(s + 1 < steps)
            def _():
                # the next slot is free once ITS previous out-DMA landed
                @pl.when(s + 1 >= nbuf)
                def _():
                    get_out(s + 1 - nbuf, nslot).wait()
                get_in(s + 1, nslot).start()

            get_in(s, slot).wait()
            _, pids, b = idx_of(s)
            row_ids = _row_ids(geo, pids)
            blk = scratch[slot].reshape(2, geo.rows_eff, LANES)
            re = blk[0]
            im = blk[1]
            re, im = _apply_stages(re, im, stages, mat_refs, geo, row_ids,
                                   b if batched else None)
            scratch[slot] = jnp.stack([re, im]).reshape(slot_shape)
            get_out(s, slot).start()
            return jnp.int32(0)

        # int32 bounds pin the loop counter (and everything derived from
        # it in idx_of) to 32 bits: under jax_enable_x64 Python-int
        # bounds trace as int64, which Mosaic cannot lower (the x64 test
        # suite's on-chip smoke run hits exactly this)
        jax.lax.fori_loop(jnp.int32(0), jnp.int32(steps), step_body,
                          jnp.int32(0))
        for j in range(nbuf):                # drain the tail out-DMAs
            s = steps - nbuf + j
            if s >= 0:
                get_out(s, s % nbuf).wait()

    pl.run_scoped(
        body,
        scratch=pltpu.VMEM((nbuf, *slot_shape), jnp.float32),
        in_sems=pltpu.SemaphoreType.DMA((nbuf,)),
        out_sems=pltpu.SemaphoreType.DMA((nbuf,)),
    )


def _decoupled_kernel(in_hbm, *rest, stages, geo: _Geometry, grid,
                      block_shape, in_slots, out_slots, nbatch=1,
                      batched=None):
    """DECOUPLED multi-buffer sweep pipeline (QUEST_FUSED_PIPELINE=1,
    the default): separate in-slot and out-slot rings, each with its
    own DMA semaphore chain, so the three streams of a sweep —

        HBM read  ->  per-stage MXU/VPU compute  ->  HBM write

    each run a full step ahead of the next. The legacy driver's
    in-place slots made one buffer serve as DMA-in target, compute
    scratch AND DMA-out source, which serializes the two DMA
    directions: in(s+1) had to wait for out(s+1-nbuf) to drain the
    same buffer — a stall of a whole out-DMA per step at nbuf=2
    (measured 23.8 vs 20.5 ms on the 28q bench) and a whole extra
    block of slack-buffer VMEM at nbuf=3. Here the read ring refills
    the moment compute has consumed a slot, regardless of where the
    write stream is:

        warm-up   in(0..in_slots-1) start          read ring fills
        step s    wait in(s)                       [in sems]
                  stage chain on in-slot s%I       compute
                  wait out(s-out_slots) drained    [out sems]
                  write out-slot s%O; out(s) start
                  in(s+in_slots) start             ring refill
        drain     wait the last out_slots out-DMAs

    During the stage loop of step s the DMAs for blocks s+1..s+I-1
    (started by earlier iterations / the warm-up) and the write-backs
    of blocks s-O..s-1 are all in flight — stage-level overlap of the
    next block's DMA under the current block's compute, with neither
    DMA direction gating the other. The refill for step s+I starts
    only AFTER the stage chain (its in-slot holds the block compute is
    reading until then); with in_slots >= 2 the read stream still runs
    a full step ahead. VMEM cost: in_slots + out_slots block buffers
    (4 x 8 MiB at the 2^13-row cap) vs the legacy 3 — paid back out of
    the sweep operand budget (PIPELINE_SWEEP_OPERAND_BYTES), so the
    total stays inside the 100 MiB scoped limit; sweep_vmem_bytes is
    the CPU-assertable accounting.

    Bit-identity with the legacy driver holds by construction: the
    same _step_index walk, the same _apply_stages chain, the same
    float ops per block — only the buffer/semaphore schedule differs
    (pinned across the randomized sweep suite in tests/test_sweeps.py).

    The in/out waits sit inside jax.named_scope regions
    ('quest:dma_in_wait' / 'quest:dma_out_wait' / 'quest:stages') so a
    chip profile can attribute residual stall time to the read stream,
    the write stream or the stage chain directly. Mosaic emits them at
    trace level 10: they reach the device trace only from kernels
    compiled with libtpu's --xla_enable_custom_call_region_trace=true
    (docs/SWEEPS.md; profiling.sweep_dma_report is the host-side split
    without it)."""
    mat_refs = rest[:len(stages)]
    out_hbm = rest[len(stages)]
    if batched is None:
        batched = nbatch > 1
    steps = int(np.prod(grid)) * nbatch
    n_in = min(in_slots, steps)
    n_out = min(out_slots, steps)
    idx_of = _step_index(grid, block_shape, batched)
    slot_shape = (1, *block_shape) if batched else block_shape

    plane_axis = 1 if batched else 0

    def body(in_scr, out_scr, in_sems, out_sems):
        def get_in(step, slot):
            idx, _, _ = idx_of(step)
            return _BlockDMA(in_hbm, idx, in_scr.at[slot], in_sems.at[slot],
                             to_hbm=False, plane_axis=plane_axis)

        def get_out(step, slot):
            idx, _, _ = idx_of(step)
            return _BlockDMA(out_hbm, idx, out_scr.at[slot],
                             out_sems.at[slot], to_hbm=True,
                             plane_axis=plane_axis)

        for j in range(n_in):                # fill the read ring
            get_in(j, j).start()

        def step_body(s, _):
            # explicit i32 operands: under jax_enable_x64 a Python-int
            # operand traces as i64, and a mixed-dtype rem fails to
            # lower (interpret mode) or legalize (Mosaic)
            islot = jax.lax.rem(s, jnp.int32(n_in))
            oslot = jax.lax.rem(s, jnp.int32(n_out))
            with jax.named_scope("quest:dma_in_wait"):
                get_in(s, islot).wait()
            _, pids, b = idx_of(s)
            row_ids = _row_ids(geo, pids)
            blk = in_scr[islot].reshape(2, geo.rows_eff, LANES)
            re = blk[0]
            im = blk[1]
            with jax.named_scope("quest:stages"):
                re, im = _apply_stages(re, im, stages, mat_refs, geo,
                                       row_ids, b if batched else None)
            # the out slot is free once ITS previous occupant drained —
            # the only cross-stream ordering left, and it trails compute
            # by a whole out_slots steps
            @pl.when(s >= n_out)
            def _():
                with jax.named_scope("quest:dma_out_wait"):
                    get_out(s - n_out, oslot).wait()
            out_scr[oslot] = jnp.stack([re, im]).reshape(slot_shape)
            get_out(s, oslot).start()

            # refill the read ring: in-slot s%I was consumed by the
            # stage chain above, so block s+I may stream in now —
            # it will be in flight under the NEXT steps' stage loops
            @pl.when(s + n_in < steps)
            def _():
                get_in(s + n_in, islot).start()
            return jnp.int32(0)

        # int32 bounds pin the loop counter (and everything derived
        # from it in idx_of) to 32 bits — see _pipelined_kernel
        jax.lax.fori_loop(jnp.int32(0), jnp.int32(steps), step_body,
                          jnp.int32(0))
        for j in range(n_out):               # drain the tail out-DMAs
            s = steps - n_out + j
            if s >= 0:
                get_out(s, s % n_out).wait()

    pl.run_scoped(
        body,
        in_scr=pltpu.VMEM((n_in, *slot_shape), jnp.float32),
        out_scr=pltpu.VMEM((n_out, *slot_shape), jnp.float32),
        in_sems=pltpu.SemaphoreType.DMA((n_in,)),
        out_sems=pltpu.SemaphoreType.DMA((n_out,)),
    )


def _rows_eff_override():
    """QUEST_ROWS_EFF_BITS block-size experiment knob, parsed ONCE at
    import (mid-process changes are deliberately ignored: the value is
    not part of any compiled-program cache key, so honoring them would
    silently return stale kernels — sweep via subprocesses instead,
    like scripts' block experiments do). Malformed/out-of-range values
    fall back to the default, loudly."""
    from quest_tpu.env import knob_value
    try:
        v = knob_value("QUEST_ROWS_EFF_BITS")
    except ValueError as e:
        import sys
        print(f"[pallas_band] ignoring QUEST_ROWS_EFF_BITS: {e}",
              file=sys.stderr)
        return ROWS_EFF_BITS
    if v is None:
        return ROWS_EFF_BITS
    if v > max_block_row_bits():
        # upper bound depends on the device's VMEM — checkable only here,
        # not in the registry parser
        import sys
        print(f"[pallas_band] ignoring QUEST_ROWS_EFF_BITS={v} above "
              f"max_block_row_bits()={max_block_row_bits()}",
              file=sys.stderr)
        return ROWS_EFF_BITS
    return v


_ROWS_EFF_BITS_EFFECTIVE = None  # resolved lazily on first compile


_DRIVER_EFFECTIVE = None  # resolved once on first compile


def _driver_override() -> str:
    """QUEST_FUSED_DRIVER experiment knob: 'pipelined' (default) or
    'grid' (the automatic BlockSpec pipeline — kept for A/B probes and
    as a fallback). Resolved ONCE per process (like NBUF): compiled
    programs cache across engines without carrying the knob in every
    cache key, and flipping the env mid-process cannot hand back a
    program built with the other driver (ADVICE r4 item 2) — sweep via
    subprocesses like the block experiments."""
    global _DRIVER_EFFECTIVE
    if _DRIVER_EFFECTIVE is not None:
        return _DRIVER_EFFECTIVE
    from quest_tpu.env import KNOBS, knob_value
    try:
        v = knob_value("QUEST_FUSED_DRIVER")
    except ValueError as e:
        import sys
        print(f"[pallas_band] ignoring QUEST_FUSED_DRIVER: {e}",
              file=sys.stderr)
        v = KNOBS["QUEST_FUSED_DRIVER"].default
    _DRIVER_EFFECTIVE = v
    return v


def segment_geometry(stages: Sequence, n: int,
                     rows_eff_bits: int | None = None) -> _Geometry:
    """Block geometry of a compiled stage list — the rows_eff
    resolution + stage_requirements accounting compile_segment sizes
    its block from, factored out so the CPU-side schedule introspection
    (pipeline_stats, sweep_vmem_bytes) derives step counts and slot
    bytes from EXACTLY what the kernel will allocate, never a parallel
    re-derivation."""
    global _ROWS_EFF_BITS_EFFECTIVE
    if rows_eff_bits is None:
        if _ROWS_EFF_BITS_EFFECTIVE is None:
            _ROWS_EFF_BITS_EFFECTIVE = _rows_eff_override()
        rows_eff_bits = _ROWS_EFF_BITS_EFFECTIVE
    total_row_bits = n - LANE_QUBITS
    rows_eff_bits = min(rows_eff_bits, total_row_bits)
    # block geometry from the shared requirements accounting (the same
    # scat/floor contract sweep_plan merges under)
    scat_bits, b1_bits = stage_requirements(stages)
    rows_eff_bits = max(rows_eff_bits, b1_bits + len(scat_bits))
    return _geometry(n, scat_bits, rows_eff_bits)


def kernel_name(stages: Sequence, geo: _Geometry,
                batch: int | None = None) -> str:
    """The stable name a segment's kernel carries into the HLO and the
    device trace: its stage kinds and counts, its block geometry (inner
    row bits `r`, scattered axes `s`) and a digest of the whole
    structure, e.g. `quest_seg_mat2_phase1_r13s1_5f0c2a91`. Segments
    that share a kernel (compile_segment_cached) share the name; it
    changes only when what the kernel computes does."""
    kinds = collections.Counter(
        type(st).__name__.removesuffix("Stage").lower() for st in stages)
    parts = "_".join(f"{k}{c}" for k, c in sorted(kinds.items())) or "copy"
    digest = hashlib.sha1(
        repr((tuple(stages), geo, batch)).encode()).hexdigest()[:8]
    return (f"quest_seg_{parts}_r{geo.inner_bits}s{len(geo.scat)}"
            f"{'' if batch is None else f'_b{batch}'}_{digest}")


def compile_segment(stages: Sequence, n: int,
                    rows_eff_bits: int | None = None,
                    interpret: bool = False, batch: int | None = None):
    """Build fn(amps, mat_arrays) -> amps applying `stages` in one kernel
    launch (the manually pipelined slot driver by default; the automatic
    grid pipeline via QUEST_FUSED_DRIVER=grid). batch=B (any B >= 1)
    adds a leading batch grid dimension: the launch streams B states
    through HBM back-to-back with the SAME stage list — one launch for
    the whole bucket instead of one per state — and apply takes/returns
    (B, 2, rows, 128) even at B=1 so callers keep one calling convention
    per bucket (docs/BATCHING.md). batch=None compiles the unbatched
    kernel over (2, rows, 128). Block geometry, VMEM residency and the
    stage chain are per-state and unchanged; only BatchSelStage operands
    carry a per-state axis."""
    geo = segment_geometry(stages, n, rows_eff_bits)
    dims, blocks = geo.view_dims()
    grid = tuple(1 << w for (lo, w) in geo.gaps)
    grid_axes = [i for i, b in enumerate(blocks) if b == 1]
    batched = batch is not None
    nbatch = batch if batched else 1
    name = kernel_name(stages, geo, batch)

    def index_map(*ids):
        # batched: the leading grid id selects the state; row-axis
        # offsets shift one slot right for the batch view axis
        if batched:
            b, ids = ids[0], ids[1:]
            out = [b] + [0] * (len(dims) + 2)
            off = 2
        else:
            out = [0] * (len(dims) + 2)   # + plane axis, + lane axis
            off = 1
        for ax, i in zip(grid_axes, ids):
            out[off + ax] = i
        return tuple(out)

    block_shape = (2, *blocks, LANES)
    view_shape = (2, *dims, LANES)
    if batched:
        full_view = (nbatch, *view_shape)
        full_block = (1, *block_shape)
        full_grid = (nbatch, *grid)
    else:
        full_view, full_block, full_grid = view_shape, block_shape, grid

    if _driver_override() == "pipelined":
        if pipeline_enabled():
            # decoupled multi-buffer pipeline (default): separate
            # in/out slot rings, independent DMA semaphore chains
            kernel = functools.partial(
                _decoupled_kernel, stages=tuple(stages), geo=geo,
                grid=grid, block_shape=block_shape,
                in_slots=PIPELINE_IN_SLOTS, out_slots=PIPELINE_OUT_SLOTS,
                nbatch=nbatch, batched=batched)
        else:
            # legacy in-place slot schedule (QUEST_FUSED_PIPELINE=0 —
            # the silicon A/B control)
            kernel = functools.partial(
                _pipelined_kernel, stages=tuple(stages), geo=geo,
                grid=grid, block_shape=block_shape, nbuf=NBUF,
                nbatch=nbatch, batched=batched)
        # the state stays in HBM; the kernel DMAs its own blocks through
        # the in-place slot buffers. Operands are whole-array VMEM.
        in_specs = [pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)]
        for _ in stages:
            in_specs.append(
                pl.BlockSpec(memory_space=pltpu.MemorySpace.VMEM))
        fn = pl.pallas_call(
            kernel,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),
            out_shape=jax.ShapeDtypeStruct(full_view, jnp.float32),
            input_output_aliases={0: 0},  # in-place on the state buffer
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=interpret,
            name=name,
        )
    else:
        kernel = functools.partial(_segment_kernel, stages=tuple(stages),
                                   geo=geo, batched=batched)
        in_specs = [pl.BlockSpec(full_block, index_map)]
        for st in stages:
            # each operand rides whole (a BatchSelStage's per-state
            # table too: the kernel row-selects by the batch grid id)
            shape = _operand_shape(st, nbatch)
            in_specs.append(pl.BlockSpec(
                shape, lambda *ids, rank=len(shape): (0,) * rank))
        fn = pl.pallas_call(
            kernel,
            grid=full_grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(full_block, index_map),
            out_shape=jax.ShapeDtypeStruct(full_view, jnp.float32),
            input_output_aliases={0: 0},  # in-place on the state buffer
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=interpret,
            name=name,
        )

    def apply(amps, mat_arrays):
        # callers keep the state in (2, rows, 128) between segments: that
        # shape and every segment view share the same (8, 128) physical
        # tiling, so these reshapes are free bitcasts. A flat (2, 2^n)
        # boundary would get XLA's T(2,128) tiling and cost a whole-state
        # retile copy per dispatch (the 8 GB HLO temp that OOMed 30q).
        # The kernel is pure f32/int32; trace it with x64 disabled —
        # under jax_enable_x64 stray int64 ops fail Mosaic legalization.
        # Interpret mode keeps the caller's x64 setting: its emulated
        # grid loop mixes its own index dtypes with the surrounding
        # trace, and flipping x64 mid-trace is what breaks it (i32
        # carry vs i64 bound); there is no Mosaic pass to appease there.
        if interpret:
            out = fn(amps.reshape(full_view), *mat_arrays)
        else:
            with jax.enable_x64(False):
                out = fn(amps.reshape(full_view), *mat_arrays)
        if batched:
            return out.reshape(nbatch, 2, -1, LANES)
        return out.reshape(2, -1, LANES)

    return apply


def compile_segment_cached(cache: dict, stages: Sequence, n: int,
                           interpret: bool = False,
                           batch: int | None = None):
    """Kernel-sharing wrapper around compile_segment: stages are pure
    STRUCTURE (operand values ride as kernel inputs), so segments that
    differ only in values — e.g. RCS layers with different angles —
    share one compiled kernel. The ONE place the cache key lives
    (batch is part of it: a bucket's kernels are shaped for it).
    Counts each call's MultiPhaseStages by path, hit or miss, into the
    active recording: `quest.multiphase_trigfree` / `quest.multiphase_trig`."""
    for st in stages:
        if isinstance(st, MultiPhaseStage):
            profiling.count("quest.multiphase_trigfree"
                            if multiphase_trigfree(st)
                            else "quest.multiphase_trig")
    key = (tuple(stages), n, interpret, batch)
    fn = cache.get(key)
    if fn is None:
        fn = compile_segment(stages, n, interpret=interpret, batch=batch)
        cache[key] = fn
    return fn


def usable(n: int) -> bool:
    """Need at least one (8, 128) f32 tile per block."""
    return n >= TILE_QUBITS
