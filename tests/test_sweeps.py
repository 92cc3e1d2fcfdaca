"""Sweep-fusion layer tests (quest_tpu/ops/pallas_band.py sweep_plan):
merge rules, golden hbm_sweeps values for the benchmark circuits, and a
randomized equivalence suite proving sweep-fused execution matches the
unfused semantics within documented eps (docs/SWEEPS.md) — across f32
interpret-mode kernels, the f64 banded fallback, and the sharded fused
engine. CPU-only: the merge decision and the hbm_sweeps metric are pure
host planning; execution runs in the Pallas interpreter.

References are the dense NumPy oracle (tests/oracle.py), NOT the
per-gate XLA engine: a deep unrolled per-gate program costs minutes of
XLA-CPU compile at x64, while the oracle is exact and compile-free.

Structure templates: the randomized circuits draw their GATE PATTERN
from a small template pool and their parameters per instance, so
identical-structure sweeps share one compiled kernel
(compile_segment_cached) and 50 circuits cost ~a dozen interpret-mode
compiles, not 50 (the tier-1 budget note in ROADMAP.md).
"""

import numpy as np
import pytest

import jax.numpy as jnp

import bench
from quest_tpu.circuit import Circuit, GateOp, qft_circuit, random_circuit
from quest_tpu.ops import fusion as F
from quest_tpu.ops import pallas_band as PB
from tests import oracle

pytestmark = pytest.mark.dtype_agnostic

N = 10

# documented equivalence eps (docs/SWEEPS.md): f32 kernels vs the f64
# oracle, relative to the largest amplitude — the same envelope the
# per-stage Pallas tests use, widened for multi-application sweeps
EPS_F32 = 1e-4
EPS_F64 = 1e-11


def plan_parts(c: Circuit, n: int = N, density: bool = False):
    items = F.plan(c._planned_flat(n * (2 if density else 1), density), n,
                   bands=PB.plan_bands(n))
    return PB.segment_plan(items, n)


# ---------------------------------------------------------------------------
# goldens: the benchmark circuits' hbm_sweeps (acceptance metric)
# ---------------------------------------------------------------------------

QFT30_GOLDEN_SWEEPS = 8      # committed golden (scripts/check_sweep_golden.py
CHAIN30_GOLDEN_SWEEPS = 1    # runs the same assertions in CI); QFT-30's
# sweeps 7 and 8 are the VMEM price's: at most 10 wide phase groups a sweep


def test_qft30_golden_hbm_sweeps():
    rec = qft_circuit(30).plan_stats()["fused"]
    assert rec["hbm_sweeps"] == QFT30_GOLDEN_SWEEPS, rec
    # strictly below the per-stage pass count (what a no-fusion engine
    # would pay) AND no worse than the pre-sweep segment plan
    assert rec["hbm_sweeps"] < rec["stages"], rec
    assert rec["hbm_sweeps"] <= rec["full_state_passes"], rec
    assert sum(rec["sweep_stages"]) == rec["stages"], rec


def test_chain30_golden_hbm_sweeps():
    """The fusion-resistant chain: every gate is its own stage, yet one
    application is ONE HBM sweep — >= 2x below the per-stage count."""
    rec = bench._build_chain_circuit(30).plan_stats()["fused"]
    assert rec["hbm_sweeps"] == CHAIN30_GOLDEN_SWEEPS, rec
    assert rec["stages"] == bench.GATES_PER_STEP
    assert 2 * rec["hbm_sweeps"] <= rec["stages"], rec


# ---------------------------------------------------------------------------
# the VMEM price (pallas_band.sweep_vmem_bytes, docs/KERNELS.md)
# ---------------------------------------------------------------------------

# Mosaic's scoped allocation for QFT-30's sweep 2 as planned before the
# price, [scb128, 21 phase groups, phase] at 2^12 rows, compiled for a v5e
QFT30_FORMER_SWEEP2_MOSAIC = 125.58 * (1 << 20)


def _swept(c: Circuit, n: int, density: bool = False, budget=None):
    items = F.plan(c._planned_flat(n, density), n, bands=PB.plan_bands(n))
    return PB.sweep_plan(PB.segment_plan(items, n, vmem_budget=budget), n,
                         vmem_budget=budget)


def qft30_former_sweep2():
    """QFT-30's sweep 2 as planned before the price: the planner with no
    VMEM budget."""
    return _swept(qft_circuit(30), 30, budget=1 << 62)[2]


def test_vmem_price_covers_mosaic_on_qft30_former_sweep2():
    _, stages, arrays = qft30_former_sweep2()
    geo = PB.segment_geometry(stages, 30, PB.ROWS_EFF_BITS)
    assert PB.kernel_name(stages, geo).startswith(
        "quest_seg_mat1_multiphase21_phase1_r5s7_"), stages
    rec = PB.sweep_vmem_bytes(stages, arrays, 30,
                              rows_eff_bits=PB.ROWS_EFF_BITS)
    assert rec["total_bytes"] >= QFT30_FORMER_SWEEP2_MOSAIC, rec
    assert rec["total_bytes"] > PB.VMEM_LIMIT_BYTES


def test_qft30_sweeps_price_within_limit():
    """Every sweep the planner makes of QFT-30 prices within the scoped
    limit at the rows it compiles with: a long chain of wide phase
    groups is cut, at most 10 of them a sweep."""
    parts = _swept(qft_circuit(30), 30)
    for part in parts:
        assert part[0] == "segment"
        rec = PB.sweep_vmem_bytes(part[1], part[2], 30)
        assert rec["total_bytes"] <= PB.VMEM_LIMIT_BYTES, rec
    trig = [sum(isinstance(st, PB.MultiPhaseStage)
                and not PB.multiphase_trigfree(st) for st in p[1])
            for p in parts]
    assert sum(trig) == 48 and max(trig) <= 10, trig
    assert sum(len(p[1]) for p in parts) == 98


def test_adjacent_butterflies_across_applications_priced():
    """Two applications of a lone high-qubit gate would merge into two
    unmasked butterflies on one bit, which Mosaic holds in 52 planes (48
    more than either alone, docs/KERNELS.md): past the scoped limit at
    2^12 rows, so they stay two sweeps; at 16 qubits they merge."""
    c = Circuit(30)
    c.h(20)
    parts = PB.sweep_plan(plan_parts(c, 30) * 2, 30)
    assert [[(st.kind, st.bit) for st in p[1]] for p in parts] == \
        [[("sc", 13)]] * 2
    stages = parts[0][1] + parts[1][1]
    assert PB.chain_planes(stages) == PB.chain_planes(stages[:1]) + \
        PB.BUTTERFLY_PAIR_PLANES
    assert not PB.vmem_fits(stages, 30)
    c = Circuit(16)
    c.h(15)
    (part,) = PB.sweep_plan(plan_parts(c, 16) * 2, 16)
    assert [(st.kind, st.bit) for st in part[1]] == [("sc", 8)] * 2


def noisy_d2_circuit(n: int = 15) -> Circuit:
    """qbench/traffic/noisy_d2.json's circuit: random_circuit's draws for
    two brick layers, a depolarising channel after every gate (on both
    qubits of an entangler) and damping at each layer's end."""
    rng = np.random.default_rng(7)
    c = Circuit(n)
    for d in range(2):
        for q in range(n):
            angle = float(rng.uniform(0, 2 * np.pi))
            (c.rx, c.ry, c.rz)[int(rng.integers(0, 3))](q, angle)
            c.depolarising(q, 0.0016)
        for q in range(d % 2, n - 1, 2):
            c.cz(q, q + 1)
            c.depolarising(q, 0.0062)
            c.depolarising(q + 1, 0.0062)
        for q in range(n):
            c.damping(q, 0.002)
    return c


# The benchmark cells' sweeps as planned before the VMEM price: each
# kernel name's stage kinds, counts and block geometry (the digest after
# it covers the stage tuples).
CELL_PLANS = {
    "sv30_f32.rcs_d20": (
        lambda: _swept(random_circuit(30, 20, seed=7, entangler="cz"), 30),
        ["mat2_parity3_r12s0", "mat1_r5s7",
         "mat2_multiphase1_parity2_phase1_r5s7", "mat1_r12s0",
         "mat1_multiphase1_parity1_r5s7", "mat2_r10s2",
         "mat1_multiphase1_r5s7", "mat1_r5s7", "mat1_multiphase1_r5s7",
         "mat1_r12s0", "mat1_multiphase1_r5s7", "mat3_r10s2", "mat1_r5s7",
         "mat1_multiphase1_r5s7", "mat1_multiphase1_r5s7", "mat1_r5s7",
         "mat3_r10s2", "mat2_multiphase2_r5s7", "mat1_r5s7",
         "mat3_multiphase1_r10s2", "mat1_multiphase1_r5s7", "mat1_r5s7",
         "mat2_r10s2", "mat2_multiphase1_r5s7", "mat1_r12s0",
         "mat1_multiphase1_r5s7", "mat3_r10s2", "mat1_multiphase1_r5s7",
         "mat1_multiphase1_r5s7", "mat2_r5s7", "mat1_r12s0", "mat1_r5s7",
         "mat2_multiphase2_r10s2", "mat1_r5s7", "mat4_multiphase1_r10s2",
         "mat1_multiphase1_r5s7", "mat1_multiphase1_r12s0", "mat1_r5s7"],
        "cb9948cebe39426f88217bcfc11f8b47469bd6b5e8bde9fff9d8d51fc2dd8822"),
    "dm15_f32.noisy_d2": (
        lambda: _swept(noisy_d2_circuit(), 30, density=True),
        ["mat2_pair6_parity5_r5s7", "mat1_pair1_r11s1",
         "mat1_pair6_parity1_phase1_r7s6", "pair5_parity1_phase3_r7s6",
         "pair6_phase5_r7s6", "pair6_phase3_r7s6", "pair6_r6s6",
         "pair6_r7s6", "mat1_pair2_r7s3", "mat1_pair6_r5s7",
         "mat1_pair1_parity1_phase1_r5s7", "mat1_pair6_parity2_r7s6",
         "pair5_parity1_phase5_r7s6", "pair6_phase4_r7s6",
         "pair5_phase3_r7s6", "pair7_r5s7", "pair6_r7s6", "pair2_r7s3"],
        "263e19fe2f669c94378f118bf62cf91f65277492d719ad1e1a8e39b174adba86"),
}


@pytest.mark.parametrize("cell", sorted(CELL_PLANS))
def test_benchmark_cell_plans_unchanged_by_vmem_price(cell):
    """The price leaves the short chains of the RCS and noisy cells as
    they were: the same sweeps, stage kinds and kernel names."""
    import hashlib
    import re
    plan, kinds, digest = CELL_PLANS[cell]
    parts = plan()
    names = [PB.kernel_name(p[1], PB.segment_geometry(p[1], 30))
             for p in parts]
    assert [re.sub(r"^quest_seg_|_[0-9a-f]{8}$", "", x)
            for x in names] == kinds
    assert hashlib.sha256("\n".join(names).encode()).hexdigest() == digest
    for p in parts:
        assert PB.sweep_vmem_bytes(p[1], p[2], 30)["total_bytes"] <= \
            PB.VMEM_LIMIT_BYTES


def test_vmem_cut_counted_in_plan_span():
    """quest.sweep_vmem_cut counts the merges only the price refused and
    quest.sweep_vmem_peak_mib the largest priced sweep, both inside the
    quest.plan span of compiled_fused."""
    from quest_tpu import profiling
    c = qft_circuit(30)
    with profiling.recording() as rec:
        c.compiled_fused(30, False, donate=True)
    assert rec.counts["quest.sweep_vmem_cut"] == 2
    peak = max(PB.sweep_vmem_bytes(p[1], p[2], 30)["total_bytes"]
               for p in _swept(c, 30))
    assert rec.counts["quest.sweep_vmem_peak_mib"] == peak / (1 << 20)
    assert rec.counts["quest.multiphase_trig"] == 48
    with profiling.recording() as rec:
        random_circuit(12, 4, seed=1).compiled_fused(12, False)
    assert rec.counts["quest.sweep_vmem_cut"] == 0


def test_cross_iteration_sweeps_collapse_bench_dispatch():
    """The bench's INNER_STEPS=16 unrolled applications merge across
    iteration boundaries: the headline step becomes ONE kernel launch
    per dispatch (16 -> 1 HBM sweeps) and the chain collapses 16 -> 4
    (the MAX_SWEEP_STAGES budget binds at 64 stages) — the 'G sweeps ->
    ~G/k' floor the sweep layer exists for."""
    for build, want_sweeps in ((bench._build_circuit, 1),
                               (bench._build_chain_circuit, 4)):
        c = build(30)
        parts = plan_parts(c, 30)
        swept = PB.sweep_plan(parts * bench.INNER_STEPS, 30)
        assert len(swept) == want_sweeps, (build.__name__, len(swept))
        assert all(len(p[1]) <= PB.MAX_SWEEP_STAGES for p in swept)
        # stage multiset preserved, order concatenated
        assert sum(len(p[1]) for p in swept) == \
            bench.INNER_STEPS * sum(len(p[1]) for p in parts
                                    if p[0] == "segment")


# ---------------------------------------------------------------------------
# merge rules
# ---------------------------------------------------------------------------


def _seg(stages, arrays=None):
    return ("segment", list(stages),
            list(arrays) if arrays is not None
            else [np.zeros((1, 8), np.float32) for _ in stages])


def test_sweep_respects_xla_barrier():
    c = Circuit(N)
    c.h(0)
    parts = plan_parts(c)
    assert len(parts) == 1
    barrier = ("xla", object())
    swept = PB.sweep_plan([parts[0], barrier, parts[0]], N)
    assert [p[0] for p in swept] == ["segment", "xla", "segment"]


def test_sweep_scatter_budget_blocks_merge():
    """Two segments whose scattered-bit UNION exceeds the scatter budget
    stay separate sweeps; within budget they merge."""
    n = 23
    c1 = Circuit(n)
    for q in range(14, 21):
        c1.ry(q, 0.3)              # scb: scat bits 7..13
    c2 = Circuit(n)
    c2.ry(21, 0.4)
    c2.ry(22, 0.5)                 # scb: scat bits 14, 15
    (p1,) = plan_parts(c1, n)
    (p2,) = plan_parts(c2, n)
    assert len(PB.sweep_plan([p1, p2], n)) == 2      # union: 9 bits > 7
    assert len(PB.sweep_plan([p2, p2], n)) == 1      # union: 2 bits


def test_sweep_row_budget_blocks_merge():
    """A b1 sublane floor plus scattered axes above max_block_row_bits()
    blocks the merge (the same budget compile_segment sizes blocks by)."""
    n = 23
    cb1 = Circuit(n)
    for q in range(7, 14):
        cb1.ry(q, 0.2)             # b1: floor 7
    chigh = Circuit(n)
    for q in range(14, 21):
        chigh.ry(q, 0.3)           # scb: 7 scat bits
    (pb1,) = plan_parts(cb1, n)
    (ph,) = plan_parts(chigh, n)
    # floor 7 + 7 scat = 14 > 13: no merge (the measured Mosaic spill
    # wall of PIPELINED_MAX_BLOCK_ROW_BITS)
    assert len(PB.sweep_plan([pb1, ph], n)) == 2
    assert len(PB.sweep_plan([pb1, pb1], n)) == 1


def test_sweep_stage_and_operand_budgets():
    c = Circuit(N)
    for q in range(7):
        c.h(q)
    (p,) = plan_parts(c)
    assert len(PB.sweep_plan([p] * 4, N, max_stages=2)) == 2
    nbytes = sum(a.nbytes for a in p[2])
    assert len(PB.sweep_plan([p] * 4, N, operand_bytes=2 * nbytes)) == 2
    assert len(PB.sweep_plan([p] * 4, N)) == 1


def test_stage_requirements_matches_segment_geometry():
    """stage_requirements (the shared merge/geometry accounting) agrees
    with what segment_plan reserved: every planned segment fits the
    budgets it was planned under."""
    rng = np.random.default_rng(5)
    for n in (N, 17, 23):
        c = Circuit(n)
        for _ in range(24):
            q = int(rng.integers(0, n))
            c.ry(q, float(rng.uniform(0, 2 * np.pi)))
            if rng.integers(0, 2):
                r = int(rng.integers(0, n))
                if r != q:
                    c.cz(r, q)
        for part in plan_parts(c, n):
            if part[0] != "segment":
                continue
            scat, floor = PB.stage_requirements(part[1])
            assert len(scat) <= PB.SCATTER_MAX
            assert floor + len(scat) <= PB.max_block_row_bits()


def test_maybe_sweep_honors_knob(monkeypatch):
    c = Circuit(N)
    for q in range(7):
        c.h(q)
    (p,) = plan_parts(c)
    monkeypatch.setenv("QUEST_SWEEP_FUSION", "0")
    assert len(PB.maybe_sweep([p, p], N)) == 2
    rec = c.plan_stats()["fused"]
    assert not rec["sweeps_enabled"]
    assert rec["hbm_sweeps"] == rec["full_state_passes"]
    monkeypatch.setenv("QUEST_SWEEP_FUSION", "1")
    assert len(PB.maybe_sweep([p, p], N)) == 1


def test_sweep_stats_shape():
    c = Circuit(N)
    c.h(0)
    parts = plan_parts(c)
    sw = PB.sweep_stats(PB.sweep_plan(parts * 3, N))
    assert sw["hbm_sweeps"] == sw["kernel_sweeps"] == 1
    assert sw["xla_passthroughs"] == 0
    assert sw["sweep_stages"] == [3]


# ---------------------------------------------------------------------------
# randomized equivalence: 50 mixed circuits vs the dense oracle
# ---------------------------------------------------------------------------

_SEG_CACHE: dict = {}   # shared across the suite: identical-structure
# sweeps compile once (operands ride as kernel inputs)


def _template_circuit(n: int, tmpl: int, inst: int) -> Circuit:
    """A random mixed circuit whose gate PATTERN depends only on `tmpl`
    (so kernel structures repeat across instances) and whose parameters
    on (tmpl, inst). Mixes diagonal, non-diagonal and 2-qubit gates
    over every band of the register."""
    srng = np.random.default_rng(1000 + tmpl)        # structure
    arng = np.random.default_rng(7000 + 97 * tmpl + inst)  # angles
    c = Circuit(n)
    for _ in range(10):
        kind = int(srng.integers(0, 8))
        q = int(srng.integers(0, n))
        r = int(srng.integers(0, n))
        if r == q:
            r = (q + 1) % n
        ang = float(arng.uniform(0, 2 * np.pi))
        if kind == 0:
            c.h(q)
        elif kind == 1:
            c.rx(q, ang)
        elif kind == 2:
            c.ry(q, ang)
        elif kind == 3:
            c.rz(q, ang)
        elif kind == 4:
            c.phase(q, ang)                          # diagonal
        elif kind == 5:
            c.cz(q, r)                               # allones
        elif kind == 6:
            c.cnot(q, r)                             # controlled matrix
        else:
            c.multi_rotate_z(sorted({q, r}), ang)    # parity
    return c


def _oracle_vec(amps_planes: np.ndarray) -> np.ndarray:
    return (amps_planes[0].astype(np.complex128)
            + 1j * amps_planes[1].astype(np.complex128))


def _oracle_apply_ops(vec: np.ndarray, n: int, ops) -> np.ndarray:
    """Apply original GateOps to a dense complex vector (tests/oracle)."""
    for op in ops:
        k = len(op.targets)
        if op.kind == "matrix":
            mat = np.asarray(op.operand, dtype=np.complex128)
        elif op.kind == "diagonal":
            mat = np.diag(np.asarray(op.operand,
                                     dtype=np.complex128).reshape(-1))
        elif op.kind == "parity":
            diag = np.ones(1 << k, dtype=np.complex128)
            half = float(op.operand) / 2.0
            for i in range(1 << k):
                par = bin(i).count("1") & 1
                diag[i] = np.exp(-1j * half * (-1.0) ** par)
            mat = np.diag(diag)
        elif op.kind == "allones":
            diag = np.ones(1 << k, dtype=np.complex128)
            diag[-1] = complex(op.operand)
            mat = np.diag(diag)
        else:
            raise AssertionError(op.kind)
        vec = oracle.apply_to_vector(vec, n, mat, op.targets,
                                     op.controls, op.cstates)
    return vec


def _run_swept_parts(parts, n: int, amps_planes: np.ndarray) -> np.ndarray:
    """Execute a (swept) part list in the Pallas interpreter, sharing
    compiled kernels through the suite-wide structure cache."""
    out = jnp.asarray(amps_planes).reshape(2, -1, PB.LANES)
    for part in parts:
        assert part[0] == "segment", "templates avoid XLA passthroughs"
        fn = PB.compile_segment_cached(_SEG_CACHE, tuple(part[1]), n,
                                       interpret=True)
        out = fn(out, part[2])
    return np.asarray(out).reshape(2, -1)


_CASES_F32 = [(t, i) for t in range(8) for i in range(5)]      # 40
_CASES_F64 = [(8, i) for i in range(5)]                        # 5
_CASES_SHARDED = [(9, 0, np.float32), (9, 1, np.float32),
                  (9, 2, np.float32), (10, 0, np.float64),
                  (10, 1, np.float64)]                         # 5 -> 50


@pytest.mark.parametrize("tmpl,inst", _CASES_F32)
def test_sweep_fused_matches_oracle_f32(tmpl, inst):
    """Two applications' segment plans concatenated and sweep-fused
    (the cross-iteration merge in miniature) executed through the
    interpreter must match the oracle applying the circuit twice."""
    c = _template_circuit(N, tmpl, inst)
    rng = np.random.default_rng(inst)
    amps = rng.standard_normal((2, 1 << N)).astype(np.float32)
    parts = plan_parts(c)
    swept = PB.sweep_plan(parts * 2, N)
    assert len(swept) <= len(parts) * 2
    got = _run_swept_parts(swept, N, amps)
    want = _oracle_apply_ops(_oracle_vec(amps), N, list(c.ops) * 2)
    scale = max(1.0, float(np.max(np.abs(want))))
    np.testing.assert_allclose(got[0] + 1j * got[1], want,
                               atol=EPS_F32 * scale, rtol=0)


@pytest.mark.parametrize("tmpl,inst", _CASES_F64)
def test_sweep_fused_matches_oracle_f64_limb(tmpl, inst):
    """f64 registers ride the fused engine's banded-XLA fallback; the
    sweep knob must leave their numerics bit-faithful to the oracle at
    f64 eps (sweeps only regroup f32 kernel launches)."""
    c = _template_circuit(N, tmpl, inst)
    rng = np.random.default_rng(100 + inst)
    amps = rng.standard_normal((2, 1 << N)).astype(np.float64)
    fn = c.compiled_fused(N, density=False, donate=False, interpret=True)
    got = np.asarray(fn(jnp.asarray(amps))).reshape(2, -1)
    want = _oracle_apply_ops(_oracle_vec(amps), N, c.ops)
    scale = max(1.0, float(np.max(np.abs(want))))
    np.testing.assert_allclose(got[0] + 1j * got[1], want,
                               atol=EPS_F64 * scale, rtol=0)


@pytest.mark.parametrize("tmpl,inst,rdt", _CASES_SHARDED)
def test_sweep_fused_matches_oracle_sharded(tmpl, inst, rdt):
    """Per-shard sweeps (parallel.sharded._plan_fused_parts) on a
    2-device CPU mesh: the sharded fused engine with sweep fusion on
    must match the oracle — f32 through interpret-mode kernels, f64
    through the banded schedule over the same plan."""
    from quest_tpu.parallel.mesh import make_amp_mesh

    n = 11                      # local_n = 10: kernel tier on each shard
    mesh = make_amp_mesh(2)
    c = _template_circuit(n, tmpl, inst)
    rng = np.random.default_rng(200 + 10 * tmpl + inst)
    amps = rng.standard_normal((2, 1 << n)).astype(rdt)
    fn = c.compiled_sharded_fused(n, density=False, mesh=mesh,
                                  donate=False, interpret=True)
    got = np.asarray(fn(jnp.asarray(amps))).reshape(2, -1)
    want = _oracle_apply_ops(_oracle_vec(amps), n, c.ops)
    eps = EPS_F32 if rdt == np.float32 else EPS_F64
    scale = max(1.0, float(np.max(np.abs(want))))
    np.testing.assert_allclose(got[0] + 1j * got[1], want,
                               atol=eps * scale, rtol=0)


def test_compiled_fused_cross_iteration_end_to_end():
    """The engine-level integration: compiled_fused(iters=4) merges the
    unrolled applications into one launch (plan-asserted) and matches
    the oracle applying the circuit 4 times."""
    n = N
    c = Circuit(n)
    for q in range(7):
        c.h(q)
    c.cz(0, 8)
    c.rz(9, 0.4)
    parts = plan_parts(c)
    assert len(PB.sweep_plan(parts * 4, n)) == 1
    rng = np.random.default_rng(3)
    amps = rng.standard_normal((2, 1 << n)).astype(np.float32)
    fn = c.compiled_fused(n, density=False, donate=False,
                          interpret=True, iters=4)
    got = np.asarray(fn(jnp.asarray(amps))).reshape(2, -1)
    want = _oracle_apply_ops(_oracle_vec(amps), n, list(c.ops) * 4)
    scale = max(1.0, float(np.max(np.abs(want))))
    np.testing.assert_allclose(got[0] + 1j * got[1], want,
                               atol=EPS_F32 * scale, rtol=0)


# ---------------------------------------------------------------------------
# decoupled multi-buffer pipeline (QUEST_FUSED_PIPELINE, ISSUE 11):
# bit-identity vs the legacy in-place driver, schedule introspection,
# and the slot/VMEM accounting
# ---------------------------------------------------------------------------


def _run_parts_fresh(parts, n: int, amps_planes: np.ndarray) -> np.ndarray:
    """Execute a part list through a FRESH kernel cache — the knob
    A/B below flips QUEST_FUSED_PIPELINE between runs, and
    compile_segment_cached's key deliberately does not carry it (the
    engines key their caches on engine_mode_key at circuit level), so
    sharing _SEG_CACHE across the flip would hand back stale drivers."""
    cache: dict = {}
    out = jnp.asarray(amps_planes).reshape(2, -1, PB.LANES)
    for part in parts:
        fn = PB.compile_segment_cached(cache, tuple(part[1]), n,
                                       interpret=True)
        out = fn(out, part[2])
    return np.asarray(out).reshape(2, -1)


@pytest.mark.parametrize("tmpl", [0, 3, 6])
def test_decoupled_pipeline_bit_identical_f32(tmpl, monkeypatch):
    """The decoupled rings only reschedule DMA — the same _step_index
    walk, the same stage chain, the same float ops per block — so the
    output must be BIT-identical to the legacy in-place driver, not
    merely close (interpret mode makes the comparison deterministic)."""
    c = _template_circuit(N, tmpl, 0)
    rng = np.random.default_rng(50 + tmpl)
    amps = rng.standard_normal((2, 1 << N)).astype(np.float32)
    swept = PB.sweep_plan(plan_parts(c) * 2, N)
    monkeypatch.setenv("QUEST_FUSED_PIPELINE", "1")
    got_new = _run_parts_fresh(swept, N, amps)
    monkeypatch.setenv("QUEST_FUSED_PIPELINE", "0")
    got_old = _run_parts_fresh(swept, N, amps)
    np.testing.assert_array_equal(got_new, got_old)


def test_decoupled_pipeline_bit_identical_f64_limb(monkeypatch):
    """f64 registers ride the banded fallback inside compiled_fused —
    the pipeline knob must leave that path untouched bit-for-bit (it
    only selects Pallas kernel drivers, which f64 never reaches)."""
    c = _template_circuit(N, 8, 0)
    rng = np.random.default_rng(60)
    amps = rng.standard_normal((2, 1 << N)).astype(np.float64)
    monkeypatch.setenv("QUEST_FUSED_PIPELINE", "1")
    on = np.asarray(c.compiled_fused(N, density=False, donate=False,
                                     interpret=True)(jnp.asarray(amps)))
    monkeypatch.setenv("QUEST_FUSED_PIPELINE", "0")
    off = np.asarray(c.compiled_fused(N, density=False, donate=False,
                                      interpret=True)(jnp.asarray(amps)))
    np.testing.assert_array_equal(on, off)


def test_decoupled_pipeline_bit_identical_batched(monkeypatch):
    """B>1: the batched step space (batch slowest, blocks back-to-back)
    through the decoupled rings matches the legacy driver shot-for-shot
    — the batch quotient/idx_of walk is shared, so any divergence would
    be a slot-schedule bug."""
    c = _template_circuit(N, 1, 0)
    rng = np.random.default_rng(61)
    amps_b = rng.standard_normal((3, 2, 1 << N)).astype(np.float32)
    monkeypatch.setenv("QUEST_FUSED_PIPELINE", "1")
    on = np.asarray(c.compiled_batched(3, donate=False, interpret=True)(
        jnp.asarray(amps_b)))
    monkeypatch.setenv("QUEST_FUSED_PIPELINE", "0")
    off = np.asarray(c.compiled_batched(3, donate=False, interpret=True)(
        jnp.asarray(amps_b)))
    np.testing.assert_array_equal(on, off)


def test_decoupled_pipeline_bit_identical_sharded(monkeypatch):
    """2-device mesh: per-shard sweeps through the decoupled rings
    match the legacy driver bit-for-bit (collectives are outside the
    kernels and identical on both sides)."""
    from quest_tpu.parallel.mesh import make_amp_mesh

    n = 11
    mesh = make_amp_mesh(2)
    c = _template_circuit(n, 9, 0)
    rng = np.random.default_rng(62)
    amps = rng.standard_normal((2, 1 << n)).astype(np.float32)
    monkeypatch.setenv("QUEST_FUSED_PIPELINE", "1")
    on = np.asarray(c.compiled_sharded_fused(
        n, density=False, mesh=mesh, donate=False, interpret=True)(
        jnp.asarray(amps)))
    monkeypatch.setenv("QUEST_FUSED_PIPELINE", "0")
    off = np.asarray(c.compiled_sharded_fused(
        n, density=False, mesh=mesh, donate=False, interpret=True)(
        jnp.asarray(amps)))
    np.testing.assert_array_equal(on, off)


def test_pipeline_stats_and_knob_off_bit_for_bit(monkeypatch):
    """plan_stats()['fused'] reports the pipeline schedule CPU-side
    when the decoupled driver is active, and QUEST_FUSED_PIPELINE=0
    reproduces the legacy record BIT-FOR-BIT — same keys, same values,
    no pipeline_* keys (the A/B control cannot drift; the CI gate in
    scripts/check_sweep_golden.py runs the same comparison at 30q)."""
    c = bench._build_circuit(16)
    monkeypatch.setenv("QUEST_FUSED_PIPELINE", "1")
    on = c.plan_stats()["fused"]
    assert on["pipeline_in_slots"] == PB.PIPELINE_IN_SLOTS
    assert on["pipeline_out_slots"] == PB.PIPELINE_OUT_SLOTS
    assert on["pipeline_overlap_steps"] >= 0
    monkeypatch.setenv("QUEST_FUSED_PIPELINE", "0")
    off = c.plan_stats()["fused"]
    assert not any(k.startswith("pipeline_") for k in off)
    assert off == {k: v for k, v in on.items()
                   if not k.startswith("pipeline_")}


def test_pipeline_overlap_on_headline_plan(monkeypatch):
    """The 30q headline plan must schedule read-ahead: every sweep's
    step count exceeds the in-ring, so pipeline_overlap_steps >= 1 —
    the next block's DMA streams under the current block's stage loop
    (mirrors the check_sweep_golden.py gate)."""
    monkeypatch.setenv("QUEST_FUSED_PIPELINE", "1")
    rec = bench._build_circuit(30).plan_stats()["fused"]
    assert rec["pipeline_overlap_steps"] >= 1, rec


def test_sweep_operand_budget_driver_aware(monkeypatch):
    """sweep_plan's operand budget pays for the decoupled rings' extra
    block slot: 40 MiB with the pipeline on, the original 48 MiB with
    it off — so knob-off plans are the old plans exactly."""
    monkeypatch.setenv("QUEST_FUSED_PIPELINE", "1")
    assert PB.sweep_operand_budget() == PB.PIPELINE_SWEEP_OPERAND_BYTES
    monkeypatch.setenv("QUEST_FUSED_PIPELINE", "0")
    assert PB.sweep_operand_budget() == PB.SWEEP_OPERAND_BYTES


def test_sweep_vmem_accounting_adversarial(monkeypatch):
    """The slot/VMEM accounting (sweep_vmem_bytes) never exceeds the
    100 MiB scoped budget for ANY geometry the planner can produce:
    max scattered axes, the b1 sublane floor + scattered mix at the
    row-bit cap, and an operand-heavy sweep AT the operand budget —
    each under both the decoupled and the legacy schedule. This is the
    invariant that lets sweep_plan merge on byte budgets instead of
    compiling to find out."""
    n = 28      # deep enough that a full high band (scat bits 14..20)
    # is a REAL geometry — the band must fit under the register top

    def scb_stage(bit, d):
        return PB.MatStage("scb", d, False, (), (), bit)

    def dense_seg(stages):
        return [np.zeros((2, max(st.dim, 2), max(st.dim, 2)),
                         np.float32) for st in stages]

    # 7 scattered axes (a full high band), the worst block geometry
    worst_scat = [scb_stage(14, 128)]
    # b1 floor + scattered bits at the row budget
    b1 = PB.MatStage("b1", 128, False, (), ())
    mixed = [b1] + [PB.MatStage("sc", 2, False, (), (), 12 + j)
                    for j in range(PB.max_block_row_bits() - 7)]
    # operand-heavy: dense 128x128 pairs right up to the operand budget
    dense = [PB.MatStage("b0", 128, False, (), ())] * 64

    for knob in ("1", "0"):
        monkeypatch.setenv("QUEST_FUSED_PIPELINE", knob)
        budget = PB.sweep_operand_budget()
        for stages in (worst_scat, mixed, dense):
            arrays = dense_seg(stages)
            nbytes = sum(a.nbytes for a in arrays)
            if nbytes > budget:      # sweep_plan would refuse to merge
                continue             # past the budget; clamp like it
            rec = PB.sweep_vmem_bytes(stages, arrays, n)
            assert rec["total_bytes"] <= rec["budget_bytes"], \
                (knob, len(stages), rec)
        # the budget itself is sized so slots + a FULL operand budget
        # still fit the scoped limit (the headroom claim of
        # docs/SWEEPS.md "VMEM accounting")
        rec = PB.sweep_vmem_bytes(worst_scat, dense_seg(worst_scat), n)
        assert rec["slot_bytes"] + budget <= PB.VMEM_LIMIT_BYTES, \
            (knob, rec, budget)


def test_sweep_vmem_matches_planned_geometry():
    """Every sweep the planner emits for random circuits satisfies the
    accounting: sweep_steps/sweep_vmem_bytes derive from
    segment_geometry — the SAME resolution compile_segment uses — so
    a plan that passes the merge rule can always be compiled."""
    rng = np.random.default_rng(7)
    for n in (N, 17):
        c = Circuit(n)
        for _ in range(30):
            q = int(rng.integers(0, n))
            c.ry(q, float(rng.uniform(0, 2 * np.pi)))
        for part in PB.sweep_plan(plan_parts(c, n), n):
            if part[0] != "segment":
                continue
            rec = PB.sweep_vmem_bytes(part[1], part[2], n)
            assert rec["total_bytes"] <= rec["budget_bytes"], rec
            assert PB.sweep_steps(part[1], n) >= 1
            assert PB.sweep_steps(part[1], n, batch=4) == \
                4 * PB.sweep_steps(part[1], n)


def test_explain_reports_sweeps(monkeypatch):
    monkeypatch.setenv("QUEST_SWEEP_FUSION", "1")
    c = bench._build_circuit(16)
    assert "sweep fusion: on" in c.explain()
    monkeypatch.setenv("QUEST_SWEEP_FUSION", "0")
    assert "sweep fusion: OFF" in c.explain()


def test_explain_sharded_reports_sweeps():
    from quest_tpu.parallel.mesh import make_amp_mesh
    c = _template_circuit(11, 0, 0)
    text = c.explain_sharded(make_amp_mesh(2), engine="fused")
    assert "local kernel sweeps:" in text
