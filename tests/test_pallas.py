"""Pallas band-segment engine tests (quest_tpu/ops/pallas_band.py), run
in the Pallas interpreter on CPU: fused execution must match the XLA
per-gate path across every stage type — band-0/1/2 matmuls, diagonal and
parity phases, controls in every position, segment breaks, multi-block
grids, and density duals."""

import numpy as np
import pytest

import quest_tpu as qt
from quest_tpu.circuit import Circuit, random_circuit, qft_circuit
from quest_tpu.ops import fusion as F
from quest_tpu.ops import pallas_band as PB
from quest_tpu.state import to_dense

from . import oracle

N = 10  # 8 rows x 128 lanes — the smallest cleanly-tiled register


def parts_of(c: Circuit, n=N, scatter_max=PB.SCATTER_MAX):
    items = F.plan(c.ops, n, bands=PB.plan_bands(n))
    return PB.segment_plan(items, n, scatter_max)


def check(circ: Circuit, n=N, density=False, tol=1e-5):
    make = qt.create_density_qureg if density else qt.create_qureg
    q = qt.init_debug_state(make(n if not density else n // 2))
    want = to_dense(circ.apply(q))
    got = to_dense(circ.apply_fused(q, interpret=True))
    # f32 relative precision against the debug state's large amplitudes
    scale = max(1.0, float(np.max(np.abs(want))))
    np.testing.assert_allclose(got, want, atol=tol * scale, rtol=0)


def test_band0_gates_fuse_to_one_stage():
    c = Circuit(N)
    for q in range(PB.LANE_QUBITS):
        c.h(q)
    c.cnot(0, 1)
    c.z(2)
    c.s(3)
    c.t(4)
    parts = parts_of(c)
    assert len(parts) == 1
    kind, stages, arrays = parts[0]
    assert kind == "segment" and len(stages) == 1
    assert stages[0].kind == "b0" and len(arrays) == 1
    check(c)


@pytest.mark.parametrize("q", range(7, N))
def test_row_qubit_gates(q):
    c = Circuit(N)
    c.h(q)
    c.ry(q, 0.37)
    parts = parts_of(c)
    assert [p[0] for p in parts] == ["segment"]
    check(c)


@pytest.mark.parametrize("q", range(7, N))
def test_row_diag(q):
    c = Circuit(N)
    c.s(q)
    c.phase(q, 0.41)
    check(c)


def test_parity_mixed():
    c = Circuit(N)
    c.rz(2, 0.3)
    c.rz(8, 0.5)
    c.multi_rotate_z((1, 5, 9), 0.7)
    check(c)


def test_allones_mixed():
    c = Circuit(N)
    c.cz(0, 1)          # both lanes
    c.cz(2, 9)          # lane target controlled on row qubit
    c.cz(7, 8)          # row target controlled on row qubit
    check(c)


def test_controls_every_position():
    c = Circuit(N)
    c.x(0, 3)            # lane target, lane control
    c.x(1, 8)            # lane target, row control
    c.x(9, 2)            # row target, lane control
    c.x(7, 9)            # row target, row control
    parts = parts_of(c)
    # all four fuse — none falls through to the XLA path
    assert [p[0] for p in parts] == ["segment"]
    check(c)


def test_cross_band_2q_fuses_via_kak():
    rng = np.random.default_rng(3)
    z = rng.normal(size=(4, 4)) + 1j * rng.normal(size=(4, 4))
    u, _ = np.linalg.qr(z)
    c = Circuit(N)
    c.h(0)
    c.gate(u, (3, 8))     # cross-band 2q unitary -> KAK, stays fused
    c.h(9)
    parts = parts_of(c)
    assert [p[0] for p in parts] == ["segment"]
    check(c, tol=5e-5)


def test_cross_band_superop_fuses_as_pair_stage():
    # 6q density register: superop targets (1, 7) straddle bands; the
    # non-unitary superoperator fuses as a PairStage (lane op, sliced
    # sublane qubit)
    c = Circuit(6)
    c.damping(1, 0.2)
    items = F.plan(c._flat_ops(12, True), 12, bands=PB.plan_bands(12))
    parts = PB.segment_plan(items, 12)
    assert [p[0] for p in parts] == ["segment"]
    kinds = [type(s).__name__ for s in parts[0][1]]
    assert "PairStage" in kinds


@pytest.mark.parametrize("nq", [6, 8])
def test_density_channels_fuse_at_scale(nq):
    """Channels on registers whose doubled targets straddle bands run
    through PairStages (all three op kinds: lane / b1 / scattered) and
    match the per-gate engine."""
    c = Circuit(nq)
    c.h(0)
    c.cnot(0, nq - 1)
    c.damping(1, 0.2)         # lane-op pair
    c.damping(nq - 1, 0.3)    # nq=8: targets (7,15) -> b1-op pair
    c.depolarising(nq - 2, 0.1)
    c.dephasing(0, 0.15)
    q1 = qt.init_debug_state(qt.create_density_qureg(nq))
    want = to_dense(c.apply(q1))
    got = to_dense(c.apply_fused(
        qt.init_debug_state(qt.create_density_qureg(nq)), interpret=True))
    scale = max(1.0, float(np.max(np.abs(want))))
    np.testing.assert_allclose(got, want, atol=5e-5 * scale, rtol=0)


def test_scat_scat_pair_stage():
    """A 2q matrix with both qubits on scattered axes of DIFFERENT high
    bands (the 'sc' op kind PairStage): numerics vs the per-gate
    engine."""
    rng = np.random.default_rng(9)
    n = 23
    m = rng.standard_normal((4, 4)) + 1j * rng.standard_normal((4, 4))
    # non-unitary so the KAK path cannot take it
    m = m @ np.diag([1.0, 0.8, 0.9, 1.0])
    c = Circuit(n)
    c.h(0)
    c._add("matrix", (14, 21), m.astype(np.complex128))
    items = F.plan(c.ops, n, bands=PB.plan_bands(n))
    parts = PB.segment_plan(items, n)
    assert [p[0] for p in parts] == ["segment"]
    kinds = [type(s).__name__ for s in parts[0][1]]
    assert "PairStage" in kinds
    import jax.numpy as jnp
    amps = jnp.zeros((2, 1 << n), dtype=jnp.float32).at[0, 3].set(1.0)
    got = np.asarray(c.compiled_fused(n, density=False, donate=False,
                                      interpret=True)(amps)).reshape(2, -1)
    want = np.asarray(c.compiled(n, density=False, donate=False)(amps))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


def test_same_high_band_2q_composes_to_scb():
    """A 2q matrix whose qubits share one high band composes into that
    band's scb operator — no PairStage, no passthrough."""
    rng = np.random.default_rng(9)
    n = 17
    m = rng.standard_normal((4, 4)) + 1j * rng.standard_normal((4, 4))
    m = m @ np.diag([1.0, 0.8, 0.9, 1.0])  # non-unitary: no KAK escape
    c = Circuit(n)
    c.h(0)
    c._add("matrix", (14, 16), m.astype(np.complex128))
    parts = parts_of(c, n=n)
    assert [p[0] for p in parts] == ["segment"]
    kinds = [s.kind for s in parts[0][1]]
    assert kinds == ["b0", "scb"]
    import jax.numpy as jnp
    amps = jnp.zeros((2, 1 << n), dtype=jnp.float32).at[0, 3].set(1.0)
    got = np.asarray(c.compiled_fused(n, density=False, donate=False,
                                      interpret=True)(amps)).reshape(2, -1)
    want = np.asarray(c.compiled(n, density=False, donate=False)(amps))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


def test_small_register_superop_fuses():
    # 4q density register: superop targets (1, 5) sit in ONE band, so the
    # (non-unitary) superoperator embeds straight into the band operator
    c = Circuit(4)
    c.damping(1, 0.2)
    items = F.plan(c._flat_ops(8, True), 8, bands=PB.plan_bands(8))
    parts = PB.segment_plan(items, 8)
    assert [p[0] for p in parts] == ["segment"]


def test_scattered_qubits_fuse():
    """Gates on high qubits compose into ONE scb stage per high band —
    one MXU dot over the band's merged scattered axes, no passthrough."""
    n = 16
    c = Circuit(n)
    c.h(0)
    for q in (14, 15):
        c.ry(q, 0.1 * q)      # both in the (14, 2) high band
    parts = parts_of(c, n=n)
    assert [p[0] for p in parts] == ["segment"]
    kinds = [s.kind for s in parts[0][1]]
    assert kinds == ["b0", "scb"]
    assert parts[0][1][1].dim == 4
    check(c, n=n)


@pytest.mark.slow          # ~9 s — tier-1 budget discipline; the
                           # sparse-high-band SCB test keeps
                           # scattered-bit coverage in tier-1
def test_full_high_band_scb():
    """A whole 7-qubit high band (d=128 scb) plus gates in every other
    band and a cross-band CZ — numerics through the interpreter. The
    rotation layer composes to ONE wide dot: splitting a factorizable
    band op into narrow per-factor dots measured 3.8x SLOWER on chip
    (161 vs 42.6 ms/pass at 30q — a small-M dot idles most of the MXU,
    so narrow-stage time is ~flat in d), so the planner must keep the
    composed d=128 stage."""
    n = 23
    c = Circuit(n)
    for q in range(14, 21):
        c.ry(q, 0.1 * (q - 13))
    c.cz(13, 14)              # crosses the sublane/high-band split
    c.h(2)
    c.ry(9, 0.3)
    c.x(21, 15)               # top-band target, scb-band control — its
    # band's 2 scat bits exceed the budget next to the d=128 scb's 7, so
    # a second segment starts (still no XLA passthrough)
    parts = parts_of(c, n=n)
    assert [p[0] for p in parts] == ["segment", "segment"]
    kinds = [s.kind for s in parts[0][1] if hasattr(s, "kind")]
    assert "scb" in kinds
    assert any(getattr(s, "dim", 0) == 128 and s.kind == "scb"
               for s in parts[0][1])
    check(c, n=n)


def test_oversized_band_passthrough_under_small_budget():
    """A high-band operator spanning more scattered bits than the budget
    allows even in a fresh segment must fall back to an XLA passthrough,
    never silently over-claim axes. (A lone h(14) no longer triggers
    this — sub-band extraction shrinks it to one scattered bit.)"""
    n = 23
    c = Circuit(n)
    c.h(14)
    c.h(20)                   # composed span covers the whole (14, 7) band
    parts = parts_of(c, n=n, scatter_max=5)
    assert [p[0] for p in parts] == ["xla"]
    assert isinstance(parts[0][1], F.BandOp) and parts[0][1].w == 7


def test_sparse_high_band_extracts_sub_band():
    """A lone high-qubit gate costs one scattered-bit butterfly, and a
    2-qubit-support run costs a d=4 sub-band dot — never the padded
    full-band contraction."""
    n = 23
    c = Circuit(n)
    c.h(16)
    parts = parts_of(c, n=n)
    (st,) = parts[0][1]
    assert st.kind == "sc" and st.bit == 9 and st.dim == 2
    check(c, n=n)

    c2 = Circuit(n)
    c2.ry(15, 0.3)
    c2.ry(16, 0.7)
    c2.cz(15, 16)
    parts = parts_of(c2, n=n)
    (st,) = parts[0][1]
    assert st.kind == "scb" and st.bit == 8 and st.dim == 4
    check(c2, n=n)


def test_scatter_overflow_splits_segment():
    """Two high bands whose scattered axes exceed the scatter budget get
    separate segments; numerics still match."""
    n = 23
    c = Circuit(n)
    c.h(14)
    c.h(20)                   # span = the whole (14, 7) band: 7 scat bits
    c.h(21)                   # band (21, 2): 1 more
    parts = parts_of(c, n=n, scatter_max=7)
    assert [p[0] for p in parts] == ["segment", "segment"]
    # numerics at the tiny scatter budget
    import jax.numpy as jnp
    amps = jnp.zeros((2, 1 << n), dtype=jnp.float32).at[0, 0].set(1.0)
    out = amps.reshape(2, -1, PB.LANES)
    for part in parts:
        out = PB.compile_segment(part[1], n, interpret=True)(
            out, part[2])
    want = c.compiled(n, density=False, donate=False)(amps)
    np.testing.assert_allclose(np.asarray(out.reshape(2, -1)),
                               np.asarray(want), atol=1e-5, rtol=0)


def test_random_circuit_fused_matches():
    c = random_circuit(N, depth=6, seed=11)
    check(c, tol=5e-5)


def test_qft_fused_matches():
    check(qft_circuit(N), tol=5e-5)


def test_density_fused_matches():
    c = Circuit(5)
    c.h(0)
    c.cnot(0, 1)
    c.rz(4, 0.3)
    c.ry(2, 0.8)
    c.cz(1, 3)
    check(c, n=10, density=True, tol=5e-5)


def test_multi_block_grid():
    """Small block size -> many grid blocks: pid-dependent paths (global
    row ids for masks/diagonals/parity, BlockSpec index maps) must agree
    with the XLA engine."""
    n = 17  # rows_eff_bits=7 -> grid over 8 blocks of 128 rows
    c = Circuit(n)
    c.h(0)
    c.h(8)               # sublane butterfly within a block
    c.rz(16, 0.3)        # parity on a grid row bit
    c.s(7)
    c.x(1, 16)           # lane target controlled on a GRID row qubit
    c.cz(2, 15)          # phase with a grid row bit
    items = F.plan(c.ops, n, bands=PB.plan_bands(n))
    parts = PB.segment_plan(items, n)
    assert [p[0] for p in parts] == ["segment"]
    import jax.numpy as jnp
    amps = jnp.zeros((2, 1 << n), dtype=jnp.float32).at[0, 0].set(1.0)
    for part in parts:
        amps = PB.compile_segment(part[1], n, rows_eff_bits=7,
                                  interpret=True)(amps, part[2])
    want = c.compiled(n, density=False, donate=False)(
        jnp.zeros((2, 1 << n), dtype=jnp.float32).at[0, 0].set(1.0))
    np.testing.assert_allclose(np.asarray(amps.reshape(2, -1)),
                               np.asarray(want), atol=1e-5, rtol=0)


def test_small_register_falls_back():
    c = Circuit(4)
    c.h(0)
    q = qt.create_qureg(4)
    got = to_dense(c.apply_fused(q, interpret=True))
    want = to_dense(c.apply(qt.create_qureg(4)))
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_noisy_circuit_channels():
    """Noise channels compiled into a circuit (superop ops) match the
    eager channel path — on both the XLA and fused engines."""
    from quest_tpu.ops import channels as ch

    c = Circuit(5)
    c.h(0)
    c.cnot(0, 1)
    c.damping(1, 0.2)
    c.depolarising(0, 0.3)
    c.dephasing(2, 0.25)
    c.ry(3, 0.4)

    # eager reference result
    q = qt.init_debug_state(qt.create_density_qureg(5))
    from quest_tpu.ops import gates as G
    e = G.hadamard(q, 0)
    e = G.controlled_not(e, 0, 1)
    e = ch.mix_damping(e, 1, 0.2)
    e = ch.mix_depolarising(e, 0, 0.3)
    e = ch.mix_dephasing(e, 2, 0.25)
    e = G.rotate_y(e, 3, 0.4)
    want = to_dense(e)

    got_xla = to_dense(c.apply(qt.init_debug_state(qt.create_density_qureg(5))))
    got_fused = to_dense(c.apply_fused(
        qt.init_debug_state(qt.create_density_qureg(5)), interpret=True))
    scale = max(1.0, float(np.max(np.abs(want))))
    np.testing.assert_allclose(got_xla, want, atol=1e-5 * scale, rtol=0)
    np.testing.assert_allclose(got_fused, want, atol=1e-5 * scale, rtol=0)


def test_channels_need_density_register():
    from quest_tpu.validation import QuESTError
    c = Circuit(3)
    c.damping(0, 0.1)
    with pytest.raises(QuESTError, match="density"):
        c.apply(qt.create_qureg(3))


def test_channels_need_density_register_all_engines():
    from quest_tpu.validation import QuESTError
    from quest_tpu.parallel.mesh import make_amp_mesh
    c = Circuit(12)
    c.damping(0, 0.1)
    with pytest.raises(QuESTError, match="density"):
        c.apply_fused(qt.create_qureg(12), interpret=True)
    with pytest.raises(QuESTError, match="density"):
        c.apply_banded(qt.create_qureg(12))
    mesh = make_amp_mesh(1)
    with pytest.raises(QuESTError, match="density"):
        c.compiled_sharded(12, density=False, mesh=mesh)


def test_channel_builders_validate():
    from quest_tpu.validation import QuESTError
    c = Circuit(3)
    with pytest.raises(QuESTError, match="[Pp]robabilit"):
        c.damping(0, 1.2)
    with pytest.raises(QuESTError, match="[Pp]robabilit"):
        c.depolarising(0, 0.9)
    with pytest.raises(QuESTError, match="[Pp]robabilit"):
        c.dephasing(0, 0.6)
    with pytest.raises(QuESTError):
        c.kraus(0, [np.eye(2) * 0.5])          # non-CPTP
    with pytest.raises(QuESTError):
        c.kraus((0, 1), [np.eye(2)])           # dim mismatch


@pytest.mark.slow          # ~18 s on this host — tier-1 budget
                           # discipline (runs in the full CI suite step)
def test_deep_circuit_segment_stage_cap():
    """Deep circuits split at MAX_SEGMENT_STAGES so kernel operand blocks
    cannot accumulate without bound in VMEM; numerics unchanged."""
    rng = np.random.default_rng(7)
    n, depth = 12, 60
    c = Circuit(n)
    for d in range(depth):
        for q in range(n):
            c.rx(q, float(rng.uniform(0, 2 * np.pi)))
        for q in range(d % 2, n - 1, 2):
            c.cz(q, q + 1)
    parts = parts_of(c, n=n)
    segs = [p for p in parts if p[0] == "segment"]
    assert len(segs) >= 2
    assert all(len(s[1]) <= PB.MAX_SEGMENT_STAGES + 1 for s in segs)
    check(c, n=n, tol=5e-5)


class TestMatmulPrecisionTiers:
    """The session precision knob on the fused engine: HIGHEST (default,
    6-pass f32-exact) and HIGH (manual double-bf16 3-pass inside the
    kernel — Mosaic lowers only DEFAULT/HIGHEST, so _mxu_dot_general
    splits the operands itself at half the MXU passes, ~5e-6 relative
    error per dot measured vs an f64 oracle)."""

    def _run(self, tier):
        from quest_tpu import precision as P
        rng = np.random.default_rng(3)
        n = 12
        c = Circuit(n)
        for d in range(3):
            for q in range(n):
                c.rx(q, float(rng.uniform(0, 2 * np.pi)))
            for q in range(d % 2, n - 1, 2):
                c.cz(q, q + 1)
        old = P.matmul_precision()
        P.set_matmul_precision(tier)
        try:
            q = qt.init_debug_state(qt.create_qureg(n))
            return to_dense(c.apply_fused(q, interpret=True))
        finally:
            P.set_matmul_precision(old)

    def test_high_tier_accuracy_envelope(self):
        """HIGH must stay within ~1e-4 of the HIGHEST (f32-exact) result
        on a depth-3 mixed circuit (per-dot 5e-6, accumulated) — far
        inside the ~1e-3 drift single-pass bf16 (DEFAULT) shows."""
        got = self._run("high")
        want = self._run("highest")
        scale = float(np.max(np.abs(want)))   # debug-state amps are large
        err = float(np.max(np.abs(got - want))) / scale
        assert err < 1e-4, f"HIGH tier drifted {err} (relative) from HIGHEST"
        # the relative norm must be preserved to the same envelope
        n_got = float(np.sum(np.abs(got.astype(np.complex128)) ** 2))
        n_want = float(np.sum(np.abs(want.astype(np.complex128)) ** 2))
        assert abs(n_got / n_want - 1.0) < 1e-4, (n_got, n_want)

    def test_high_tier_actually_engages(self):
        """The 3-pass path must produce DIFFERENT bits than HIGHEST:
        a silent clamp back to 6-pass would make the knob a no-op (the
        pre-r3 kernel did exactly that)."""
        got = self._run("high")
        want = self._run("highest")
        assert float(np.max(np.abs(got - want))) > 0.0


def test_explain_reports_schedule_without_compiling():
    """Circuit.explain: the fused schedule as text — segments, stage
    mixes, pass/kernel totals — with no jit/compile side effects."""
    rng = np.random.default_rng(42)
    c = Circuit(16)
    for i in range(16):
        c.rx(1 + i % 15, float(rng.uniform(0, 2 * np.pi)))
    text = c.explain()
    assert "kernel segment" in text and "mat:b0" in text
    assert "1 segments, 1 distinct kernels" in text
    assert not c._compiled            # planning only, nothing compiled
    # the CPU-fallback sweep plan rides along when the native host
    # library is available (review r5: plan_summary was test-only)
    from quest_tpu import host as H
    if H._load() is not None:
        assert "cpu fallback host engine:" in text

    # the scheduler composes QFT-12's cross-band phases into ONE
    # segment (was >= 2 pre-scheduler); its stats line rides along
    qft_text = qft_circuit(12).explain()
    assert qft_text.count("kernel segment") >= 1
    assert "scheduler: on" in qft_text and "multiphase" in qft_text

    small = Circuit(6)
    small.h(0)
    assert "banded XLA engine" in small.explain()

    dyn = Circuit(12)
    dyn.h(0)
    dyn.measure(0)
    with pytest.raises(Exception):
        dyn.explain()


def test_explain_estimate_brackets_measurements():
    """The steady-state estimate line exists and its range is anchored
    to the measured cost model: the 30q bench application's range must
    bracket the round-5 on-chip measurement (79.9 ms), scaled by state
    size."""
    import re

    rng = np.random.default_rng(42)
    c = Circuit(30)
    for i in range(16):
        c.rx(1 + i % 29, float(rng.uniform(0, 2 * np.pi)))
    text = c.explain()
    m = re.search(r"estimated steady state on one v5e: "
                  r"([0-9.]+)-([0-9.]+) ms", text)
    assert m, text
    lo, hi = float(m.group(1)), float(m.group(2))
    assert lo <= 79.9 <= hi * 1.1, (lo, hi)
    # the estimate scales with state size: 2x amps -> ~2x time
    c29 = Circuit(29)
    for i in range(16):
        c29.rx(1 + i % 28, float(rng.uniform(0, 2 * np.pi)))
    m29 = re.search(r"([0-9.]+)-([0-9.]+) ms", c29.explain())
    assert abs(float(m29.group(1)) * 2 - lo) < 0.2 * lo


def test_cost_model_table_is_chip_keyed():
    """VERDICT r4 item 7: the estimate's constants are per-generation
    with named provenance — v5e measured, v5p projected (datasheet x
    measured derate), unknown chips fall back to v5e WITH matched=False
    so explain() cautions instead of silently mis-scaling."""
    from quest_tpu.circuit import _COST_MODELS, _cost_model_for, _estimate_ms
    from quest_tpu.ops import fusion as F
    from quest_tpu.ops import pallas_band as PB

    v5e, ok_e = _cost_model_for("TPU v5e lite")
    v5p, ok_p = _cost_model_for("TPU v5p")
    unk, ok_u = _cost_model_for("TPU v7x")
    assert ok_e and ok_p and not ok_u
    assert v5e is _COST_MODELS["v5e"] and unk is _COST_MODELS["v5e"]
    assert "MEASURED" in v5e["provenance"]
    assert "PROJECTED" in v5p["provenance"]
    # a faster chip projects faster on the same plan
    rng = np.random.default_rng(1)
    c = Circuit(30)
    for i in range(16):
        c.rx(1 + i % 29, float(rng.uniform(0, 2 * np.pi)))
    parts = PB.segment_plan(
        __import__("quest_tpu.ops.fusion", fromlist=["plan"]).plan(
            c._flat_ops(30, False), 30, bands=PB.plan_bands(30)), 30)
    lo_e, hi_e = _estimate_ms(parts, 30, v5e)
    lo_p, hi_p = _estimate_ms(parts, 30, v5p)
    assert lo_p < lo_e and hi_p < hi_e


def test_stage_report_runs_and_audits():
    """profiling.stage_report (the shipped form of the KERNELS.md
    probes) runs end-to-end on the attached backend: one record per
    stage family with measured + model figures."""
    import io
    from quest_tpu import profiling

    buf = io.StringIO()
    rec = profiling.stage_report(n=12, reps=1, out=buf)
    txt = buf.getvalue()
    assert "phase (DMA floor)" in rec and "b0" in rec and "b1" in rec
    for r in rec.values():
        assert r["measured_ms"] >= 0 and r["model_hi_ms"] >= r["model_lo_ms"]
    assert "DMA floor" in txt
    # CPU host: the caution must be loud
    import jax as _jax
    if _jax.devices()[0].platform != "tpu":
        assert "INTERPRETER" in txt


def test_scan_partition_groups_identical_structure_runs():
    """QUEST_FUSED_SCAN's grouping logic (circuit._scan_partition),
    previously untestable inline code with zero CI coverage (VERDICT r4
    weak item 4): runs >= scan_min of identical-structure segments
    group; shorter runs and XLA passthroughs stay singletons."""
    from quest_tpu.circuit import _scan_partition

    sA = ("stageA",)
    sB = ("stageB",)
    parts = [("segment", sA, [1]), ("segment", sA, [2]),
             ("segment", sA, [3]), ("sharded-ish", None),
             ("segment", sB, [4]), ("segment", sB, [5]),
             ("segment", sA, [6])]
    out = _scan_partition(parts, scan_min=3)
    assert out[0] == ("scan", sA, [[1], [2], [3]])
    assert out[1] == ("one", parts[3])
    # the two-long B run is below scan_min
    assert out[2] == ("one", parts[4]) and out[3] == ("one", parts[5])
    assert out[4] == ("one", parts[6])
    # disabled grouping passes everything through
    assert all(g[0] == "one" for g in _scan_partition(parts, 0))


def test_scan_applier_matches_sequential_with_stub_segment():
    """make_scan_applier's operand stacking + lax.scan semantics equal
    sequential application — verified with a STUB segment (plain jnp
    matmul apply), since the real kernel's scan execution is chip-only."""
    import jax
    import jax.numpy as jnp
    from quest_tpu.circuit import make_scan_applier

    rng = np.random.default_rng(0)
    mats = [rng.normal(size=(4, 4)).astype(np.float32) for _ in range(5)]
    vecs = [rng.normal(size=(4,)).astype(np.float32) for _ in range(5)]

    def stub_seg(amps, arrays):
        m, v = arrays
        return amps @ m.T + v

    apply = make_scan_applier(stub_seg, [[m, v] for m, v in
                                         zip(mats, vecs)])
    x0 = rng.normal(size=(3, 4)).astype(np.float32)
    got = np.asarray(jax.jit(apply)(jnp.asarray(x0)))
    want = x0
    for m, v in zip(mats, vecs):
        want = want @ m.T + v
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-5)


@pytest.mark.slow          # ~11 s — tier-1 budget discipline (runs in
                           # the full CI suite step)
def test_apply_matrix_rows_matches_flat():
    """apply_matrix on the (2, rows, 128) kernel layout must match the
    flat path across target/control placements. The shaped path exists
    because the flat round-trip at capacity costs a full-state layout
    copy (the 8 GiB copy_bitcast that OOMed the 30q density bench)."""
    import jax.numpy as jnp
    from quest_tpu.ops import apply as A
    n = 12
    rng = np.random.default_rng(7)
    amps = rng.standard_normal((2, 1 << n)).astype(np.float32)
    amps3 = jnp.asarray(amps.reshape(2, -1, 128))
    cases = [
        ((0, 8, 9, 11), (), ()),           # low + high targets (laneblock)
        ((8, 10), (), ()),                 # all-row targets
        ((7, 11), (3,), (1,)),             # row targets, lane control
        ((9,), (8, 2), (0, 1)),            # row target, mixed controls
        ((1, 3), (9,), (1,)),              # lane targets, row control
        ((8, 9, 10, 11), (), ()),          # k=4 all-row
        ((0, 5, 8, 11), (2, 10), (1, 0)),  # mixed everything
        ((4, 7), (), ()),                  # straddling lane/row boundary
    ]
    for targets, controls, cstates in cases:
        k = len(targets)
        m = (rng.standard_normal((2, 1 << k, 1 << k)) * 0.5
             ).astype(np.float32)
        pair = (m[0], m[1])                # non-unitary on purpose
        want = A.apply_matrix(jnp.asarray(amps), n, pair, targets,
                              controls, cstates)
        got = A.apply_matrix_rows(amps3, n, pair, targets, controls,
                                  cstates)
        assert got.shape == amps3.shape, (targets, controls)
        np.testing.assert_allclose(
            np.asarray(got).reshape(2, -1), np.asarray(want),
            atol=2e-5, rtol=0, err_msg=f"{targets} {controls} {cstates}")


def test_apply_matrix_rows_traced_operand():
    """The shaped path must accept traced operands (dynamic gate
    parameters) on both the laneblock and row flip-form routes."""
    import jax
    import jax.numpy as jnp
    from quest_tpu.ops import apply as A
    n = 11
    rng = np.random.default_rng(3)
    amps = rng.standard_normal((2, 1 << n)).astype(np.float32)
    amps3 = jnp.asarray(amps.reshape(2, -1, 128))
    for targets in [(0, 9), (8, 10)]:
        m = (rng.standard_normal((2, 4, 4)) * 0.5).astype(np.float32)

        def f(a3, mm):
            return A.apply_matrix_rows(a3, n, (mm[0], mm[1]), targets)

        got = jax.jit(f)(amps3, jnp.asarray(m))
        want = A.apply_matrix(jnp.asarray(amps), n, (m[0], m[1]), targets)
        np.testing.assert_allclose(np.asarray(got).reshape(2, -1),
                                   np.asarray(want), atol=2e-5, rtol=0)


def test_matrix_passthrough_runs_shaped():
    """A scattered multi-target unitary no stage can host must fall
    through as a matrix passthrough AND still match the per-gate engine
    — through apply_matrix_rows, never a flat intermediate."""
    rng = np.random.default_rng(11)
    z = rng.normal(size=(8, 8)) + 1j * rng.normal(size=(8, 8))
    u, _ = np.linalg.qr(z)
    c = Circuit(N)
    c.h(0)
    c.gate(u, (0, 5, 9))
    c.ry(8, 0.3)
    parts = parts_of(c)
    assert any(p[0] != "segment" for p in parts)   # the passthrough
    check(c, tol=5e-5)


def test_density_channel_passthrough_at_bench_shape():
    """The bench's capacity scenario in miniature: a 2q Kraus map whose
    doubled-register superop hits 4 scattered targets (0, nd-1, nd,
    2nd-1) — the exact op that was OOMing nd=15 on chip — must ride the
    shaped passthrough and match the per-gate engine on a density
    register."""
    from quest_tpu.ops import matrices as M
    nd = 8
    rng = np.random.default_rng(5)
    c = Circuit(nd)
    for q in range(nd):
        c.rx(q, float(rng.uniform(0, 2 * np.pi)))
    p = 0.15
    paulis = [np.eye(2), M.PAULI_X, M.PAULI_Y, M.PAULI_Z]
    ops2 = []
    for i, a in enumerate(paulis):
        for j, b in enumerate(paulis):
            w = np.sqrt(1 - 15 * p / 16) if i == j == 0 else np.sqrt(p / 16)
            ops2.append(w * np.kron(b, a))
    c.kraus((0, nd - 1), ops2)
    items = F.plan(c._flat_ops(2 * nd, True), 2 * nd,
                   bands=PB.plan_bands(2 * nd))
    parts = PB.segment_plan(items, 2 * nd)
    kinds = [getattr(p[1].op, "kind", "?") for p in parts
             if p[0] != "segment"]
    assert "matrix" in kinds                      # the 4-target superop
    check(c, n=2 * nd, density=True, tol=5e-5)


def test_laneblock_chunked_sweep_matches():
    """The capacity-mode chunked sweep (fori_loop over a free segment
    axis, in-place chunk updates) must agree exactly with the
    whole-plane sweep and the flat engine — including high controls and
    zero-coefficient skipping."""
    import jax.numpy as jnp
    from quest_tpu.ops import apply as A
    n = 13
    rng = np.random.default_rng(21)
    amps = rng.standard_normal((2, 1 << n)).astype(np.float32)
    st2 = jnp.asarray(amps.reshape(2, -1, 128))
    cases = [
        ((0, 12), (), ()),              # free interior axis q7..q11
        ((2, 8, 12), (), ()),
        ((1, 12), (9,), (0,)),          # high control rides the mask
    ]
    for targets, controls, cstates in cases:
        k = len(targets)
        m = (rng.standard_normal((2, 1 << k, 1 << k)) * 0.5
             ).astype(np.float32)
        pair = (m[0], m[1])
        whole = A._laneblock_core(st2, n, pair, targets,
                                  controls, cstates, chunks=1)
        chunked = A._laneblock_core(st2, n, pair,
                                    targets, controls, cstates, chunks=4)
        np.testing.assert_allclose(np.asarray(chunked),
                                   np.asarray(whole), atol=1e-6, rtol=0,
                                   err_msg=f"{targets} {controls}")
        want = A.apply_matrix(jnp.asarray(amps), n, pair, targets,
                              controls, cstates)
        np.testing.assert_allclose(
            np.asarray(chunked).reshape(2, -1), np.asarray(want),
            atol=2e-5, rtol=0)


# -- MultiPhaseStage: trig-free unit factors up to the crossover, the
#    per-element angle sum with one cos/sin above it --------------------------

_MP_N = 13
_MP_KERNELS = {}     # forms -> jitted kernel: operands are data


def _mp_qubits(rng, masks):
    lane = rng.choice(PB.LANE_QUBITS, size=rng.integers(1, 3),
                      replace=False)
    row = rng.choice(np.arange(PB.LANE_QUBITS, _MP_N),
                     size=rng.integers(1, 3), replace=False)
    return {"lane": list(lane), "row": list(row),
            "mixed": [lane[0], row[0]]}[masks]


def _mp_want(vec, forms, rows):
    """The group's phase per basis index, from the operand rows alone."""
    n = vec.size.bit_length() - 1
    idx = np.arange(1 << n)
    phase = np.zeros(1 << n)
    for form, (ang, lm, rlo, rhi) in zip(forms, rows):
        mask = int(lm) | ((int(rlo) | (int(rhi) << 15)) << PB.LANE_QUBITS)
        if form == "a":
            phase += np.where(idx & mask == mask, ang, 0.0)
        else:
            par = np.zeros_like(idx)
            for b in range(n):
                par ^= ((idx & mask) >> b) & 1
            phase += ang * (1 - 2 * par)
    return vec * np.exp(1j * phase)


@pytest.mark.parametrize("m", [1, 2, PB.MULTIPHASE_TRIGFREE_MAX,
                               PB.MULTIPHASE_TRIGFREE_MAX + 1])
@pytest.mark.parametrize("angle", ["pi", "pi/4", "random"])
@pytest.mark.parametrize("masks", ["lane", "row", "mixed"])
@pytest.mark.parametrize("form", ["a", "p"])
def test_multiphase_stage_matches_oracle(form, masks, angle, m):
    """One MultiPhaseStage segment on a random 13-qubit state (4 blocks,
    so row bits come from the grid too) against the phases its operand
    rows define; both sides of the crossover."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng([m, len(masks), len(angle), ord(form)])
    forms = (form,) * m
    st = PB.MultiPhaseStage(forms)
    assert PB.multiphase_trigfree(st) == (m <= PB.MULTIPHASE_TRIGFREE_MAX)
    rows = []
    for _ in range(m):
        ang = {"pi": np.pi, "pi/4": np.pi / 4,
               "random": rng.uniform(-np.pi, np.pi)}[angle]
        qs = _mp_qubits(rng, masks)
        lm = sum(1 << int(q) for q in qs if q < PB.LANE_QUBITS)
        rm = sum(1 << (int(q) - PB.LANE_QUBITS) for q in qs
                 if q >= PB.LANE_QUBITS)
        rows.append([ang, lm, rm & 0x7FFF, rm >> 15, 0, 0, 0, 0])
    operand = np.array(rows, dtype=np.float32)
    if forms not in _MP_KERNELS:
        _MP_KERNELS[forms] = jax.jit(PB.compile_segment(
            [st], _MP_N, rows_eff_bits=4, interpret=True))
    vec = oracle.random_statevector(_MP_N, rng).astype(np.complex64)
    amps = np.stack([vec.real, vec.imag]).reshape(2, -1, PB.LANES)
    out = np.asarray(_MP_KERNELS[forms](jnp.asarray(amps), [operand]))
    got = (out[0] + 1j * out[1]).reshape(-1)
    want = _mp_want(vec.astype(np.complex128), forms,
                    operand[:, :4].astype(np.float64))
    scale = float(np.max(np.abs(want)))
    np.testing.assert_allclose(got, want, atol=1e-5 * scale, rtol=0)


def test_multiphase_counters_name_each_path():
    """compile_segment_cached counts each phase group by path on a hit
    and a miss alike; an RCS plan's CZ pairs all take the trig-free
    path."""
    from quest_tpu import profiling
    c = random_circuit(16, 20, seed=7, entangler="cz")
    with profiling.recording() as rec:
        c.compiled_fused(16, False, donate=False, interpret=True)
    assert rec.counts.get("quest.multiphase_trigfree", 0) == 3
    assert "quest.multiphase_trig" not in rec.counts
    x = PB.MULTIPHASE_TRIGFREE_MAX
    cache = {}
    with profiling.recording() as rec:
        for st in (PB.MultiPhaseStage(("a",) * x),
                   PB.MultiPhaseStage(("a",) * (x + 1)),
                   PB.MultiPhaseStage(("a",) * (x + 1)),
                   PB.LookupPhaseStage(("a",) * (x + 1), 2)):
            PB.compile_segment_cached(cache, (st,), 16, interpret=True)
    assert len(cache) == 3
    assert rec.counts == {"quest.multiphase_trigfree": 1,
                          "quest.multiphase_trig": 2,
                          "quest.multiphase_lookup": 1}


# -- LookupPhaseStage: a wide group's phases from a per-step table where
#    its terms read few row bits that vary inside the block ---------------

# (stages ahead of the group, block row bits, grid row bits) at 14 qubits
# in 2^5-row blocks, so that a table of 2^4 rows is still smaller than
# its block: alone, the block holds row bits 0-4; behind a butterfly on
# row bit 6, it holds 0-3 and the scattered axis 6
_LK_N = 14
_LK_ROWS = 5
_LK_GEOMETRIES = {
    "inner": ([], [0, 1, 2, 3, 4], [5, 6]),
    "scattered": ([PB.MatStage("sc", 2, True, (), (), 6)], [0, 1, 2, 3, 6],
                  [4, 5]),
}
_LK_KERNELS = {}     # stage tuple -> jitted kernel: operands are data


def _lk_segment(rng, form, masks, k, geometry, m):
    """One segment [the geometry's stages, a MultiPhaseStage of m terms]
    whose terms read `masks` bits: lane bits, k of the block's row bits
    ('local'), grid row bits ('const'), or all three ('mixed')."""
    pre, local, const = _LK_GEOMETRIES[geometry]
    use = [int(b) for b in rng.choice(local, size=k, replace=False)]
    rows = []
    for r in range(m):
        lane, row = [], []
        if masks in ("lane", "mixed"):
            lane = list(rng.choice(PB.LANE_QUBITS, size=rng.integers(1, 3),
                                   replace=False))
        if masks in ("local", "mixed") and k:
            row += [use[r % k]] + [b for b in use if rng.random() < 0.3]
        if masks in ("const", "mixed"):
            row += list(rng.choice(const, size=rng.integers(1, 3),
                                   replace=False))
        rm = sum(1 << int(b) for b in set(row))
        rows.append([rng.uniform(-np.pi, np.pi), sum(1 << int(q) for q in lane),
                     rm & 0x7FFF, rm >> 15, 0, 0, 0, 0])
    ident = np.stack([np.eye(2), np.zeros((2, 2))]).astype(np.float32)
    return ("segment", pre + [PB.MultiPhaseStage((form,) * m)],
            [ident] * len(pre) + [np.array(rows, dtype=np.float32)])


@pytest.mark.parametrize("geometry", sorted(_LK_GEOMETRIES))
@pytest.mark.parametrize("masks,k", [("lane", 0), ("const", 0),
                                     ("local", 1), ("local", 2),
                                     ("local", 3), ("local", 4),
                                     ("mixed", 0), ("mixed", 1),
                                     ("mixed", 2), ("mixed", 3),
                                     ("mixed", 4)])
@pytest.mark.parametrize("form", ["a", "p"])
def test_lookup_stage_matches_oracle(form, masks, k, geometry):
    """A group above the trig-free constant whose terms read k of the
    block's row bits becomes a LookupPhaseStage (lookup_plan) and applies
    the phases its original operand rows define, on a random 14-qubit
    state in 4 blocks (the grid supplies the other row bits)."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng([k, len(masks), len(geometry), ord(form)])
    m = PB.MULTIPHASE_TRIGFREE_MAX + 2
    part = _lk_segment(rng, form, masks, k, geometry, m)
    operand = part[2][-1]
    (_, stages, arrays), = PB.lookup_plan([part], _LK_N,
                                          rows_eff_bits=_LK_ROWS)
    assert stages[:-1] == part[1][:-1]
    assert stages[-1] == PB.LookupPhaseStage((form,) * m, k)
    key = tuple(stages)
    if key not in _LK_KERNELS:
        _LK_KERNELS[key] = jax.jit(PB.compile_segment(
            stages, _LK_N, rows_eff_bits=_LK_ROWS, interpret=True))
    vec = oracle.random_statevector(_LK_N, rng).astype(np.complex64)
    amps = np.stack([vec.real, vec.imag]).reshape(2, -1, PB.LANES)
    out = np.asarray(_LK_KERNELS[key](jnp.asarray(amps), arrays))
    got = (out[0] + 1j * out[1]).reshape(-1)
    want = _mp_want(vec.astype(np.complex128), (form,) * m,
                    operand[:, :4].astype(np.float64))
    scale = float(np.max(np.abs(want)))
    np.testing.assert_allclose(got, want, atol=1e-5 * scale, rtol=0)


def test_lookup_plan_keeps_other_groups():
    """lookup_plan leaves a group on its path where the table would not
    pay: more block row bits than MULTIPHASE_LOOKUP_MAX_BITS (trig), a
    table as tall as the block (trig: its cos/sin and sums cost what the
    trig path's do), or few enough terms for the trig-free tree; and a
    part list without a wide group passes through as it was."""
    rng = np.random.default_rng(3)

    def reading(k, rows_eff):
        # k + 1 terms, each reading one of the block's row bits 0..k-1
        part = ("segment", [PB.MultiPhaseStage(("a",) * (k + 1))],
                [np.array([[0.3, 1, 1 << (r % k), 0, 0, 0, 0, 0]
                           for r in range(k + 1)], dtype=np.float32)])
        return part, _MP_N, rows_eff

    # a block of all 6 row bits: each term reads one of k = max + 1
    wide = reading(PB.MULTIPHASE_LOOKUP_MAX_BITS + 1,
                   _MP_N - PB.LANE_QUBITS)
    # k = max, but the block holds just those k row bits
    whole = reading(PB.MULTIPHASE_LOOKUP_MAX_BITS,
                    PB.MULTIPHASE_LOOKUP_MAX_BITS)
    narrow = (_lk_segment(rng, "a", "local", 1, "inner",
                          PB.MULTIPHASE_TRIGFREE_MAX), _LK_N, _LK_ROWS)
    for part, n, rows_eff in (wide, whole, narrow):
        (got,) = PB.lookup_plan([part], n, rows_eff_bits=rows_eff)
        assert got[1] == part[1]
        assert all(a is b for a, b in zip(got[2], part[2]))
    parts = parts_of(random_circuit(N, depth=4, seed=2))
    assert PB.lookup_plan(parts, N) == parts


def test_lookup_plan_keeps_trig_past_the_vmem_limit(monkeypatch):
    """A lookup's select tree can price above the trig stage it replaces
    (2^k + 2 working planes against 5): where that would take the launch
    past the scoped limit, lookup_plan keeps the group's trig form."""
    rng = np.random.default_rng(4)
    part = _lk_segment(rng, "a", "local", PB.MULTIPHASE_LOOKUP_MAX_BITS,
                       "inner", PB.MULTIPHASE_TRIGFREE_MAX + 2)
    geo = PB.segment_geometry(part[1], _LK_N, _LK_ROWS)
    (looked,) = PB.lookup_plan([part], _LK_N, rows_eff_bits=_LK_ROWS)
    price = PB._vmem_price(looked[1], geo)["total_bytes"]
    assert price > PB._vmem_price(part[1], geo)["total_bytes"]
    monkeypatch.setattr(PB, "VMEM_LIMIT_BYTES", price - 1)
    (kept,) = PB.lookup_plan([part], _LK_N, rows_eff_bits=_LK_ROWS)
    assert kept[1] == part[1] and kept[2][0] is part[2][0]


@pytest.mark.parametrize("n", [10, 11, 12, 14, 15])
def test_qft_matches_inverse_fft(n):
    """compiled_fused's QFT is numpy's ifft with norm='ortho'
    (amplitude j -> sum_k e^{2 pi i jk/N} / sqrt(N)). At 10 and 11
    qubits one block holds every row bit and the wide groups read all of
    them, so a table would be as tall as the block: they stay on trig, as
    at 12, where they read 5; at 14 (one 2^7-row block) the two that read
    3 and 2 take the lookup, and at 15 three, beside a scattered axis."""
    import jax.numpy as jnp
    from quest_tpu import profiling
    rng = np.random.default_rng(n)
    vec = oracle.random_statevector(n, rng)
    with profiling.recording() as rec:
        fn = qft_circuit(n).compiled_fused(n, False, donate=False,
                                           interpret=True)
    amps = np.stack([vec.real, vec.imag]).astype(np.float32)
    out = np.asarray(fn(jnp.asarray(amps.reshape(2, -1, PB.LANES))))
    got = out.reshape(2, -1)
    np.testing.assert_allclose(got[0] + 1j * got[1],
                               np.fft.ifft(vec, norm="ortho"), atol=5e-6,
                               rtol=0)
    lookups = rec.counts.get("quest.multiphase_lookup", 0)
    trig = rec.counts.get("quest.multiphase_trig", 0)
    assert (lookups, trig) == {10: (0, 2), 11: (0, 3), 12: (0, 4),
                               14: (2, 5), 15: (3, 5)}[n]


def test_qft_cut_by_vmem_budget_matches_oracle():
    """QFT whose phase groups the VMEM price cuts into several sweeps at
    a budget passed to the planner (a chip's limit scaled down to a
    10-qubit block) still matches the dense oracle."""
    import jax.numpy as jnp

    c = qft_circuit(N)
    items = F.plan(c._planned_flat(N, False), N, bands=PB.plan_bands(N))
    whole = PB.sweep_plan(PB.segment_plan(items, N), N)
    assert len(whole) == 1
    budget = PB.sweep_vmem_bytes(whole[0][1], whole[0][2], N)[
        "total_bytes"] // 2
    parts = PB.sweep_plan(PB.segment_plan(items, N, vmem_budget=budget), N,
                          vmem_budget=budget)
    assert len(parts) > 1
    for p in parts:
        assert PB.vmem_fits(p[1], N, budget)
    assert [st for p in parts for st in p[1]] == whole[0][1]
    assert any(isinstance(st, PB.MultiPhaseStage)
               and not PB.multiphase_trigfree(st)
               for p in parts for st in p[1])

    rng = np.random.default_rng(5)
    vec = oracle.random_statevector(N, rng)
    out = jnp.stack([vec.real, vec.imag]).astype(jnp.float32).reshape(
        2, -1, PB.LANES)
    for _, stages, arrays in parts:
        out = PB.compile_segment(stages, N, interpret=True)(out, arrays)
    got = np.asarray(out).reshape(2, -1)
    want = vec
    for op in c.ops:
        if op.kind == "matrix":
            mat = np.asarray(op.operand, dtype=np.complex128)
        else:
            assert op.kind == "allones", op.kind
            mat = np.diag([1.0] * ((1 << len(op.targets)) - 1)
                          + [complex(op.operand)])
        want = oracle.apply_to_vector(want, N, mat, op.targets, op.controls,
                                      op.cstates)
    np.testing.assert_allclose(got[0] + 1j * got[1], want, atol=5e-5,
                               rtol=0)
