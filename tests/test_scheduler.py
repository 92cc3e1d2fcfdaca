"""Commutation-aware gate scheduler (quest_tpu/ops/fusion.py schedule):
golden pass-count regressions + equivalence fuzz across every engine.

The scheduler's contract has two halves, tested separately:

  * PLANNING (pure host math, no compile): scheduled plans must show the
    promised pass-count reductions — the QFT-30 fused-engine schedule
    drops >= 2x in full-state HBM passes (the r5 QFT-vs-RCS gap's
    currency), and no benchmark workload regresses. Pass counts come
    from Circuit.plan_stats, the same statistics explain() prints, so
    the asserted metric IS the reported one.

  * SEMANTICS: a scheduled program must equal the unscheduled one.
    Every reorder is justified by the planner's structural commutation
    rule and every composition is a product of commuting diagonals, so
    scheduled engines are fuzzed against the UNSCHEDULED per-gate XLA
    oracle — statevector and density, on the banded, fused(interpret),
    host and sharded engines.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import quest_tpu as qt
from quest_tpu.circuit import (Circuit, flatten_ops, qft_circuit,
                               random_circuit)
from quest_tpu.ops import fusion as F
from quest_tpu.state import to_dense


def _stats(circ, sched: bool, density=False):
    os.environ["QUEST_SCHEDULE"] = "1" if sched else "0"
    try:
        return circ.plan_stats(density=density)
    finally:
        os.environ.pop("QUEST_SCHEDULE", None)


def ghz_circuit(n):
    c = Circuit(n)
    c.h(0)
    for q in range(n - 1):
        c.cnot(q, q + 1)
    return c


# ---------------------------------------------------------------------------
# golden pass-count regressions (CPU-only planning math)
# ---------------------------------------------------------------------------


def test_qft30_scheduled_halves_full_state_passes():
    """THE acceptance metric: the scheduled QFT-30 segment plan must show
    >= 2x fewer full-state passes than the unscheduled plan (the 435
    controlled phases compose into cross-layer groups instead of one
    stage each: 14 -> 6), counted before the VMEM price, which then cuts
    the scheduled plan's long chains of wide groups (-> 8) and none of
    the unscheduled plan's."""
    from quest_tpu.ops import pallas_band as PB

    c = qft_circuit(30)

    def passes(sched, budget):
        os.environ["QUEST_SCHEDULE"] = "1" if sched else "0"
        try:
            items = F.plan(c._planned_flat(30, False), 30,
                           bands=PB.plan_bands(30))
        finally:
            os.environ.pop("QUEST_SCHEDULE", None)
        return len(PB.segment_plan(items, 30, vmem_budget=budget))

    assert passes(True, np.inf) * 2 <= passes(False, np.inf)
    un = _stats(c, sched=False)["fused"]
    sc = _stats(c, sched=True)["fused"]
    assert un["full_state_passes"] == passes(False, np.inf)
    assert sc["full_state_passes"] < un["full_state_passes"], (sc, un)
    # and the reduction is composition doing the work, not accounting:
    # stage count collapses too
    assert sc["stages"] * 2 <= un["stages"]


def test_qft30_scheduler_stats_surface_the_fusions():
    st = _stats(qft_circuit(30), sched=True)["scheduler"]
    assert st["fused_groups"] > 0
    assert st["fused_ops"] > 300          # most of the 435 phases
    assert st["delayed"] > 0

    text_on = os.environ.get("QUEST_SCHEDULE")
    assert text_on is None                # _stats restored the env
    out = qft_circuit(30).explain()
    assert "scheduler: on" in out


def test_ghz_plan_unchanged_by_scheduler():
    """GHZ has nothing poolable (H + CNOT chain): the scheduler must be
    an exact no-op, not merely equivalence-preserving."""
    n = 24
    c = ghz_circuit(n)
    flat = flatten_ops(c.ops, n, False)
    sched, stats = F.schedule(flat, n)
    assert sched == list(flat)
    assert stats["fused_groups"] == 0 and stats["delayed"] == 0
    un = _stats(c, sched=False)["fused"]
    sc = _stats(c, sched=True)["fused"]
    assert sc == un


@pytest.mark.slow          # ~7 s 30q-class planning — tier-1 budget
                           # discipline; the sweep golden gate holds
                           # the plan ceilings CI-side
def test_rcs30_does_not_regress():
    """The headline workload: scheduling must not add passes (it
    currently removes a couple by composing the CZ brick)."""
    c = random_circuit(30, 20, seed=11)
    un = _stats(c, sched=False)["fused"]
    sc = _stats(c, sched=True)["fused"]
    assert sc["full_state_passes"] <= un["full_state_passes"]
    assert sc["stages"] <= un["stages"]


def test_chain_bench_variant_is_fusion_resistant():
    """bench.py's dependent-chain variant must stay one stage per gate
    UNDER THE SCHEDULER — that is its whole point (VERDICT r5 weak #7:
    the per-stage floor must be publicly bounded)."""
    import bench
    n = 24
    c = bench._build_chain_circuit(n)
    sc = _stats(c, sched=True)["fused"]
    assert sc["stages"] >= len(c.ops)
    assert sc["kernel_segments"] >= 1


def test_scheduler_knob_parses_loudly(monkeypatch):
    monkeypatch.setenv("QUEST_SCHEDULE", "yes")
    with pytest.raises(ValueError, match="QUEST_SCHEDULE"):
        F._schedule_enabled()


def test_scheduler_knob_in_engine_mode_key(monkeypatch):
    """Flipping QUEST_SCHEDULE mid-process must change the compiled
    program cache key (the stale-program class of ADVICE r4 item 2)."""
    from quest_tpu.circuit import _engine_mode_key
    k1 = _engine_mode_key()
    monkeypatch.setenv("QUEST_SCHEDULE", "0")
    assert _engine_mode_key() != k1


def test_composed_diag_survives_target_remapping():
    """ComposedDiag carries its parts target-RELATIVE, so the sharded
    relabel pass's dataclasses.replace on targets keeps them valid."""
    import dataclasses
    c = qft_circuit(12)
    flat = F.maybe_schedule(flatten_ops(c.ops, 12, False), 12)
    groups = [op for op in flat if isinstance(op, F.ComposedDiag)]
    assert groups, "QFT-12 must produce composed diagonals"
    g = groups[0]
    remapped = dataclasses.replace(
        g, targets=tuple(reversed(g.targets)))
    assert remapped.parts == g.parts      # indices, not absolute qubits


def test_wide_diagonal_never_seeds_an_open_group():
    """A forced diagonal WIDER than DIAG_FUSE_MAX (e.g. a many-control
    phase) must emit alone as a CLOSED group: before the fix it seeded a
    group with empty recorded support that later ops joined, composing a
    ComposedDiag past the cap (2^k select-chain blowup in the kernel)."""
    n = 12
    wide = np.exp(1j * np.linspace(0, 1, 1 << 9))   # 9-qubit diagonal
    small = np.exp(1j * np.array([0.0, 0.4, 0.8, 1.2]))
    c = Circuit(n)
    c._add("diagonal", tuple(range(1, 10)), wide)   # spans bands 0 and 1
    c._add("diagonal", (0, 8), small)
    c.h(0)
    c.h(8)
    sched, stats = F.schedule(flatten_ops(c.ops, n, False), n)
    for op in sched:
        if isinstance(op, F.ComposedDiag):
            assert len(op.targets) <= F.DIAG_FUSE_MAX, op.targets
    # the wide diagonal survives un-composed
    assert any(len(op.targets) == 9 and not isinstance(op, F.ComposedDiag)
               for op in sched if op.kind == "diagonal")


def test_duplicate_diag_ops_pool_by_identity():
    """Two structurally-identical diagonal ops with DISTINCT (but equal)
    ndarray operands: pool bookkeeping must use identity — GateOp
    equality compares operands elementwise and raises on ndarrays."""
    n = 10
    d = np.exp(1j * np.array([0.0, 0.1, 0.2, 0.3]))
    c = Circuit(n)
    c._add("diagonal", (0, 8), d.copy())
    c._add("diagonal", (0, 8), d.copy())
    c.h(0)
    sched, stats = F.schedule(flatten_ops(c.ops, n, False), n)
    assert stats["fused_groups"] == 1 and stats["fused_ops"] == 2
    got = np.sort_complex(np.asarray(
        [op for op in sched if isinstance(op, F.ComposedDiag)][0]
        .operand).reshape(-1))
    want = np.sort_complex((np.asarray(d) ** 2).reshape(-1))
    np.testing.assert_allclose(got, want, atol=1e-12)


# ---------------------------------------------------------------------------
# equivalence fuzz: scheduled engines vs the unscheduled XLA oracle
# ---------------------------------------------------------------------------


def _phase_heavy_circuit(n, depth, seed):
    """The scheduler's adversarial mix: interleaved Hadamards, rotations,
    controlled phases (cross- and in-band), parities, diagonals, CNOTs
    and swaps — everything the pool/compose path touches."""
    rng = np.random.default_rng(seed)
    c = Circuit(n)
    for _ in range(depth):
        kind = int(rng.integers(0, 8))
        q = int(rng.integers(0, n))
        q2 = int(rng.integers(0, n))
        a = float(rng.uniform(0, 2 * np.pi))
        if kind == 0:
            c.h(q)
        elif kind == 1:
            c.rx(q, a)
        elif kind == 2 and q2 != q:
            c.cphase(a, q, q2)
        elif kind == 3:
            qs = sorted(rng.choice(n, size=min(3, n), replace=False))
            c.multi_rotate_z(tuple(int(x) for x in qs), a)
        elif kind == 4:
            c.phase(q, a)
        elif kind == 5 and q2 != q:
            c.cnot(q, q2)
        elif kind == 6 and q2 != q:
            c.cz(q, q2)
        elif kind == 7 and q2 != q:
            c.swap(q, q2)
    return c


def _sv(circ, n, runner):
    amps = jnp.zeros((2, 1 << n), dtype=jnp.float32).at[0, 0].set(1.0)
    out = runner(circ, n, amps)
    return np.asarray(out[0]) + 1j * np.asarray(out[1])


def _oracle_state(builder, n):
    """UNSCHEDULED per-gate XLA engine — the semantic reference."""
    os.environ["QUEST_SCHEDULE"] = "0"
    try:
        c = builder()
        return _sv(c, n, lambda c_, n_, a: c_.compiled(
            n_, density=False, donate=False)(a))
    finally:
        os.environ.pop("QUEST_SCHEDULE", None)


@pytest.mark.parametrize("seed", [0, 1])
def test_fuzz_scheduled_banded_and_fused_match_oracle(seed):
    n = 10
    want = _oracle_state(lambda: _phase_heavy_circuit(n, 45, seed), n)
    c = _phase_heavy_circuit(n, 45, seed)
    got_b = _sv(c, n, lambda c_, n_, a: c_.compiled_banded(
        n_, density=False, donate=False)(a))
    np.testing.assert_allclose(got_b, want, atol=3e-5, rtol=0)

    from quest_tpu.state import fused_state_shape
    c2 = _phase_heavy_circuit(n, 45, seed)
    amps = jnp.zeros(fused_state_shape(n),
                     jnp.float32).at[0, 0, 0].set(1.0)
    out = c2.compiled_fused(n, density=False, donate=False,
                            interpret=True)(amps).reshape(2, -1)
    got_f = np.asarray(out[0]) + 1j * np.asarray(out[1])
    np.testing.assert_allclose(got_f, want, atol=3e-5, rtol=0)


def test_fuzz_scheduled_qft_every_single_chip_engine():
    n = 11
    want = _oracle_state(lambda: qft_circuit(n), n)
    c = qft_circuit(n)
    got = _sv(c, n, lambda c_, n_, a: c_.compiled_banded(
        n_, density=False, donate=False)(a))
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=0)


def test_fuzz_scheduled_host_engine_matches():
    """The native host engine consumes Circuit.ops directly (no
    scheduling), so it doubles as an independent oracle here."""
    from quest_tpu import host as H
    if not H.available():
        pytest.skip("native host library unavailable")
    n = 9
    c = _phase_heavy_circuit(n, 50, 7)
    want = _oracle_state(lambda: _phase_heavy_circuit(n, 50, 7), n)
    q = qt.create_qureg(n)
    q = qt.init_zero_state(q)
    got = to_dense(c.apply_host(q))
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=0)


def test_fuzz_scheduled_density_banded_matches():
    """Density register: duals are scheduled too (the flat list carries
    them explicitly); banded scheduled vs unscheduled XLA."""
    n = 4
    c = _phase_heavy_circuit(n, 30, 3)
    c.damping(1, 0.1)
    rho_w = qt.init_debug_state(qt.create_density_qureg(n))
    os.environ["QUEST_SCHEDULE"] = "0"
    try:
        want = to_dense(c.apply(rho_w))
    finally:
        os.environ.pop("QUEST_SCHEDULE", None)
    rho_g = qt.init_debug_state(qt.create_density_qureg(n))
    got = to_dense(c.apply_banded(rho_g))
    np.testing.assert_allclose(got, want, atol=3e-4, rtol=0)


def test_fuzz_scheduled_sharded_engines_match():
    """Scheduled sharded banded + fused(interpret) on a virtual mesh vs
    the unscheduled oracle — the relabel interaction path (engine_flat
    schedules BEFORE plan_full_relabels; its A/B guard judges the
    scheduled list)."""
    from quest_tpu.parallel import make_amp_mesh, shard_qureg
    from quest_tpu.parallel.sharded import (
        compile_circuit_sharded_banded, compile_circuit_sharded_fused)
    from quest_tpu.state import init_state_from_amps
    from .helpers import max_mesh_devices

    mesh = make_amp_mesh(max_mesh_devices())
    n = 8
    want = _oracle_state(lambda: _phase_heavy_circuit(n, 50, 5), n)
    for compiler, kw in ((compile_circuit_sharded_banded, {}),
                         (compile_circuit_sharded_fused,
                          {"interpret": True})):
        c = _phase_heavy_circuit(n, 50, 5)
        q = qt.init_zero_state(qt.create_qureg(n))
        step = compiler(c.ops, n, False, mesh, donate=False, **kw)
        sq = shard_qureg(q, mesh)
        got = to_dense(sq.replace_amps(step(sq.amps)))
        np.testing.assert_allclose(got, want, atol=3e-5, rtol=0)


def test_scheduled_dynamic_circuit_respects_measure_barrier():
    """Mid-circuit measurement is a scheduling barrier: phases must not
    cross the collapse. Same key => identical outcomes and states
    between scheduled-banded and unscheduled-xla dynamic engines."""
    n = 6
    key = jax.random.PRNGKey(42)

    def build():
        c = Circuit(n)
        c.h(0)
        c.cphase(0.7, 0, 5)
        c.h(5)
        c.measure(0)
        c.cphase(1.1, 0, 4)
        c.x_if(4, (0, 1))
        c.h(4)
        return c

    os.environ["QUEST_SCHEDULE"] = "0"
    try:
        q0 = qt.init_zero_state(qt.create_qureg(n))
        q0, out0 = build().apply_measured(q0, key, engine="xla")
    finally:
        os.environ.pop("QUEST_SCHEDULE", None)
    q1 = qt.init_zero_state(qt.create_qureg(n))
    q1, out1 = build().apply_measured(q1, key, engine="banded")
    np.testing.assert_array_equal(np.asarray(out0), np.asarray(out1))
    np.testing.assert_allclose(to_dense(q1), to_dense(q0), atol=3e-5,
                               rtol=0)
