"""Communication planner (quest_tpu/parallel/comm.py, docs/DISTRIBUTED.md).

Correctness: rewritten schedules (coalesced resharding, sliced
exchanges) produce the single-device amplitudes through every sharded
engine on 2- and 8-device CPU meshes, with QUEST_COMM_PLAN on and off.
Accounting: the CPU-side predicted comm_stats equal XLA's lowered
StableHLO collective accounting (parse_collectives) — the
plan->predict->assert contract that makes ICI a trustworthy metric.
Goldens mirror scripts/check_comm_golden.py: the per-gate engine's
planned bytes stay >=2x below the lazy-relabel plan on the deep-global
testbed, and the banded engine never selects a plan costlier than its
layer-amortized relabel incumbent (the lazy-regression class, fixed by
construction).
"""

import numpy as np
import pytest

import quest_tpu as qt
from bench import _build_deep_global_circuit
from quest_tpu.circuit import Circuit, flatten_ops, random_circuit
from quest_tpu.ops import fusion as F
from quest_tpu.parallel import comm as C
from quest_tpu.parallel import make_amp_mesh, shard_qureg
from quest_tpu.parallel import relabel as R
from quest_tpu.parallel import sharded as S
from quest_tpu.parallel.introspect import (parse_collectives,
                                           sharded_schedule)
from quest_tpu.state import to_dense
from .helpers import max_mesh_devices

N = 6
DEPTH = 6
DTYPE = np.complex128


@pytest.fixture(scope="module")
def mesh():
    return make_amp_mesh(max_mesh_devices())


@pytest.fixture(scope="module")
def mesh2():
    return make_amp_mesh(2)


def _single_device(circ, density=False, dtype=DTYPE):
    make = qt.create_density_qureg if density else qt.create_qureg
    q = qt.init_debug_state(make(circ.num_qubits, dtype=dtype))
    return to_dense(circ.apply(q))


def _through_engine(circ, mesh, engine, density=False, dtype=DTYPE):
    make = qt.create_density_qureg if density else qt.create_qureg
    q = qt.init_debug_state(make(circ.num_qubits, dtype=dtype))
    sq = shard_qureg(q, mesh)
    n = q.num_state_qubits
    if engine == "pergate":
        fn = S.compile_circuit_sharded(circ.ops, n, density, mesh,
                                       donate=False)
    elif engine == "banded":
        fn = S.compile_circuit_sharded_banded(circ.ops, n, density, mesh,
                                              donate=False)
    else:
        fn = S.compile_circuit_sharded_fused(circ.ops, n, density, mesh,
                                             donate=False, interpret=True)
    return to_dense(sq.replace_amps(fn(sq.amps)))


# -- coalescer invariants ----------------------------------------------------

def test_coalesce_restores_standard_order_and_event_shape():
    n, local_n = 8, 5
    g = n - local_n
    flat = flatten_ops(_build_deep_global_circuit(n, 4).ops, n, False)
    out = C.coalesce(flat, n, local_n)
    events = [op for op in out if op.kind == "relabel"]
    assert events, "deep-global circuit fired no relabel events"
    for ev in events:
        slots = ev.operand
        assert len(slots) == g and len(set(slots)) == g
        assert all(0 <= s < local_n for s in slots)
    # replaying the rewrite's own permutation bookkeeping must end at
    # identity (the restore contract)
    tr = R._PermTracker(n, local_n, [])
    for op in out:
        if op.kind == "relabel":
            tr.emit_relabel(op.operand)
        elif (op.kind == "matrix" and len(op.targets) == 2
              and isinstance(op.operand, np.ndarray)
              and np.array_equal(op.operand, R.SWAP)):
            tr.emit_swap(*op.targets)
    # non-swap ops carry PHYSICAL positions; only swaps/relabels move
    # the permutation, which must return home
    assert tr.perm == list(range(n))

    # a local-only circuit comes back untouched
    local = Circuit(n)
    for q in range(local_n):
        local.rx(q, 0.1 * (q + 1))
    flat2 = flatten_ops(local.ops, n, False)
    assert C.coalesce(flat2, n, local_n) == list(flat2)
    # chunks smaller than the device-bit count keep the plain schedule
    assert C.coalesce(flat, n, g - 1) == list(flat)


def test_coalesce_rejects_dynamic_ops():
    c = Circuit(3).h(0)
    c.measure(0)
    flat = flatten_ops(c.ops, 3, False)
    with pytest.raises(ValueError, match="static circuits only"):
        C.coalesce(flat, 3, 2)


def test_choose_plan_banded_never_above_incumbent():
    """Satellite-1 regression pin: for ANY circuit the banded engine's
    auto choice prices <= the layer-amortized relabel incumbent AND <=
    plain — the 1152 -> 1856 lazy-regression class cannot recur by
    construction (strictly-better-or-incumbent selection)."""
    for seed in range(6):
        c = random_circuit(N, depth=4, seed=seed)
        flat = list(F.maybe_schedule(flatten_ops(c.ops, N, False), N))
        local_n = N - 3
        bands = S._shard_bands(N, local_n)
        chosen, info = C.choose_plan(flat, N, local_n, engine="banded",
                                     bands=bands)
        cand = info["candidates"]
        assert cand[info["strategy"]]["elem_bytes"] \
            <= cand.get("relabel", cand["plain"])["elem_bytes"]
        assert cand[info["strategy"]]["elem_bytes"] \
            <= cand["plain"]["elem_bytes"]


# -- equivalence: every engine, knob on/off, both meshes ---------------------

@pytest.mark.parametrize("engine", ["pergate", "banded", "fused"])
def test_randomized_equivalence_knob_on(mesh, engine):
    # one seed for the fused engine: its interpret-mode kernel compiles
    # dominate this file's budget, and fused parity/equivalence is also
    # covered by the lowering-only parity test below plus the existing
    # sweep/relabel fused suites
    for seed in ((3, 11) if engine != "fused" else (3,)):
        c = random_circuit(N, depth=5, seed=seed)
        want = _single_device(c)
        got = _through_engine(c, mesh, engine)
        atol = 1e-12 if engine != "fused" else 2e-4
        np.testing.assert_allclose(got, want, atol=atol, rtol=0)


@pytest.mark.parametrize("engine", ["pergate", "banded"])
def test_deep_global_equivalence_knob_on_off(mesh, engine, monkeypatch):
    c = _build_deep_global_circuit(N, 3)
    want = _single_device(c)
    got_on = _through_engine(c, mesh, engine)
    np.testing.assert_allclose(got_on, want, atol=1e-12, rtol=0)
    monkeypatch.setenv("QUEST_COMM_PLAN", "0")
    got_off = _through_engine(c, mesh, engine)
    np.testing.assert_allclose(got_off, want, atol=1e-12, rtol=0)


def test_equivalence_two_device_mesh(mesh2):
    c = _build_deep_global_circuit(5, 3)
    want = _single_device(c)
    for engine in ("pergate", "banded"):
        got = _through_engine(c, mesh2, engine)
        np.testing.assert_allclose(got, want, atol=1e-12, rtol=0)


def test_density_channels_equivalence(mesh):
    c = Circuit(3).h(2).damping(2, 0.2).cnot(0, 2).depolarising(1, 0.1)
    want = _single_device(c, density=True)
    for engine in ("pergate", "banded"):
        got = _through_engine(c, mesh, engine, density=True)
        np.testing.assert_allclose(got, want, atol=1e-12, rtol=0)


def test_f64_banded_equivalence(mesh):
    # complex128 through the banded engine IS the f64 pod path; the
    # fused engine falls back to the same banded schedule for f64
    c = random_circuit(N, depth=4, seed=9)
    want = _single_device(c, dtype=np.complex128)
    got = _through_engine(c, mesh, "banded", dtype=np.complex128)
    np.testing.assert_allclose(got, want, atol=1e-12, rtol=0)


# -- comm_stats == parse_collectives parity ----------------------------------

@pytest.mark.parametrize("engine", ["pergate", "banded", "fused"])
def test_comm_stats_matches_lowered_hlo(mesh, engine):
    # depth 3 (not the golden depth 6): parity is depth-independent and
    # lowering cost is the budget here; the depth-6 byte goldens live in
    # the slow-marked test below + scripts/check_comm_golden.py
    for circ in (_build_deep_global_circuit(N, 3),
                 random_circuit(10, depth=4, seed=3)):
        rec = sharded_schedule(circ.ops, circ.num_qubits, False, mesh,
                               engine=engine)
        assert rec["comm_matches_hlo"], rec
        assert rec["comm_exchanges"] == rec["collective_exchanges"]
        assert rec["comm_bytes"] == rec["ici_bytes_per_device"]


def test_comm_stats_parity_two_device_mesh(mesh2):
    rec = sharded_schedule(_build_deep_global_circuit(5, 3).ops, 5, False,
                           mesh2, engine="banded")
    assert rec["comm_matches_hlo"], rec


def test_comm_stats_parity_density_and_knob_off(mesh, monkeypatch):
    c = Circuit(3).h(2).damping(2, 0.2).cnot(0, 2)
    rec = sharded_schedule(c.ops, 6, True, mesh, engine="banded")
    assert rec["comm_matches_hlo"], rec
    monkeypatch.setenv("QUEST_COMM_PLAN", "0")
    for engine in ("pergate", "banded"):
        rec = sharded_schedule(_build_deep_global_circuit(N, 3).ops, N,
                               False, mesh, engine=engine)
        assert rec["comm_strategy"] in ("plain", "relabel")
        assert rec["comm_matches_hlo"], rec


def test_comm_stats_parity_dynamic(mesh):
    from quest_tpu.parallel.introspect import sharded_measured_schedule
    dc = Circuit(N)
    for q in range(N):
        dc.h(q)
    dc.cnot(0, N - 1)
    dc.measure(N - 1)
    dc.x_if(0, (0, 1))
    dc.measure(0)
    for engine in ("xla", "banded"):
        rec = sharded_measured_schedule(dc.ops, N, False, mesh,
                                        engine=engine)
        assert rec["comm_matches_hlo"], rec
        assert rec["comm_all_reduces"] == rec["all_reduces"] == 2


# -- exchange slicing --------------------------------------------------------

def test_exchange_slicing_structure_and_bit_identity(mesh, monkeypatch):
    """QUEST_EXCHANGE_SLICES=4 must multiply the collective-permute
    count by the slice factor at UNCHANGED total bytes (the overlap
    structure, verifiable on the CPU mesh), keep predicted == lowered,
    and reproduce the unsliced amplitudes (slicing only splits the
    transfer; the arithmetic per element is the same). Compared to
    4 eps of the largest amplitude, not bit for bit: XLA:CPU's LLVM
    backend contracts the combine into FMAs differently per fusion
    shape — 1 ulp on 3 of 128 f64 values under JAX 0.9, and bit-equal
    with --xla_backend_optimization_level=0."""
    monkeypatch.setenv("QUEST_COMM_PLAN", "0")   # fixed plain schedule
    c = Circuit(N).rx(N - 1, 0.4).swap(0, N - 1)
    n = N
    rec1 = sharded_schedule(c.ops, n, False, mesh, engine="pergate")
    monkeypatch.setenv("QUEST_EXCHANGE_SLICES", "4")
    rec4 = sharded_schedule(c.ops, n, False, mesh, engine="pergate")
    assert rec4["comm_matches_hlo"], rec4
    assert rec4["comm_bytes"] == rec1["comm_bytes"]
    assert rec4["comm_collective_permutes"] \
        > rec1["comm_collective_permutes"]

    q = qt.init_debug_state(qt.create_qureg(n, dtype=DTYPE))
    sq = shard_qureg(q, mesh)
    monkeypatch.delenv("QUEST_EXCHANGE_SLICES")
    f1 = S.compile_circuit_sharded(c.ops, n, False, mesh, donate=False)
    a = np.asarray(f1(sq.amps))
    monkeypatch.setenv("QUEST_EXCHANGE_SLICES", "4")
    f4 = S.compile_circuit_sharded(c.ops, n, False, mesh, donate=False)
    b = np.asarray(f4(sq.amps))
    np.testing.assert_allclose(b, a, rtol=0,
                               atol=4 * np.finfo(a.dtype).eps
                               * np.abs(a).max())


def test_effective_slices_clamps():
    assert C.effective_slices(8) == 1          # default knob = 1
    import os
    os.environ["QUEST_EXCHANGE_SLICES"] = "16"
    try:
        assert C.effective_slices(8) == 8      # clamped to the block
        assert C.effective_slices(64) == 16
    finally:
        del os.environ["QUEST_EXCHANGE_SLICES"]


# -- goldens (mirrored by scripts/check_comm_golden.py) ----------------------

@pytest.mark.slow
def test_deep_global_goldens(mesh):
    """The acceptance gate, HLO-verified on the 8-device mesh: per-gate
    planned-and-lowered bytes >=2x below the lazy-relabel plan; banded
    no worse than its pre-lazy baseline (plain) OR its relabel
    incumbent.

    slow-marked (tier-1 budget discipline, the PR-4/5 pattern): five
    depth-6 lowerings ~7 s, and the SAME gate runs in every CI pass
    anyway — scripts/check_comm_golden.py asserts these byte ceilings
    on the predictions, and the (tier-1) parity tests above pin those
    predictions EQUAL to the lowered StableHLO, so this direct
    HLO-level check is transitively covered between full-suite runs."""
    if int(mesh.devices.size) < 8:
        pytest.skip("goldens are pinned at the 8-device geometry")
    import jax
    import jax.numpy as jnp

    c = _build_deep_global_circuit(N, DEPTH)

    def lowered(build, **kw):
        step = build(c.ops, N, False, mesh, donate=False, **kw)
        low = jax.jit(step).lower(
            jax.ShapeDtypeStruct((2, 1 << N), jnp.float64))
        return parse_collectives(low.as_text(), num_devices=8)

    planned = lowered(S.compile_circuit_sharded)
    lazy = lowered(S.compile_circuit_sharded, lazy=True)
    assert 2 * planned["ici_bytes_per_device"] \
        <= lazy["ici_bytes_per_device"], (planned, lazy)

    banded = lowered(S.compile_circuit_sharded_banded)
    banded_plain = lowered(S.compile_circuit_sharded_banded, relabel=False)
    banded_rel = lowered(S.compile_circuit_sharded_banded, relabel=True)
    assert banded["ici_bytes_per_device"] \
        <= banded_plain["ici_bytes_per_device"], (banded, banded_plain)
    assert banded["ici_bytes_per_device"] \
        <= banded_rel["ici_bytes_per_device"], (banded, banded_rel)


# -- cache discipline --------------------------------------------------------

def test_zero_retrace_and_knob_flip(mesh, compile_auditor):
    c = random_circuit(N, depth=3, seed=4)
    amps = shard_qureg(qt.init_debug_state(
        qt.create_qureg(N, dtype=DTYPE)), mesh).amps
    fn = c.compiled_sharded_banded(N, False, mesh, donate=False)
    fn(amps)
    with compile_auditor:
        fn2 = c.compiled_sharded_banded(N, False, mesh, donate=False)
        fn2(amps)
    compile_auditor.assert_no_retrace("warmed sharded banded engine")
    assert fn is fn2

    # both knobs are keyed with flips: the registry audit covers them
    from quest_tpu.analysis.audit import audit_knob_flips
    report = audit_knob_flips(["QUEST_COMM_PLAN",
                               "QUEST_EXCHANGE_SLICES"])
    assert {r["knob"] for r in report} \
        == {"QUEST_COMM_PLAN", "QUEST_EXCHANGE_SLICES"}


# -- parse_collectives: loops and calls --------------------------------------

def test_parse_collectives_counts_through_while_and_calls(mesh):
    """One logical exchange lowered inside a lax.fori_loop body must
    count TRIP-COUNT times (XLA outlines the body into a private func
    called from a stablehlo.while) — the flat-regex undercount that
    would let the comm parity assertion pass vacuously."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from quest_tpu import compat
    from quest_tpu.env import AMP_AXIS

    D = int(mesh.devices.size)
    perm = [(i, i ^ 1) for i in range(D)]

    def body(chunk):
        def step(_, c):
            return c + lax.ppermute(c, AMP_AXIS, perm)
        return lax.fori_loop(0, 3, step, chunk)

    fn = jax.jit(compat.shard_map(body, mesh, P(None, AMP_AXIS),
                                  P(None, AMP_AXIS)))
    txt = fn.lower(
        jax.ShapeDtypeStruct((2, 8 * D), jnp.float32)).as_text()
    rec = parse_collectives(txt, num_devices=D)
    assert rec["collective_permutes"] == 3, rec
    assert rec["ici_bytes_per_device"] == 3 * 2 * 8 * 4, rec


def test_parse_collectives_call_multiplicity_fixture():
    """Handwritten module: a private func holding one collective-permute
    called TWICE from main counts twice; a while with derivable trip
    count multiplies; an unresolvable while conservatively counts
    once."""
    txt = """
module @fix {
  func.func public @main(%arg0: tensor<2x8xf32>) -> tensor<2x8xf32> {
    %0 = call @helper(%arg0) : (tensor<2x8xf32>) -> tensor<2x8xf32>
    %1 = call @helper(%0) : (tensor<2x8xf32>) -> tensor<2x8xf32>
    return %1 : tensor<2x8xf32>
  }
  func.func private @helper(%arg0: tensor<2x8xf32>) -> tensor<2x8xf32> {
    %0 = "stablehlo.collective_permute"(%arg0) <{channel_handle = #stablehlo.channel_handle<handle = 1, type = 1>, source_target_pairs = dense<[[0, 1], [1, 0]]> : tensor<2x2xi64>}> : (tensor<2x8xf32>) -> tensor<2x8xf32>
    return %0 : tensor<2x8xf32>
  }
}
"""
    rec = parse_collectives(txt)
    assert rec["collective_permutes"] == 2, rec
    assert rec["ici_bytes_per_device"] == 2 * 2 * 8 * 4, rec

    # unresolvable while (bound is an argument, not a constant): the op
    # inside the body counts once, never zero
    txt2 = """
module @fix2 {
  func.func public @main(%arg0: tensor<2x8xf32>, %arg1: tensor<i64>) -> tensor<2x8xf32> {
    %c = stablehlo.constant dense<0> : tensor<i64>
    %0:2 = stablehlo.while(%iterArg = %c, %iterArg_0 = %arg0) : tensor<i64>, tensor<2x8xf32>
     cond {
      %1 = stablehlo.compare  LT, %iterArg, %arg1,  SIGNED : (tensor<i64>, tensor<i64>) -> tensor<i1>
      stablehlo.return %1 : tensor<i1>
    } do {
      %1 = "stablehlo.collective_permute"(%iterArg_0) <{channel_handle = #stablehlo.channel_handle<handle = 1, type = 1>, source_target_pairs = dense<[[0, 1], [1, 0]]> : tensor<2x2xi64>}> : (tensor<2x8xf32>) -> tensor<2x8xf32>
      %c_1 = stablehlo.constant dense<1> : tensor<i64>
      %2 = stablehlo.add %iterArg, %c_1 : tensor<i64>
      stablehlo.return %2, %1 : tensor<i64>, tensor<2x8xf32>
    }
    return %0#1 : tensor<2x8xf32>
  }
}
"""
    rec2 = parse_collectives(txt2)
    assert rec2["collective_permutes"] == 1, rec2


# -- plan_stats / explain surfaces -------------------------------------------

def test_plan_stats_devices_record():
    c = _build_deep_global_circuit(N, DEPTH)
    rec = c.plan_stats(devices=8)["comm"]
    assert rec["comm_exchanges"] >= 1
    assert rec["comm_bytes"] > 0
    assert rec["comm_strategy"] in ("plain", "coalesce", "relabel",
                                    "lazy")
    assert rec["devices"] == 8
    with pytest.raises(ValueError, match="power of two"):
        c.plan_stats(devices=3)


def test_explain_sharded_comm_line(mesh):
    text = _build_deep_global_circuit(N, 3).explain_sharded(mesh)
    assert "comm plan:" in text
    assert "matches lowered StableHLO" in text, text
