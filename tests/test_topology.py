"""Topology-aware comm planning (docs/DISTRIBUTED.md §topology).

The hierarchical mesh model (comm.Topology) splits the planner's
pricing into ICI and DCI link classes: cost selection weights
DCI-crossing bytes, relabel victims place hot qubits on intra-host
device bits, and the cluster coalescer (comm.coalesce_clusters) defers
per qubit cluster so a DCI hop is paid once per gate chain instead of
once per layer. Pins, mirroring scripts/check_comm_golden.py:

  * the flat model (QUEST_COMM_TOPOLOGY=0, or unset on a single-host
    process) selects bit-for-bit the pre-topology plans — 6 events /
    672 B on the deep-global testbed;
  * under hosts=2 the hierarchical plan's predicted comm_dci_bytes
    sit >= 2x below the flat plan's DCI share, with EXACT event counts
    pinned (2 DCI-crossing events vs 6);
  * comm_stats' ici/dci split tiles the HLO-asserted total exactly and
    predicted == lowered StableHLO holds with the knob set (the
    hosts=2 planner parity leg; the true 2-process-per-host variant
    rides tests/test_gang.py);
  * amplitudes through the rewritten plans stay exact.
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
from quest_tpu.parallel.introspect import sharded_schedule
from quest_tpu.state import to_dense
from .helpers import max_mesh_devices

N, DEPTH, DEVICES, BPR = 6, 6, 8, 8
LOCAL_N = N - 3

# the committed topology goldens (scripts/check_comm_golden.py holds
# the CI mirror): flat = PR-8 exactly; hier = the cluster plan
FLAT_EXCHANGES, FLAT_BYTES = 6, 672
FLAT_DCI_BYTES = 384            # the 6 a2as' cross-host share, hosts=2
HIER_DCI_BYTES = 192
HIER_DCI_EXCHANGES = 2


@pytest.fixture(scope="module")
def mesh():
    return make_amp_mesh(max_mesh_devices())


def _deep_sched():
    flat = flatten_ops(_build_deep_global_circuit(N, DEPTH).ops, N,
                       False)
    return list(F.maybe_schedule(flat, N))


def _stats(lst, topo=None):
    items = F.plan(lst, N, bands=S._shard_bands(N, LOCAL_N))
    ib = topo.ici_bits(DEVICES) if (topo and topo.hierarchical) else None
    return C.comm_stats(C.predict_exchanges_items(items, LOCAL_N, ib),
                        num_devices=DEVICES, bytes_per_real=BPR,
                        topo=topo)


# -- the model itself --------------------------------------------------------

def test_topology_resolution_and_links():
    t = C.Topology(hosts=2, ici=1.0, dci=4.0)
    assert t.hierarchical
    assert t.devices_per_host(8) == 4
    assert t.ici_bits(8) == 2
    assert t.link_of(0, 8) == "ici" and t.link_of(1, 8) == "ici"
    assert t.link_of(2, 8) == "dci"
    assert t.link_of(None, 8) == "dci"      # an a2a touches every bit
    assert not C.FLAT.hierarchical
    assert C.FLAT.link_of(2, 8) == "ici"
    # more hosts than devices degenerates to one device per host
    assert C.Topology(hosts=16).ici_bits(8) == 0


def test_topology_knob_resolution(monkeypatch):
    monkeypatch.setenv("QUEST_COMM_TOPOLOGY", "0")
    assert C.topology(8) == C.FLAT
    monkeypatch.setenv("QUEST_COMM_TOPOLOGY", "hosts=2,ici=1,dci=8")
    t = C.topology(8)
    assert (t.hosts, t.ici, t.dci) == (2, 1.0, 8.0)
    # hosts clamp to the device count
    monkeypatch.setenv("QUEST_COMM_TOPOLOGY", "hosts=16")
    assert C.topology(8).hosts == 8
    # unset on a single-host process: flat, whatever the mesh size —
    # pure host planning of a hypothetical pod stays single-tier
    monkeypatch.delenv("QUEST_COMM_TOPOLOGY", raising=False)
    assert C.topology(256) == C.FLAT


def test_comm_stats_split_tiles_total():
    ex = [("cp", 16, 0), ("cp", 16, 2), ("a2a", 16, None)]
    topo = C.Topology(hosts=2)
    rec = C.comm_stats(ex, num_devices=8, bytes_per_real=8, topo=topo)
    assert rec["comm_ici_bytes"] + rec["comm_dci_bytes"] \
        == rec["comm_bytes"]
    # cp over bit 2 crosses; the a2a ships (8-4)/8 of 128 B across
    assert rec["comm_dci_bytes"] == 16 * 8 + (16 * 8) * 4 // 8
    assert rec["comm_dci_exchanges"] == 2
    flat = C.comm_stats(ex, num_devices=8, bytes_per_real=8)
    assert flat["comm_bytes"] == rec["comm_bytes"]
    assert flat["comm_dci_bytes"] == 0 and flat["comm_ici_bytes"] \
        == flat["comm_bytes"]


def test_weighted_cost_flat_is_pre_topology():
    ex = [("cp", 16, 0), ("cp", 16, 2), ("a2a", 16, None)]
    flat_cost = C._cost(ex, 8)
    assert flat_cost == (16 + 16 + 16 * 7 / 8, 3)
    w = C._cost(ex, 8, C.Topology(hosts=2, ici=1.0, dci=4.0))
    # bit-2 cp weighted 4x; a2a splits 3/8 ici + 4/8 dci
    assert w == (16 + 64 + 16 * (3 / 8 + 4 * 4 / 8), 3)


# -- goldens: flat bit-for-bit, hier >= 2x DCI below -------------------------

def test_flat_plan_reproduces_pre_topology_goldens(monkeypatch):
    """QUEST_COMM_TOPOLOGY=0 (and unset, on this single-host process)
    must select the PR-8 plans bit-for-bit: same strategy, same ops."""
    sched = _deep_sched()
    bands = S._shard_bands(N, LOCAL_N)
    plan_unset, info_unset = C.choose_plan(sched, N, LOCAL_N,
                                           engine="banded", bands=bands)
    monkeypatch.setenv("QUEST_COMM_TOPOLOGY", "0")
    plan_off, info_off = C.choose_plan(sched, N, LOCAL_N,
                                       engine="banded", bands=bands)
    assert info_unset["strategy"] == info_off["strategy"] == "coalesce"
    assert plan_unset == plan_off
    st = _stats(plan_off)
    assert st["comm_exchanges"] == FLAT_EXCHANGES
    assert st["comm_bytes"] == FLAT_BYTES
    assert "hier" not in info_off["candidates"]


def test_hier_plan_halves_dci_bytes_exact_counts():
    """The acceptance gate, CPU-side: on the deep-global testbed under
    hosts=2 the hierarchical planner's predicted comm_dci_bytes sit
    >= 2x below the flat plan's DCI share, at the pinned exact event
    counts — 2 DCI-crossing events (one localizing a2a + one restore
    hop) instead of one per layer."""
    sched = _deep_sched()
    bands = S._shard_bands(N, LOCAL_N)
    topo = C.Topology(hosts=2)
    flat_plan, _ = C.choose_plan(sched, N, LOCAL_N, engine="banded",
                                 bands=bands, topo=C.FLAT)
    hier_plan, info = C.choose_plan(sched, N, LOCAL_N, engine="banded",
                                    bands=bands, topo=topo)
    assert info["strategy"] == "hier"
    assert info["topology"]["hosts"] == 2
    flat_h = _stats(flat_plan, topo)
    hier_h = _stats(hier_plan, topo)
    assert flat_h["comm_dci_bytes"] == FLAT_DCI_BYTES
    assert flat_h["comm_dci_exchanges"] == FLAT_EXCHANGES
    assert hier_h["comm_dci_bytes"] == HIER_DCI_BYTES
    assert hier_h["comm_dci_exchanges"] == HIER_DCI_EXCHANGES
    assert 2 * hier_h["comm_dci_bytes"] <= flat_h["comm_dci_bytes"]
    # and the hierarchical plan also ships fewer TOTAL bytes here
    assert hier_h["comm_bytes"] < flat_h["comm_bytes"]


def test_cluster_plan_restores_standard_order():
    sched = _deep_sched()
    plan = C.coalesce_clusters(sched, N, LOCAL_N, C.Topology(hosts=2))
    tr = R._PermTracker(N, LOCAL_N, [])
    for op in plan:
        if op.kind == "relabel":
            tr.emit_relabel(op.operand)
        elif (op.kind == "matrix" and len(op.targets) == 2
              and isinstance(op.operand, np.ndarray)
              and np.array_equal(op.operand, R.SWAP)):
            tr.emit_swap(*op.targets)
    assert tr.perm == list(range(N))
    # local-only circuits and too-small chunks pass through untouched
    local = Circuit(N)
    for q in range(LOCAL_N):
        local.rx(q, 0.1 * (q + 1))
    flat2 = flatten_ops(local.ops, N, False)
    assert C.coalesce_clusters(flat2, N, LOCAL_N,
                               C.Topology(hosts=2)) == list(flat2)


def test_hot_victim_order_in_relabel_events():
    """Under a hierarchical topology plan_full_relabels assigns the
    SOONEST-reused victim to the lowest (ICI) device bit; flat keeps
    the farthest-first order bit-for-bit."""
    n, local_n = 6, 3
    flat = flatten_ops(_build_deep_global_circuit(n, 3).ops, n, False)
    ev_flat = [op.operand for op in
               R.plan_full_relabels(flat, n, local_n)
               if op.kind == "relabel"]
    ev_hot = [op.operand for op in
              R.plan_full_relabels(flat, n, local_n,
                                   topo=C.Topology(hosts=2))
              if op.kind == "relabel"]
    assert ev_flat and ev_hot
    # the victim SET is unchanged; the first event's bit assignment
    # reverses (the flat order is farthest-use-first onto bit 0)
    assert ev_hot[0] == tuple(reversed(ev_flat[0]))
    assert sorted(ev_hot[0]) == sorted(ev_flat[0])


# -- equivalence + lowered parity under the knob -----------------------------

def test_hier_equivalence_and_hlo_parity(mesh, monkeypatch):
    monkeypatch.setenv("QUEST_COMM_TOPOLOGY", "hosts=2")
    c = _build_deep_global_circuit(N, 3)
    make = qt.create_qureg
    want = to_dense(c.apply(qt.init_debug_state(
        make(N, dtype=np.complex128))))
    for engine, build in (("pergate", S.compile_circuit_sharded),
                          ("banded", S.compile_circuit_sharded_banded)):
        sq = shard_qureg(qt.init_debug_state(
            make(N, dtype=np.complex128)), mesh)
        fn = build(c.ops, N, False, mesh, donate=False)
        got = to_dense(sq.replace_amps(fn(sq.amps)))
        np.testing.assert_allclose(got, want, atol=1e-12, rtol=0)
        rec = sharded_schedule(c.ops, N, False, mesh, engine=engine)
        assert rec["comm_matches_hlo"], rec
        assert rec["comm_topology"]["hosts"] == 2
        assert rec["comm_ici_bytes"] + rec["comm_dci_bytes"] \
            == rec["comm_bytes"]


def test_dci_slicing_parity_and_bit_identity(mesh, monkeypatch):
    """QUEST_EXCHANGE_SLICES_DCI slices ONLY host-crossing exchanges —
    finer than the ICI ones — with predicted == lowered per link class,
    and the same amplitudes (slicing splits transfers, never
    arithmetic)."""
    monkeypatch.setenv("QUEST_COMM_PLAN", "0")
    monkeypatch.setenv("QUEST_COMM_TOPOLOGY", "hosts=2")
    c = Circuit(N).rx(N - 1, 0.4).rx(3, 0.2).swap(0, N - 1)
    rec1 = sharded_schedule(c.ops, N, False, mesh, engine="pergate")
    monkeypatch.setenv("QUEST_EXCHANGE_SLICES_DCI", "4")
    rec4 = sharded_schedule(c.ops, N, False, mesh, engine="pergate")
    assert rec4["comm_matches_hlo"], rec4
    assert rec4["comm_bytes"] == rec1["comm_bytes"]
    # only the DCI exchanges multiplied (x4): the rx(3) ICI butterfly
    # stays one permute
    assert rec4["comm_collective_permutes"] \
        > rec1["comm_collective_permutes"]
    assert rec4["comm_dci_bytes"] == rec1["comm_dci_bytes"]

    q = qt.init_debug_state(qt.create_qureg(N, dtype=np.complex128))
    sq = shard_qureg(q, mesh)
    monkeypatch.delenv("QUEST_EXCHANGE_SLICES_DCI")
    f1 = S.compile_circuit_sharded(c.ops, N, False, mesh, donate=False)
    a = np.asarray(f1(sq.amps))
    monkeypatch.setenv("QUEST_EXCHANGE_SLICES_DCI", "4")
    f4 = S.compile_circuit_sharded(c.ops, N, False, mesh, donate=False)
    b = np.asarray(f4(sq.amps))
    # 4 eps, not bit for bit: XLA:CPU contracts the combine into FMAs
    # differently per fusion shape (test_comm's slicing test says more)
    np.testing.assert_allclose(b, a, rtol=0,
                               atol=4 * np.finfo(a.dtype).eps
                               * np.abs(a).max())


def test_effective_slices_per_link(monkeypatch):
    monkeypatch.setenv("QUEST_EXCHANGE_SLICES", "2")
    assert C.effective_slices(64, "ici") == 2
    assert C.effective_slices(64, "dci") == 2     # dci=0 follows
    monkeypatch.setenv("QUEST_EXCHANGE_SLICES_DCI", "8")
    assert C.effective_slices(64, "ici") == 2
    assert C.effective_slices(64, "dci") == 8
    assert C.effective_slices(4, "dci") == 4      # clamped to block


# -- plan_stats / explain surfaces -------------------------------------------

def test_plan_stats_topology_record(monkeypatch):
    monkeypatch.setenv("QUEST_COMM_TOPOLOGY", "hosts=2,ici=1,dci=4")
    c = _build_deep_global_circuit(N, DEPTH)
    rec = c.plan_stats(devices=8)["comm"]
    assert rec["comm_topology"]["hosts"] == 2
    assert rec["comm_dci_bytes"] > 0
    assert rec["comm_ici_bytes"] + rec["comm_dci_bytes"] \
        == rec["comm_bytes"]


def test_explain_sharded_topology_line(mesh, monkeypatch):
    monkeypatch.setenv("QUEST_COMM_TOPOLOGY", "hosts=2")
    text = _build_deep_global_circuit(N, 3).explain_sharded(mesh)
    assert "topology: 2 host(s)" in text, text
    assert "DCI" in text
