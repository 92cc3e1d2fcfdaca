"""Elastic durable resume (ISSUE 15, docs/RESILIENCE.md §elastic): a
checkpoint chain is a property of the LOGICAL state — any mesh that can
hold the amplitudes can resume it. Pins:

  * canonical-order checkpoint layout (save-side relabel-perm
    normalization) round-trips exactly and keeps strict resume
    bit-identical;
  * elastic resume pinned BIT-identical to an uninterrupted native run
    on the target mesh for sharded 2dev->1dev, 1dev->2dev and
    fused->sharded (the mesh-portable circuit, bench's
    _build_elastic_circuit, under QUEST_SCHEDULE=0 — see its docstring
    for why general circuits resume eps-close instead);
  * mesh mismatch WITHOUT elastic=True still rejects typed; old-format
    (physical-layout, pre-elastic cursor) checkpoints load tolerantly
    on their own mesh and reject loudly on a changed one — never
    resume wrong;
  * corrupt checkpoints skip loudly to older ones under elastic scan
    (digest re-verification on reshard);
  * the serve dispatch watchdog (QUEST_DISPATCH_TIMEOUT_S) fails a
    wedged launch typed DispatchTimeout within ~2x the deadline,
    counts toward the program's breaker, and replaces the worker so
    drain() completes;
  * the PR-13 footgun warning: per-gate Circuit.compiled warns once
    per process above PERGATE_COMPILE_WARN_OPS;
  * fault catalog: checkpoint.load_gang and fleet.requeue exist and
    fire.

The gang 2-host -> 1-host -> 2-host chaos soak is slow-marked at the
bottom (tests/_elastic_worker.py, the test_multihost discipline).
"""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

import quest_tpu as qt
import bench
from quest_tpu import checkpoint as ckpt
from quest_tpu.circuit import Circuit
from quest_tpu.parallel import relabel as R
from quest_tpu.resilience import (DurableError, FaultPlan, faults,
                                  run_durable)
from quest_tpu.serve import metrics

from .helpers import max_mesh_devices

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N = 10


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    before = faults.current()
    yield
    faults.install(before)


@pytest.fixture()
def portable_env(monkeypatch):
    """The bit-identity pins run with the scheduler's diagonal pooling
    off: it hoists _build_elastic_circuit's cz blockers away and
    re-merges the rotations into mesh-UNportable multi-qubit band
    operators (the circuit builder's docstring has the full rules)."""
    monkeypatch.setenv("QUEST_SCHEDULE", "0")


def _circ(n=N, layers=3, seed=7):
    return bench._build_elastic_circuit(n, layers=layers, seed=seed)


def _sv(n=N):
    base = np.zeros((2, 1 << n), dtype=np.float32)
    base[0, 0] = 1.0
    return qt.Qureg(amps=jax.numpy.asarray(base), num_qubits=n,
                    is_density=False)


def _shv(mesh, n=N):
    from quest_tpu.parallel import shard_qureg
    return shard_qureg(_sv(n), mesh)


def _amps(q):
    return np.asarray(jax.device_get(q.amps))


def _preempt(runner, after, times=1):
    plan = FaultPlan().inject("durable.preempt", after_n=after,
                              times=times)
    with faults.active(plan):
        with pytest.raises(faults.InjectedFault):
            runner()
    assert plan.fired() == times


def _mesh2():
    from quest_tpu.parallel import make_amp_mesh
    if max_mesh_devices(2) < 2:
        pytest.skip("needs 2 devices")
    return make_amp_mesh(2)


# ---------------------------------------------------------------------------
# canonical <-> physical layout: the checkpoint contract's foundation
# ---------------------------------------------------------------------------


def test_canonicalize_planes_matches_gather_oracle_and_roundtrips():
    rng = np.random.default_rng(3)
    for n in (3, 6):
        for _ in range(10):
            perm = list(rng.permutation(n))
            x = rng.standard_normal((2, 1 << n)).astype(np.float32)
            canon = R.canonicalize_planes(x, perm)
            phi = np.zeros(1 << n, dtype=np.int64)
            for c in range(1 << n):
                v = 0
                for bit in range(n):
                    v |= ((c >> bit) & 1) << perm[bit]
                phi[c] = v
            np.testing.assert_array_equal(canon, x[:, phi])
            np.testing.assert_array_equal(
                R.physicalize_planes(canon, perm), x)
    # identity perm passes through untouched (no copy even)
    x = rng.standard_normal((2, 8)).astype(np.float32)
    assert R.canonicalize_planes(x, [0, 1, 2]) is x


def test_strict_resume_with_canonical_saves_stays_bit_identical(tmp_path):
    """The save side now normalizes sharded planes to canonical order
    (undoing the live relabel permutation); the strict resume path
    physicalizes back through the VALIDATED perm — an exact index
    round trip, pinned on a relabel-heavy circuit whose cut perm is
    nontrivial."""
    from quest_tpu.parallel import make_amp_mesh
    if max_mesh_devices(4) < 4:
        pytest.skip("needs 4 devices")
    mesh = make_amp_mesh(4)
    n = 8
    rng = np.random.default_rng(11)
    c = Circuit(n)
    for _ in range(6):
        for q in range(n):
            c.rx(q, float(rng.uniform(0, 2 * np.pi)))
            c.ry(q, float(rng.uniform(0, 2 * np.pi)))
        for q in range(0, n - 1, 2):
            c.cz(q, q + 1)
    ref = run_durable(c, _shv(mesh, n), str(tmp_path / "ref"), every=2,
                      mesh=mesh)
    d = str(tmp_path / "pre")
    _preempt(lambda: run_durable(c, _shv(mesh, n), d, every=2,
                                 mesh=mesh), after=9)
    dirs = ckpt.step_dirs(d)
    assert dirs
    cursor = ckpt.read_extra(dirs[-1][1])
    assert cursor["layout"] == "canonical"
    # the pin is only meaningful if the cut's perm is nontrivial
    assert cursor["perm"] != list(range(n))
    out = run_durable(c, _shv(mesh, n), d, every=2, mesh=mesh)
    np.testing.assert_array_equal(_amps(out), _amps(ref))
    assert ckpt.step_dirs(d) == []


# ---------------------------------------------------------------------------
# elastic bit-identity pins (the acceptance list)
# ---------------------------------------------------------------------------


def test_elastic_sharded_2dev_to_1dev_bit_identical(tmp_path,
                                                    portable_env):
    mesh = _mesh2()
    c = _circ()
    ref = run_durable(c, _sv(), str(tmp_path / "ref"), every=3,
                      engine="banded")
    d = str(tmp_path / "pre")
    _preempt(lambda: run_durable(c, _shv(mesh), d, every=3, mesh=mesh),
             after=5)
    assert ckpt.step_dirs(d), "no checkpoint before the kill"
    reg = metrics.Registry()
    out = run_durable(c, _sv(), d, every=3, engine="banded",
                      elastic=True, registry=reg)
    np.testing.assert_array_equal(_amps(out), _amps(ref))
    assert reg.counter("durable_resumes").value == 1
    assert reg.counter("durable_elastic_resumes").value == 1
    assert ckpt.step_dirs(d) == []


def test_elastic_1dev_to_2dev_bit_identical(tmp_path, portable_env):
    mesh = _mesh2()
    c = _circ()
    ref = run_durable(c, _shv(mesh), str(tmp_path / "ref"), every=3,
                      mesh=mesh)
    d = str(tmp_path / "pre")
    _preempt(lambda: run_durable(c, _sv(), d, every=3, engine="banded"),
             after=5)
    out = run_durable(c, _shv(mesh), d, every=3, mesh=mesh, elastic=True)
    np.testing.assert_array_equal(_amps(out), _amps(ref))
    assert ckpt.step_dirs(d) == []


def test_elastic_fused_to_sharded_bit_identical(tmp_path, portable_env,
                                                monkeypatch):
    # sweep fusion off: at this size the swept fused plan is ONE launch
    # — nothing to cut mid-chain; knob-off splits kernel segments
    monkeypatch.setenv("QUEST_SWEEP_FUSION", "0")
    mesh = _mesh2()
    c = _circ()
    ref = run_durable(c, _shv(mesh), str(tmp_path / "ref"), every=3,
                      mesh=mesh)
    d = str(tmp_path / "pre")
    _preempt(lambda: run_durable(c, _sv(), d, every=1, engine="fused",
                                 interpret=True), after=1)
    assert ckpt.step_dirs(d)
    out = run_durable(c, _shv(mesh), d, every=3, mesh=mesh, elastic=True)
    np.testing.assert_array_equal(_amps(out), _amps(ref))


def test_elastic_general_circuit_resumes_eps_close(tmp_path):
    """General circuits (default knobs, relabel-heavy) have no
    mesh-portable arithmetic guarantee: the elastic resume walks past
    non-portable cuts LOUDLY and still lands eps-close to the native
    run — never wrong, never a crash."""
    from quest_tpu.parallel import make_amp_mesh
    if max_mesh_devices(4) < 4:
        pytest.skip("needs 4 devices")
    mesh4, mesh2 = make_amp_mesh(4), make_amp_mesh(2)
    n = 8
    rng = np.random.default_rng(11)
    c = Circuit(n)
    for _ in range(6):
        for q in range(n):
            c.rx(q, float(rng.uniform(0, 2 * np.pi)))
            c.ry(q, float(rng.uniform(0, 2 * np.pi)))
        for q in range(0, n - 1, 2):
            c.cz(q, q + 1)
    ref = run_durable(c, _shv(mesh2, n), str(tmp_path / "ref"), every=2,
                      mesh=mesh2)
    d = str(tmp_path / "pre")
    _preempt(lambda: run_durable(c, _shv(mesh4, n), d, every=2,
                                 mesh=mesh4), after=9)
    out = run_durable(c, _shv(mesh2, n), d, every=2, mesh=mesh2,
                      elastic=True)
    np.testing.assert_allclose(_amps(out), _amps(ref), atol=1e-5)
    assert ckpt.step_dirs(d) == []


# ---------------------------------------------------------------------------
# typed rejects: elastic relaxes WHERE, never WHAT
# ---------------------------------------------------------------------------


def test_mesh_mismatch_without_elastic_still_rejects_typed(tmp_path):
    mesh = _mesh2()
    c = _circ()
    d = str(tmp_path / "pre")
    _preempt(lambda: run_durable(c, _shv(mesh), d, every=3, mesh=mesh),
             after=5)
    with pytest.raises(DurableError, match="devices|num_steps|engine"):
        run_durable(c, _sv(), d, every=3, engine="banded")


def test_elastic_rejects_a_different_circuit_typed(tmp_path,
                                                   portable_env):
    mesh = _mesh2()
    d = str(tmp_path / "pre")
    _preempt(lambda: run_durable(_circ(seed=7), _shv(mesh), d, every=3,
                                 mesh=mesh), after=5)
    with pytest.raises(DurableError, match="sched_sha|plan_sha"):
        run_durable(_circ(seed=8), _sv(), d, every=3, engine="banded",
                    elastic=True)


def test_elastic_rejects_a_different_initial_state_typed(tmp_path,
                                                         portable_env):
    mesh = _mesh2()
    c = _circ()
    d = str(tmp_path / "pre")
    _preempt(lambda: run_durable(c, _shv(mesh), d, every=3, mesh=mesh),
             after=5)
    other = _sv()
    base = np.zeros((2, 1 << N), dtype=np.float32)
    base[0, 1] = 1.0                     # |0...01>, not |0...0>
    other = other.replace_amps(jax.numpy.asarray(base))
    with pytest.raises(DurableError, match="state_efp"):
        run_durable(c, other, d, every=3, engine="banded", elastic=True)


def test_old_format_checkpoint_tolerant_same_mesh_loud_cross_mesh(
        tmp_path, portable_env):
    """A pre-elastic chain (physical layout, no sched_sha) must load
    tolerantly under elastic=True on its own mesh and reject typed on
    a changed one — never resume wrong."""
    c = _circ()
    ref = run_durable(c, _sv(), str(tmp_path / "ref"), every=3,
                      engine="banded")
    d = str(tmp_path / "pre")
    _preempt(lambda: run_durable(c, _sv(), d, every=3, engine="banded"),
             after=5)
    # rewrite the newest checkpoint as the OLD format: strip the
    # elastic fields + layout flag (banded cuts have identity perm, so
    # the stored planes are physical == canonical)
    step, path = ckpt.step_dirs(d)[-1]
    meta, arrays = ckpt.load_arrays(path, require=("planes",))
    cursor = dict(meta["extra"])
    for k in ("sched_sha", "ops_total", "ops_done", "state_efp",
              "dtype", "density", "layout"):
        cursor.pop(k, None)
    q_old = qt.Qureg(amps=np.asarray(arrays["planes"]),
                     num_qubits=N, is_density=False)
    ckpt.save_step(d, step, qureg=q_old, extra=cursor)
    # tolerant on the writing mesh
    out = run_durable(c, _sv(), d, every=3, engine="banded",
                      elastic=True)
    np.testing.assert_array_equal(_amps(out), _amps(ref))
    # loud on a changed mesh
    mesh = _mesh2()
    d2 = str(tmp_path / "pre2")
    _preempt(lambda: run_durable(c, _sv(), d2, every=3,
                                 engine="banded"), after=5)
    step, path = ckpt.step_dirs(d2)[-1]
    meta, arrays = ckpt.load_arrays(path, require=("planes",))
    cursor = dict(meta["extra"])
    for k in ("sched_sha", "ops_total", "ops_done", "state_efp",
              "dtype", "density", "layout"):
        cursor.pop(k, None)
    ckpt.save_step(d2, step,
                   qureg=qt.Qureg(amps=np.asarray(arrays["planes"]),
                                  num_qubits=N, is_density=False),
                   extra=cursor)
    with pytest.raises(DurableError):
        run_durable(c, _shv(mesh), d2, every=3, mesh=mesh, elastic=True)


def test_elastic_skips_corrupt_newest_to_older_and_stays_exact(
        tmp_path, portable_env):
    """Digest re-verification on reshard: a flipped byte in the newest
    checkpoint makes the elastic scan skip it LOUDLY and resume the
    older one — final amplitudes still bit-identical to native."""
    mesh = _mesh2()
    c = _circ(layers=4)
    ref = run_durable(c, _sv(), str(tmp_path / "ref"), every=2,
                      engine="banded")
    d = str(tmp_path / "pre")
    _preempt(lambda: run_durable(c, _shv(mesh), d, every=2, mesh=mesh,
                                 keep=3), after=9)
    dirs = ckpt.step_dirs(d)
    assert len(dirs) >= 2, "need an older checkpoint to fall back to"
    amps_path = os.path.join(dirs[-1][1], "amps.npz")
    blob = bytearray(open(amps_path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(amps_path, "wb").write(bytes(blob))
    reg = metrics.Registry()
    out = run_durable(c, _sv(), d, every=2, engine="banded",
                      elastic=True, registry=reg)
    np.testing.assert_array_equal(_amps(out), _amps(ref))
    assert reg.counter("durable_corrupt_checkpoints_skipped").value >= 1


def test_load_step_elastic_mesh_reentry_matches_manual_path(tmp_path):
    """The standalone mesh=/perm= re-entry of load_step_elastic (the
    ISSUE-15 signature) places the canonical planes onto the target
    mesh exactly like the manual physicalize + device-put path the
    durable executor uses."""
    mesh = _mesh2()
    c = _circ()
    d = str(tmp_path / "pre")
    _preempt(lambda: run_durable(c, _sv(), d, every=3, engine="banded"),
             after=5)
    step, path = ckpt.step_dirs(d)[-1]
    cursor, canon = ckpt.load_step_elastic(path)
    assert cursor["step"] == step
    rng = np.random.default_rng(0)
    perm = list(rng.permutation(N))
    cursor2, placed = ckpt.load_step_elastic(path, mesh=mesh, perm=perm)
    assert cursor2 == cursor
    import jax as _jax
    got = np.asarray(_jax.device_get(placed))
    np.testing.assert_array_equal(
        got, R.physicalize_planes(np.asarray(canon), perm))
    from quest_tpu.parallel.mesh import amp_sharding
    assert placed.sharding == amp_sharding(mesh)
    # perm=None enters canonical order unchanged
    _, placed0 = ckpt.load_step_elastic(path, mesh=mesh)
    np.testing.assert_array_equal(
        np.asarray(_jax.device_get(placed0)), np.asarray(canon))


def test_elastic_cursor_fields_ride_every_state_checkpoint(tmp_path):
    c = _circ()
    d = str(tmp_path / "pre")
    _preempt(lambda: run_durable(c, _sv(), d, every=3, engine="banded"),
             after=5)
    cursor = ckpt.read_extra(ckpt.step_dirs(d)[-1][1])
    assert cursor["layout"] == "canonical"
    assert isinstance(cursor["sched_sha"], str)
    assert isinstance(cursor["ops_total"], int)
    assert isinstance(cursor["state_efp"], str)
    assert cursor["ops_done"] is None or isinstance(cursor["ops_done"],
                                                    int)


def test_quest_durable_elastic_knob_defaults_the_parameter(
        tmp_path, portable_env, monkeypatch):
    mesh = _mesh2()
    c = _circ()
    ref = run_durable(c, _sv(), str(tmp_path / "ref"), every=3,
                      engine="banded")
    d = str(tmp_path / "pre")
    _preempt(lambda: run_durable(c, _shv(mesh), d, every=3, mesh=mesh),
             after=5)
    monkeypatch.setenv("QUEST_DURABLE_ELASTIC", "1")
    out = run_durable(c, _sv(), d, every=3, engine="banded")
    np.testing.assert_array_equal(_amps(out), _amps(ref))


# ---------------------------------------------------------------------------
# dispatch watchdog
# ---------------------------------------------------------------------------


def _wedge(eng, sleep_s):
    orig = eng._apply_program

    def wedged(q, b, rung):
        fn = orig(q, b, rung)

        def run(batch):
            time.sleep(sleep_s)
            return fn(batch)

        run.bucket = fn.bucket
        return run

    eng._apply_program = wedged
    return orig


def test_dispatch_watchdog_fails_wedged_launch_and_recovers():
    from quest_tpu.serve.admission import DispatchTimeout
    from quest_tpu.serve.engine import ServeEngine

    c = Circuit(4).h(0).cnot(0, 1)
    state = np.zeros((2, 16), dtype=np.float32)
    state[0, 0] = 1.0
    reg = metrics.Registry()
    with ServeEngine(max_wait_ms=1, registry=reg, backoff_base_s=0.0,
                     dispatch_timeout_s=0.5) as eng:
        # warm the program first so compile time cannot eat the
        # deadline (the watchdog deadline covers the WHOLE dispatch)
        eng.submit(c, state=state).result(timeout=120)
        orig = _wedge(eng, sleep_s=30.0)
        t0 = time.monotonic()
        fut = eng.submit(c, state=state)
        with pytest.raises(DispatchTimeout):
            fut.result(timeout=10.0)
        assert time.monotonic() - t0 < 2 * 0.5 + 0.5   # 2x + slack
        # the replacement worker keeps serving
        eng._apply_program = orig
        out = eng.submit(c, state=state).result(timeout=120)
        assert np.asarray(out).shape == (2, 16)
        # drain completes instead of hanging on the wedged thread
        eng.drain(timeout_s=30.0)
    snap = reg.snapshot()["counters"]
    assert snap["serve_dispatch_timeouts"] >= 1
    assert snap["serve_worker_restarts"] >= 1


def test_watchdog_wedge_counts_toward_the_breaker():
    from quest_tpu.serve.admission import DispatchTimeout
    from quest_tpu.serve.engine import ServeEngine

    c = Circuit(4).h(0)
    state = np.zeros((2, 16), dtype=np.float32)
    state[0, 0] = 1.0
    reg = metrics.Registry()
    with ServeEngine(max_wait_ms=1, registry=reg, backoff_base_s=0.0,
                     breaker_threshold=1, dispatch_timeout_s=0.4) as eng:
        eng.submit(c, state=state).result(timeout=120)
        _wedge(eng, sleep_s=30.0)
        fut = eng.submit(c, state=state)
        with pytest.raises(DispatchTimeout):
            fut.result(timeout=10.0)
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            br = eng._breakers.get(next(iter(eng._breakers), None))
            if br is not None and br.failures >= 1:
                break
            time.sleep(0.05)
        assert any(b.failures >= 1 or b.state != "closed"
                   for b in eng._breakers.values())


def test_watchdog_off_by_default_spawns_no_monitor():
    from quest_tpu.serve.engine import ServeEngine
    with ServeEngine(max_wait_ms=1,
                     registry=metrics.Registry()) as eng:
        assert eng.dispatch_timeout_s == 0.0
        assert eng._watchdog is None


# ---------------------------------------------------------------------------
# fault catalog: the two new sites
# ---------------------------------------------------------------------------


def test_new_fault_sites_registered():
    assert "checkpoint.load_gang" in faults.SITES
    assert "fleet.requeue" in faults.SITES
    FaultPlan().inject("checkpoint.load_gang").inject("fleet.requeue")


def test_fleet_requeue_site_fails_the_requeue_hop_typed(tmp_path):
    """fleet.requeue fires on the failover RE-SUBMIT hop (after the
    fleet.failover decision point): an armed error resolves the
    requeued ticket typed instead of re-serving it."""
    from quest_tpu.serve import ServeFleet

    circ = bench._build_durable_circuit(8, layers=4)
    q0 = qt.init_debug_state(qt.create_qureg(8))
    s0 = np.asarray(jax.device_get(q0.amps))
    reg = metrics.Registry()
    plan = FaultPlan()
    plan.inject("durable.preempt", after_n=3, times=1)
    # r0 dies past its budget on durable work; the requeue hop is armed
    plan.inject("serve.dispatch", error=RuntimeError("replica dying"),
                match=lambda ctx: (ctx.get("replica") == "r0"
                                   and ctx.get("durable")), after_n=1)
    plan.inject("fleet.requeue")
    with faults.active(plan):
        with ServeFleet(replicas=2, max_wait_ms=2, restart_max=1,
                        backoff_base_s=0.0, registry=reg) as fl:
            fut = fl.submit(circ, state=s0,
                            durable_dir=str(tmp_path / "job"),
                            durable_every=2)
            with pytest.raises(faults.InjectedFault):
                fut.result(timeout=600)
    assert plan.fired("fleet.requeue") == 1


def test_fleet_elastic_failover_across_meshes(tmp_path, portable_env):
    """THE heterogeneous-fleet gate (docs/RESILIENCE.md §elastic): the
    replica running a durable job SHARDED over a 4-device mesh dies
    past its budget mid-chain; the surviving replica owns a SMALLER
    (2-device) mesh and resumes the dead replica's chain elastically —
    final planes bit-identical to an uninterrupted native run (the
    mesh-portable circuit)."""
    from quest_tpu.parallel import make_amp_mesh
    from quest_tpu.serve import ServeFleet

    if max_mesh_devices(4) < 4:
        pytest.skip("needs 4 devices")
    mesh4, mesh2 = make_amp_mesh(4), make_amp_mesh(2)
    c = _circ()
    ref = run_durable(c, _shv(mesh2), str(tmp_path / "ref"), every=10,
                      mesh=mesh2)
    s0 = np.zeros((2, 1 << N), dtype=np.float32)
    s0[0, 0] = 1.0
    reg = metrics.Registry()
    plan = FaultPlan()
    plan.inject("durable.preempt", after_n=12, times=1)
    plan.inject("serve.dispatch", error=RuntimeError("replica dying"),
                match=lambda ctx: (ctx.get("replica") == "r0"
                                   and ctx.get("durable")), after_n=1)
    with faults.active(plan):
        with ServeFleet(replicas=2, max_wait_ms=2, restart_max=1,
                        backoff_base_s=0.0, registry=reg,
                        durable_mesh=[mesh4, mesh2],
                        durable_elastic=True) as fl:
            out = fl.submit(c, state=s0,
                            durable_dir=str(tmp_path / "job"),
                            durable_every=10).result(timeout=600)
    np.testing.assert_array_equal(np.asarray(out), _amps(ref))
    snap = reg.snapshot()["counters"]
    assert snap["fleet_failovers"] >= 1
    assert snap["durable_elastic_resumes"] >= 1
    assert ckpt.step_dirs(str(tmp_path / "job")) == []


# ---------------------------------------------------------------------------
# the per-gate compile footgun warning
# ---------------------------------------------------------------------------


def test_pergate_compile_warning_once_above_threshold(capfd,
                                                      monkeypatch):
    from quest_tpu import circuit as C

    monkeypatch.setattr(C, "_pergate_warned", False)
    small = Circuit(4)
    for _ in range(C.PERGATE_COMPILE_WARN_OPS // 2):
        small.rx(0, 0.1)
    small.compiled(4, False, donate=False)
    assert "PER-GATE" not in capfd.readouterr().err
    big = Circuit(4)
    for _ in range(C.PERGATE_COMPILE_WARN_OPS + 1):
        big.rx(0, 0.1)
    big.compiled(4, False, donate=False)      # jit is lazy: no compile
    err = capfd.readouterr().err
    assert "apply_banded" in err and "compiled_fused" in err
    big.compiled(4, False, donate=False, iters=2)
    assert "PER-GATE" not in capfd.readouterr().err   # once per process


# ---------------------------------------------------------------------------
# the gang elastic chaos soak (2-host -> 1-host -> 2-host)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_elastic_gang_soak_two_process(tmp_path):
    """Slow-marked (test_multihost discipline, ~3-5 min: five jax
    imports across two generations of 2-process gloo meshes plus a
    single-host interlude): a gang 2-host run is killed MID-SAVE, the
    chain resumes on ONE host at D' < D devices, is preempted again,
    and resumes BACK on 2 hosts — final amplitudes bit-identical to an
    uninterrupted native 2-host run, chain and gang tmps consumed
    (tests/_elastic_worker.py carries the per-phase assertions)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["QUEST_SCHEDULE"] = "0"       # the portable-circuit discipline
    env.pop("QUEST_COMM_TOPOLOGY", None)
    worker = os.path.join(REPO, "tests", "_elastic_worker.py")

    def gang_phase(phase: str, port: str):
        procs = [subprocess.Popen(
            [sys.executable, worker, str(i), "2", port, str(tmp_path),
             phase],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True) for i in range(2)]
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=600)
            outs.append(out)
        for i, (p, o) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"proc {i} ({phase}):\n{o[-4000:]}"
        return outs

    # phase 1 (gang): uninterrupted baseline hash + mid-save kill
    outs = gang_phase("baseline-and-kill", "19833")
    assert all("elastic baseline ok" in o for o in outs)
    assert all("elastic midsave-kill ok" in o for o in outs)

    # phase 2 (single host, D' < D): elastic resume of the gang chain,
    # preempted again mid-run — the chain now ends in a PLAIN-format
    # checkpoint on top of gang-format ones
    single = subprocess.run(
        [sys.executable, worker, "solo", "1", "0", str(tmp_path),
         "solo-resume-and-kill"],
        env=env, capture_output=True, text=True, timeout=600)
    assert single.returncode == 0, single.stdout[-4000:] + single.stderr[-2000:]
    assert "elastic solo-resume ok" in single.stdout

    # phase 3 (gang again): elastic resume back onto 2 hosts completes
    # bit-identical; chain + gang tmps consumed
    outs = gang_phase("final-resume", "19834")
    assert all("elastic final ok" in o for o in outs)
