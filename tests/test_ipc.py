"""The PR-18 process-fleet boundary (docs/SERVING.md §process-fleet):
serve/ipc.py's ReplicaProxy + worker_main wire protocol, the fault
sites it fires (fleet.spawn / ipc.send / ipc.recv — armed HERE, the
QL009 contract), the elastic autoscaler's control loop, and the
concurrent plan-cache discipline N worker processes share on disk.

The heavyweight end-to-end gates (bit-identity vs one in-process
engine, SIGKILL-zero-loss under load, autoscaler convergence on a real
process fleet) live in scripts/check_fleet_golden.py; these tests pin
the per-path contracts with the smallest process count that exercises
each one.
"""

import json
import os
import pickle
import signal
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from quest_tpu.circuit import Circuit
from quest_tpu.resilience import FaultPlan, faults
from quest_tpu.serve import ReplicaProxy, ServeFleet, metrics
from quest_tpu.serve.admission import RejectedError
from quest_tpu.serve.ipc import (circuit_descriptor, circuit_digest,
                                 decode_key, encode_key, rebuild_circuit,
                                 wire_exc)

N = 4


def _circ(n=N):
    c = Circuit(n)
    c.h(0)
    c.cnot(0, 1)
    c.rz(min(2, n - 1), 0.25)
    return c


def _states(k, n=N, seed=3):
    rng = np.random.default_rng(seed)
    s = rng.standard_normal((k, 2, 1 << n)).astype(np.float32)
    return s / np.sqrt((s ** 2).sum(axis=(1, 2), keepdims=True))


# ---------------------------------------------------------------------------
# value-keyed descriptors + key codec (pure, no processes)
# ---------------------------------------------------------------------------


def test_circuit_descriptor_round_trips_by_value():
    c = _circ()
    desc = circuit_descriptor(c)
    # the descriptor must survive the wire (pickle) and rebuild to the
    # same digest — the identity the shared plan/XLA caches key on
    desc2 = pickle.loads(pickle.dumps(desc))
    rebuilt = rebuild_circuit(desc2)
    assert rebuilt.num_qubits == c.num_qubits
    assert len(rebuilt.ops) == len(c.ops)
    assert circuit_digest(rebuilt) == circuit_digest(c)


def test_circuit_digest_is_cached_and_value_keyed():
    a, b = _circ(), _circ()
    assert a is not b
    assert circuit_digest(a) == circuit_digest(b)   # value, not identity
    a.x(0)
    assert circuit_digest(a) != circuit_digest(b)   # append invalidates


def test_key_codec_round_trips_typed_and_raw():
    k = jax.random.key(7)
    dec = decode_key(encode_key(k))
    assert np.array_equal(jax.random.key_data(dec), jax.random.key_data(k))
    raw = jax.random.PRNGKey(7)
    dec_raw = decode_key(encode_key(raw))
    assert np.array_equal(np.asarray(dec_raw), np.asarray(raw))
    assert decode_key(encode_key(None)) is None


def test_wire_exc_preserves_type_or_degrades_loudly():
    e = wire_exc(RejectedError("queue full"))
    assert isinstance(e, RejectedError) and "queue full" in str(e)

    class Unpicklable(Exception):
        def __reduce__(self):
            raise TypeError("nope")

    d = wire_exc(Unpicklable("boom"))
    assert isinstance(d, RejectedError) and "Unpicklable" in str(d)


# ---------------------------------------------------------------------------
# one shared 2-process fleet: round trip + contract surface
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def proc_fleet():
    reg = metrics.Registry()
    fleet = ServeFleet(replicas=2, process=True, max_wait_ms=2,
                       max_batch=4, registry=reg)
    yield fleet
    fleet.close(timeout_s=15)


def test_process_fleet_round_trip(proc_fleet):
    """Submit/result through the pipe, both request modes, and the
    fleet contract surface (routing counters, stats, merged scrape)."""
    c = _circ()
    states = _states(6)
    outs = [proc_fleet.submit(c, state=states[i]).result(timeout=120)
            for i in range(6)]
    assert all(np.asarray(o).shape == (2, 1 << N) for o in outs)
    shots_out = proc_fleet.submit(
        c, shots=8, key=jax.random.key(1)).result(timeout=120)
    assert isinstance(shots_out, tuple)
    st = proc_fleet.stats()
    assert st["process"] is True
    assert all(r["state"] == "running" for r in st["replicas"])
    # the merged scrape: fleet-level series from the parent registry
    # AND per-worker serve series from the heartbeat snapshots
    scrape = proc_fleet.scrape()
    assert "fleet_requests_routed" in scrape
    assert "serve_requests_served" in scrape


def test_process_fleet_results_match_thread_fleet(proc_fleet):
    """The IPC boundary is a transport: same requests, same bits as a
    thread-backed fleet (the full sweep gate lives in
    scripts/check_fleet_golden.py)."""
    c = _circ()
    states = _states(4, seed=11)
    with ServeFleet(replicas=2, process=False, max_wait_ms=2,
                    max_batch=4, registry=metrics.Registry()) as tf:
        want = [np.asarray(tf.submit(c, state=states[i])
                           .result(timeout=120)) for i in range(4)]
    got = [np.asarray(proc_fleet.submit(c, state=states[i])
                      .result(timeout=120)) for i in range(4)]
    for w, g in zip(want, got):
        assert np.array_equal(w, g)


def test_unpicklable_observable_rejected_with_guidance(proc_fleet):
    """A lambda observable cannot cross a process boundary: the submit
    must fail SYNCHRONOUSLY with actionable guidance, not wedge the
    worker with a frame it can't decode."""
    with pytest.raises(ValueError, match="thread replicas"):
        proc_fleet.submit(_circ(), state=_states(1)[0],
                          observable=lambda x: x)


def test_drain_round_trips_the_worker(proc_fleet):
    futs = [proc_fleet.submit(_circ(), state=s) for s in _states(4, seed=5)]
    proc_fleet.drain(timeout_s=120)
    assert all(f.done() for f in futs)


# ---------------------------------------------------------------------------
# supervision: SIGKILL -> respawn -> resubmit; budget -> fleet failover
# ---------------------------------------------------------------------------


def test_sigkill_respawns_and_resubmits_inflight():
    """kill -9 (no goodbye frame, no flush): the heartbeat watchdog
    must notice, respawn the worker, resubmit the inflight ledger, and
    every accepted future must still resolve — the serve-once argument
    in serve/ipc.py's module docstring makes the resubmit safe."""
    reg = metrics.Registry()
    with ServeFleet(replicas=1, process=True, max_wait_ms=2,
                    max_batch=4, heartbeat_s=0.1,
                    registry=reg) as fleet:
        c = _circ()
        states = _states(8, seed=9)
        fleet.submit(c, state=states[0]).result(timeout=120)  # warm
        futs = [fleet.submit(c, state=states[i]) for i in range(8)]
        os.kill(fleet._engines[0].worker_pid(), signal.SIGKILL)
        outs = [f.result(timeout=180) for f in futs]
        assert len(outs) == 8
        snap = reg.snapshot()["counters"]
        assert snap.get("ipc_worker_losses", 0) >= 1
        assert snap.get("ipc_worker_respawns", 0) >= 1
        assert snap.get("ipc_resubmits", 0) >= 1


def test_budget_exhaustion_fails_typed_and_fleet_requeues():
    """A proxy whose respawn budget is spent goes FAILED and resolves
    its leftovers with the requeue-typed RejectedError — so the FLEET
    failover contract (PR 11) moves them to a survivor unchanged."""
    reg = metrics.Registry()
    with ServeFleet(replicas=2, process=True, max_wait_ms=600_000,
                    max_batch=64, max_queue=32, restart_max=0,
                    heartbeat_s=0.1, registry=reg) as fleet:
        c = _circ()
        states = _states(6, seed=13)
        futs = [fleet.submit(c, state=states[i]) for i in range(6)]
        # both replicas hold queued work (huge max_wait); kill the one
        # with pending requests — restart_max=0 means FAILED, not respawn
        victim = max(range(2),
                     key=lambda i: fleet._engines[i]._pending)
        os.kill(fleet._engines[victim].worker_pid(), signal.SIGKILL)
        fleet.drain(timeout_s=180)
        outs = [f.result(timeout=120) for f in futs]
        assert len(outs) == 6
        assert fleet._engines[victim].state == "failed"
        snap = reg.snapshot()["counters"]
        assert snap.get("fleet_requeued_requests", 0) >= 1
        # a FAILED proxy rejects new submits synchronously and typed
        with pytest.raises(RejectedError, match="respawn budget"):
            fleet._engines[victim].submit(c, state=states[0])


def test_proxy_rejects_durable_mesh():
    with pytest.raises(ValueError, match="durable_mesh"):
        ReplicaProxy(registry=metrics.Registry(), durable_mesh=object())


# ---------------------------------------------------------------------------
# fault sites: fleet.spawn / ipc.send / ipc.recv (the QL009 arming)
# ---------------------------------------------------------------------------


def test_fleet_spawn_fault_makes_boot_loud():
    """An armed fleet.spawn fault fires on the REAL spawn path: the
    constructor raises it instead of booting a half-dead fleet."""
    plan = FaultPlan().inject(
        "fleet.spawn", error=RuntimeError("no capacity"), times=1)
    with faults.active(plan):
        with pytest.raises(RuntimeError, match="no capacity"):
            ServeFleet(replicas=1, process=True,
                       registry=metrics.Registry())
    assert plan.fired("fleet.spawn") == 1


def test_ipc_send_and_recv_faults_trigger_loss_recovery():
    """Armed ipc.send / ipc.recv faults fire on the real framed paths
    and are handled as transport losses: the proxy respawns, resubmits,
    and the caller's future still resolves — injected chaos and a real
    flaky pipe take the same recovery road."""
    c = _circ()
    states = _states(4, seed=17)
    reg = metrics.Registry()
    plan = (FaultPlan()
            .inject("ipc.send", error=OSError("pipe torn"), times=1,
                    match=lambda ctx: ctx.get("type") == "submit")
            .inject("ipc.recv", error=OSError("frame poisoned"),
                    times=1,
                    match=lambda ctx: ctx.get("type") == "result"))
    with ServeFleet(replicas=1, process=True, max_wait_ms=2,
                    max_batch=4, heartbeat_s=0.1,
                    registry=reg) as fleet:
        fleet.submit(c, state=states[0]).result(timeout=120)   # warm
        with faults.active(plan):
            outs = [fleet.submit(c, state=states[i]).result(timeout=180)
                    for i in range(4)]
        assert len(outs) == 4
    assert plan.fired("ipc.send") == 1
    assert plan.fired("ipc.recv") == 1
    assert reg.snapshot()["counters"].get("ipc_worker_losses", 0) >= 2


# ---------------------------------------------------------------------------
# concurrent plan-cache warmup across worker processes
# ---------------------------------------------------------------------------

_WARM_SNIPPET = r"""
import json, sys
import numpy as np
from quest_tpu.circuit import Circuit
from quest_tpu import plan as P

n = int(sys.argv[1])
c = Circuit(n)
c.h(0); c.cnot(0, 1)
for q in range(n):
    c.rz(q, 0.1 * (q + 1))
for batch in (1, 2):
    P.autotune(c, state_kind="pure", dtype=np.float32, batch=batch)
print(json.dumps(P.cache_stats()))
"""


def test_concurrent_plan_cache_warmup_is_atomic(tmp_path, monkeypatch):
    """N processes warm the SAME plan-cache dir simultaneously (the
    process fleet's cold boot): every entry lands whole (QL008's
    tmp+rename discipline — concurrent writers may both pay the
    search, but no reader ever sees a torn file), and a second wave
    over the warm dir is all LOADs: zero searches in every process."""
    # the parent validates entries via load_plan too, so it must read
    # the same dir the children write
    monkeypatch.setenv("QUEST_PLAN_CACHE_DIR", str(tmp_path))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"

    def wave():
        procs = [subprocess.Popen(
            [sys.executable, "-c", _WARM_SNIPPET, "5"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            env=env, text=True) for _ in range(3)]
        stats = []
        for p in procs:
            out, err = p.communicate(timeout=300)
            assert p.returncode == 0, err
            stats.append(json.loads(out.strip().splitlines()[-1]))
        return stats

    cold = wave()
    assert all(s["searches"] >= 1 for s in cold), cold
    entries = [f for f in os.listdir(tmp_path) if f.startswith("plan-")]
    assert entries, "no plan-cache entries persisted"
    # no torn writes: every persisted entry parses and loads
    from quest_tpu import plan as P
    for f in entries:
        assert not f.endswith(".json") or P.load_plan(
            f[len("plan-"):-len(".json")]) is not None, f
    assert not any(".tmp." in f for f in os.listdir(tmp_path))
    warm = wave()
    assert all(s["searches"] == 0 for s in warm), warm
    assert all(s["hits"] >= 1 for s in warm), warm


# ---------------------------------------------------------------------------
# the autoscaler control loop (deterministic ticks, thread fleet)
# ---------------------------------------------------------------------------


class _FleetStub:
    """A fleet-shaped stub: the autoscaler's tick is a pure function of
    stats()/counters, so its hysteresis/cooldown/bounds logic is
    testable without booting a single process."""

    def __init__(self, pressure=0.0, replicas=1):
        self.registry = metrics.Registry()
        self.pressure = pressure
        self._n = replicas
        self.ups = 0
        self.downs = 0

    @property
    def replicas(self):
        return self._n

    def stats(self):
        return {"pressure": self.pressure,
                "replicas": [{"retired": False}] * self._n}

    def add_replica(self):
        self._n += 1
        self.ups += 1
        return self._n - 1

    def remove_replica(self, timeout_s=None):
        self._n -= 1
        self.downs += 1
        return 0


def _auto(fleet, **kw):
    from quest_tpu.serve import Autoscaler
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 4)
    return Autoscaler(fleet, **kw)


def test_autoscaler_hysteresis_needs_consecutive_hot_ticks():
    f = _FleetStub(pressure=0.9)
    a = _auto(f, up_ticks=3, cooldown_ticks=0)
    assert a.tick() is None and a.tick() is None
    assert a.tick() == "up" and f.ups == 1
    # a neutral tick resets the streak
    f.pressure = 0.5
    a.tick()
    f.pressure = 0.9
    assert a.tick() is None and a.tick() is None
    assert a.tick() == "up"


def test_autoscaler_shed_delta_counts_as_hot():
    f = _FleetStub(pressure=0.1)
    a = _auto(f, up_ticks=1, cooldown_ticks=0)
    f.registry.counter("shed_requests").inc()
    assert a.tick() == "up"        # shedding = lost work, scale NOW


def test_autoscaler_cooldown_blocks_thrash():
    f = _FleetStub(pressure=0.9)
    a = _auto(f, up_ticks=1, cooldown_ticks=2)
    assert a.tick() == "up"
    assert a.tick() is None and a.tick() is None   # cooling
    assert a.tick() == "up"


def test_autoscaler_respects_bounds():
    f = _FleetStub(pressure=0.9, replicas=4)
    a = _auto(f, up_ticks=1, cooldown_ticks=0, max_replicas=4)
    assert a.tick() is None and f.ups == 0          # at max: hold
    f.pressure = 0.0
    f._n = 1
    a2 = _auto(f, down_ticks=1, cooldown_ticks=0, min_replicas=1)
    assert a2.tick() is None and f.downs == 0       # at min: hold
    with pytest.raises(ValueError, match="non-empty range"):
        _auto(f, min_replicas=3, max_replicas=2)
    with pytest.raises(ValueError, match="low_water"):
        _auto(f, low_water=0.9, high_water=0.5)


def test_autoscaler_scales_down_after_sustained_calm():
    f = _FleetStub(pressure=0.0, replicas=3)
    a = _auto(f, down_ticks=3, cooldown_ticks=0)
    assert [a.tick() for _ in range(3)] == [None, None, "down"]
    assert f.downs == 1


def test_fleet_add_remove_replica_thread_mode():
    """Elasticity on the cheap thread fleet: add_replica routes new
    work, remove_replica tombstones (never pops — ticket indices must
    not dangle) and refuses to drop the last live replica."""
    c = _circ()
    states = _states(4, seed=19)
    with ServeFleet(replicas=1, max_wait_ms=2, max_batch=4,
                    registry=metrics.Registry()) as fleet:
        assert fleet.replicas == 1
        fleet.add_replica()
        assert fleet.replicas == 2
        futs = [fleet.submit(c, state=states[i]) for i in range(4)]
        for f in futs:
            f.result(timeout=120)
        fleet.remove_replica(timeout_s=60)
        assert fleet.replicas == 1
        assert len(fleet._engines) == 2         # tombstoned, not popped
        fleet.submit(c, state=states[0]).result(timeout=120)
        with pytest.raises(ValueError, match="last live replica"):
            fleet.remove_replica(timeout_s=5)


def test_scale_down_rolls_back_instead_of_losing_requests():
    """A scale-down whose drain window expires with requests still
    incomplete must ROLL BACK the retirement (typed TimeoutError, no
    tombstone) instead of closing the replica under them — the
    never-shed-by-scale-down contract the autoscaler's short drain
    window leans on. Every queued future still resolves."""
    ca = _circ()
    cb = Circuit(N).h(1).cnot(1, 2).rz(0, 0.3)
    states = _states(6, seed=23)
    with ServeFleet(replicas=2, max_wait_ms=600_000, max_batch=64,
                    max_queue=32,
                    registry=metrics.Registry()) as fleet:
        # two program families => affinity parks work on BOTH replicas,
        # so the emptiest victim still has an undrained backlog
        futs = [fleet.submit(ca if i % 2 == 0 else cb, state=states[i])
                for i in range(6)]
        # a zero-width drain window with queued work raises
        # deterministically — no race against a warm compile cache
        with pytest.raises(TimeoutError, match="rolled back"):
            fleet.remove_replica(timeout_s=0.0)
        assert fleet.replicas == 2      # retirement undone
        assert not [r for r in fleet.stats()["replicas"]
                    if r["retired"]]
        fleet.drain(timeout_s=300)
        for f in futs:
            f.result(timeout=120)       # nothing was lost


def test_process_fleet_refused_on_a_tpu_backend(monkeypatch):
    """The parent holds the chip, so process replicas that inherit its
    environment could not load it: construction fails loudly before any
    worker starts (thread replicas stay the TPU path until ROADMAP R1)."""
    import jax

    from quest_tpu.serve.fleet import ServeFleet
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="holds the chip"):
        ServeFleet(replicas=1, process=True)
