"""Fault-injection framework + supervised serving (ISSUE 7).

Proves each recovery path END-TO-END through the deterministic fault
sites (docs/RESILIENCE.md): an injected worker crash restarts the
worker with queued futures completing bit-identical to an uninjected
run; injected compile failures open the per-program breaker and
requests complete on the degraded engine, then a half-open probe
restores the fused path; a poisoned rider in a coalesced batch is
binary-split out with its own typed error while its batch-mates still
get results; an exhausted restart budget fails LOUDLY (typed errors on
every future, RejectedError from submit) instead of stranding anyone;
and an empty FaultPlan costs nothing — the warmed mixed stream retraces
NOTHING with the sites armed-but-silent (the zero-cost acceptance
gate). Satellites ride along: the env.py backend-probe retry contract,
native.py's warn-once degrade, and the FaultPlan/QUEST_FAULT_PLAN
grammar.
"""

import math
import threading
import time

import numpy as np
import pytest

import jax

from quest_tpu.circuit import Circuit
from quest_tpu.resilience import Breaker, FaultPlan, InjectedFault, Supervisor
from quest_tpu.resilience import faults
from quest_tpu.serve import RejectedError, ServeEngine, metrics, warmup

pytestmark = pytest.mark.dtype_agnostic

N = 6


def _circuit_a(n: int = N) -> Circuit:
    c = Circuit(n)
    for q in range(n):
        c.h(q)
    return c.cnot(0, 1).rz(2, 0.25).cz(1, 3).rx(0, 0.5)


def _circuit_b(n: int = N) -> Circuit:
    c = Circuit(n).h(0)
    for q in range(n - 1):
        c.cnot(q, q + 1)
    return c.t(1).ry(3, 0.7)


def _noisy_circuit(n: int = 4) -> Circuit:
    c = Circuit(n).h(0).cnot(0, 1)
    c.depolarising(0, 0.1).damping(1, 0.2)
    return c.ry(2, 0.3).dephasing(2, 0.15)


def _random_states(b: int, n: int = N, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    s = rng.standard_normal((b, 2, 1 << n)).astype(np.float32)
    return s / np.sqrt((s ** 2).sum(axis=(1, 2), keepdims=True))


def _engine(**kw):
    kw.setdefault("registry", metrics.Registry())
    kw.setdefault("backoff_base_s", 0.0)     # tests never sleep restarts
    return ServeEngine(**kw)


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    """Every test leaves the process-wide fault plan the way it found
    it (a leaked plan would poison unrelated suites)."""
    before = faults.current()
    yield
    faults.install(before)


# ---------------------------------------------------------------------------
# FaultPlan mechanics
# ---------------------------------------------------------------------------


def test_fault_plan_is_deterministic():
    plan = FaultPlan()
    plan.inject("serve.dispatch", error=RuntimeError("boom"), after_n=2,
                every_n=2, times=2)
    fired = []
    for _ in range(10):
        try:
            plan.check("serve.dispatch", {})
            fired.append(0)
        except RuntimeError:
            fired.append(1)
    # skip 2, then every 2nd eligible hit, capped at 2 fires
    assert fired == [0, 0, 0, 1, 0, 1, 0, 0, 0, 0]
    assert plan.fired("serve.dispatch") == 2


def test_fault_plan_probabilistic_replay_is_deterministic():
    def fires(seed):
        plan = FaultPlan().inject("serve.demux", p=0.5, seed=seed)
        out = []
        for _ in range(32):
            try:
                plan.check("serve.demux", {})
                out.append(0)
            except InjectedFault:
                out.append(1)
        return out

    assert fires(3) == fires(3)              # same seed, same sequence
    assert fires(3) != fires(4)              # seeded, not constant
    assert 0 < sum(fires(3)) < 32


def test_fault_plan_match_gates_the_hit_count():
    plan = FaultPlan()
    plan.inject("serve.dispatch", match=lambda ctx: ctx.get("tag") == "bad")
    plan.check("serve.dispatch", {"tag": "good"})     # not even a hit
    with pytest.raises(InjectedFault):
        plan.check("serve.dispatch", {"tag": "bad"})


def test_fault_plan_validates_loudly():
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultPlan().inject("serve.not_a_site")
    with pytest.raises(ValueError, match="after_n"):
        FaultPlan().inject("serve.demux", after_n=-1)
    with pytest.raises(ValueError, match="p must be"):
        FaultPlan().inject("serve.demux", p=1.5)


def test_parse_plan_grammar_and_knob():
    plan = faults.parse_plan(
        "serve.dispatch:error=RuntimeError:after=2:times=1;"
        "serve.worker_loop:every=3:seed=7")
    assert not plan.empty
    for bad in ("serve.nope", "serve.demux:after=x",
                "serve.demux:error=NotAnError", "serve.demux:wat=1",
                "serve.demux:p=maybe"):
        with pytest.raises(ValueError):
            faults.parse_plan(bad)
    # the registered QUEST_FAULT_PLAN parser IS parse_plan
    from quest_tpu.env import KNOBS
    k = KNOBS["QUEST_FAULT_PLAN"]
    assert k.scope == "runtime" and k.layer == "serve"
    assert isinstance(k.parse("serve.demux:times=1"), FaultPlan)
    with pytest.raises(ValueError):
        k.parse(k.malformed)


def test_empty_plan_keeps_the_flag_off():
    with faults.active(FaultPlan()):
        assert faults.ACTIVE is False        # zero-cost guard stays cold
    plan = FaultPlan().inject("serve.demux", times=1)
    with faults.active(plan):
        assert faults.ACTIVE is True
    assert faults.ACTIVE is False            # scoped install restores


# ---------------------------------------------------------------------------
# supervisor + breaker units
# ---------------------------------------------------------------------------


def test_supervisor_backoff_and_budget():
    sup = Supervisor(3, base_s=0.1, cap_s=0.5, jitter_frac=0.0)
    assert sup.next_backoff() == pytest.approx(0.1)
    assert sup.next_backoff() == pytest.approx(0.2)
    assert sup.next_backoff() == pytest.approx(0.4)
    assert sup.next_backoff() is None        # budget exhausted
    sup.record_success()                     # health refills the budget
    assert sup.next_backoff() == pytest.approx(0.1)
    jittered = Supervisor(1, base_s=0.1, jitter_frac=0.5, seed=1)
    d = jittered.next_backoff()
    assert 0.1 <= d <= 0.15


def test_breaker_state_machine():
    now = [0.0]
    seen = []
    br = Breaker(2, cooldown_s=1.0, on_transition=lambda o, n: seen.append(
        (o, n)), clock=lambda: now[0])
    assert br.allow_primary()
    br.record_failure()
    assert br.state == "closed" and br.allow_primary()
    br.record_failure()                      # threshold -> OPEN
    assert br.state == "open" and not br.allow_primary()
    now[0] = 1.5                             # cooldown elapsed
    assert br.allow_primary()                # the half-open probe
    assert br.state == "half_open"
    br.record_failure()                      # probe failed -> OPEN again
    assert br.state == "open" and not br.allow_primary()
    now[0] = 3.0
    assert br.allow_primary()
    br.record_success()                      # probe healthy -> CLOSED
    assert br.state == "closed" and br.failures == 0
    assert seen == [("closed", "open"), ("open", "half_open"),
                    ("half_open", "open"), ("open", "half_open"),
                    ("half_open", "closed")]


# ---------------------------------------------------------------------------
# supervised restart (the worker_loop site)
# ---------------------------------------------------------------------------


def test_worker_crash_restarts_and_queued_futures_complete_bit_identical():
    """THE restart acceptance gate: the worker crashes with popped-but-
    undispatched batches in hand; the supervisor requeues them in order,
    restarts the worker, and every queued future completes EXACTLY as
    in an uninjected run (same bucket program, same results)."""
    c = _circuit_a()
    states = _random_states(4, seed=11)
    with _engine(max_wait_ms=600_000, max_batch=8) as ref:
        futs = [ref.submit(c, state=s) for s in states]
        ref.drain(timeout_s=120)
        want = [np.asarray(f.result(timeout=60)) for f in futs]

    plan = FaultPlan().inject("serve.worker_loop", times=1,
                              match=lambda ctx: ctx["phase"] == "popped")
    reg = metrics.Registry()
    with faults.active(plan):
        with _engine(max_wait_ms=600_000, max_batch=8,
                     registry=reg) as eng:
            futs = [eng.submit(c, state=s) for s in states]
            eng.drain(timeout_s=120)
            got = [np.asarray(f.result(timeout=60)) for f in futs]
    assert plan.fired("serve.worker_loop") == 1
    snap = reg.snapshot()["counters"]
    assert snap["serve_worker_restarts"] == 1
    assert snap["serve_faults_injected"] == 1
    assert snap["serve_requests_served"] == 4
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_worker_crash_at_idle_is_transparent():
    """A crash with nothing popped (phase=idle) restarts and the engine
    keeps serving — clients never notice."""
    c = _circuit_a()
    s = _random_states(1, seed=13)[0]
    want = np.asarray(c.compiled_batched(1, donate=False)(s[None]))[0]
    plan = FaultPlan().inject("serve.worker_loop", times=1,
                              match=lambda ctx: ctx["phase"] == "idle")
    reg = metrics.Registry()
    with faults.active(plan):
        with _engine(max_wait_ms=5, registry=reg) as eng:
            out = np.asarray(eng.submit(c, state=s).result(timeout=120))
    np.testing.assert_array_equal(out, want)
    assert reg.counter("serve_worker_restarts").value == 1


def test_restart_budget_exhausted_fails_loudly():
    """Budget gone => FAILED: every pending future resolves with a
    typed RejectedError (never hangs), submit rejects with the cause,
    drain returns deterministically."""
    c = _circuit_a()
    states = _random_states(2, seed=17)
    plan = FaultPlan().inject(
        "serve.worker_loop", error=RuntimeError("hardware gone"),
        match=lambda ctx: ctx["phase"] == "popped")
    reg = metrics.Registry()
    with faults.active(plan):
        eng = _engine(max_wait_ms=600_000, max_batch=8, restart_max=2,
                      registry=reg)
        try:
            futs = [eng.submit(c, state=s) for s in states]
            eng.drain(timeout_s=120)         # returns, never hangs
            for f in futs:
                with pytest.raises(RejectedError, match="FAILED"):
                    f.result(timeout=60)
            assert eng.state == "failed"
            assert reg.counter("serve_worker_restarts").value == 2
            with pytest.raises(RejectedError, match="hardware gone"):
                eng.submit(c, state=states[0])
            with pytest.raises(RejectedError):
                warmup(eng, [c], buckets=[1])
        finally:
            eng.close(timeout_s=60)


# ---------------------------------------------------------------------------
# breaker + degradation ladder (the compile site)
# ---------------------------------------------------------------------------


def test_compile_failure_opens_breaker_then_half_open_probe_recovers():
    """THE breaker acceptance gate: repeated primary compile failures
    open the program's breaker; its requests keep completing on the
    degraded (banded) engine; after the cooldown the half-open probe
    finds the primary healthy and restores fused service."""
    c = _circuit_a()
    states = _random_states(6, seed=19)
    want = [np.asarray(c.compiled_batched(1, donate=False)(s[None]))[0]
            for s in states]
    plan = FaultPlan().inject("serve.compile",
                              error=RuntimeError("mosaic fell over"),
                              times=2)
    reg = metrics.Registry()
    with faults.active(plan):
        with _engine(max_wait_ms=0, max_batch=8, breaker_threshold=2,
                     breaker_cooldown_s=0.2, registry=reg) as eng:
            outs = []
            # r1: compile fails (breaker 1/2) -> degraded, completes
            # r2: compile fails (2/2) -> breaker OPENS -> degraded
            # r3: breaker open, cooldown not elapsed -> degraded without
            #     touching the primary at all
            for s in states[:3]:
                outs.append(np.asarray(
                    eng.submit(c, state=s).result(timeout=120)))
            snap = reg.snapshot()
            assert snap["counters"]["serve_breaker_opens"] == 1
            assert snap["counters"]["serve_degraded_dispatches"] == 3
            assert snap["counters"]["serve_faults_injected"] == 2
            assert snap["gauges"]["serve_breakers_open"] == 1.0
            time.sleep(0.25)                 # past the cooldown
            # r4 is the half-open probe: the primary compiles now (the
            # plan is exhausted), so the breaker CLOSES and fused
            # service resumes for r5/r6
            for s in states[3:]:
                outs.append(np.asarray(
                    eng.submit(c, state=s).result(timeout=120)))
            snap = reg.snapshot()
            assert snap["counters"]["serve_breaker_probes"] == 1
            assert snap["counters"]["serve_breaker_closes"] == 1
            assert snap["counters"]["serve_degraded_dispatches"] == 3
            assert snap["gauges"]["serve_breakers_open"] == 0.0
    # every rider got a correct result throughout (degraded within the
    # documented engine-parity eps — identical banded math at this size)
    for got, w in zip(outs, want):
        np.testing.assert_allclose(got, w, rtol=1e-5, atol=1e-6)


def test_breaker_is_per_program_key():
    """One circuit's broken program must not degrade ANOTHER circuit's
    dispatches: breakers key on program_key."""
    ca, cb = _circuit_a(), _circuit_b()
    sa, sb = _random_states(2, seed=23)
    plan = FaultPlan().inject(
        "serve.compile", error=RuntimeError("m"),
        # ctx["program"] is the queue's program key; its second field
        # is the circuit object itself (Circuit.program_key)
        match=lambda ctx: ctx["program"][1] is ca, times=5)
    reg = metrics.Registry()
    with faults.active(plan):
        with _engine(max_wait_ms=0, max_batch=8, breaker_threshold=1,
                     registry=reg) as eng:
            eng.submit(ca, state=sa).result(timeout=120)
            eng.submit(cb, state=sb).result(timeout=120)
    snap = reg.snapshot()
    assert snap["counters"]["serve_breaker_opens"] == 1
    assert snap["counters"]["serve_degraded_dispatches"] == 1
    assert snap["counters"]["serve_requests_served"] == 2


# ---------------------------------------------------------------------------
# poisoned-batch isolation (the dispatch site + the splitter)
# ---------------------------------------------------------------------------


def test_one_poisoned_rider_in_eight_is_isolated():
    """THE splitter acceptance gate: a coalesced batch of 8 where ONE
    request poisons any launch containing it — 7 riders succeed, the
    poisoned future gets the typed error, and the poison wastes at most
    ceil(log2(8))+1 failing launches (the split-tree path containing
    it)."""
    c = _circuit_a()
    states = _random_states(8, seed=29)
    want = [np.asarray(c.compiled_batched(1, donate=False)(s[None]))[0]
            for s in states]
    bad = {}
    plan = FaultPlan().inject(
        "serve.dispatch", error=ValueError("poisoned request"),
        match=lambda ctx: any(r.future is bad.get("f")
                              for r in ctx["reqs"]))
    reg = metrics.Registry()
    with faults.active(plan):
        with _engine(max_wait_ms=600_000, max_batch=8,
                     registry=reg) as eng:
            futs = [eng.submit(c, state=s) for s in states]
            bad["f"] = futs[5]
            eng.drain(timeout_s=300)
    with pytest.raises(ValueError, match="poisoned request"):
        futs[5].result(timeout=60)
    for i, f in enumerate(futs):
        if i == 5:
            continue
        np.testing.assert_allclose(np.asarray(f.result(timeout=60)),
                                   want[i], rtol=1e-5, atol=1e-6)
    snap = reg.snapshot()["counters"]
    budget = math.ceil(math.log2(8)) + 1
    assert snap["serve_launch_failures"] <= budget, snap
    assert snap["serve_batches_split"] >= 1
    assert snap["serve_requests_served"] == 7
    assert snap["serve_requests_failed"] == 1


def test_uniform_launch_failure_fails_every_rider_with_the_error():
    """When EVERY sub-batch fails (engine-wide, not one poisoned rider)
    the splitter bottoms out and each future gets the typed error —
    bounded work, nobody hangs."""
    c = _circuit_a()
    states = _random_states(4, seed=31)
    plan = FaultPlan().inject("serve.dispatch",
                              error=RuntimeError("device lost"))
    reg = metrics.Registry()
    with faults.active(plan):
        with _engine(max_wait_ms=600_000, max_batch=4,
                     registry=reg) as eng:
            futs = [eng.submit(c, state=s) for s in states]
            eng.drain(timeout_s=300)
    for f in futs:
        with pytest.raises(RuntimeError, match="device lost"):
            f.result(timeout=60)
    assert reg.counter("serve_requests_failed").value == 4
    assert reg.counter("serve_requests_served").value == 0


def test_demux_error_fails_only_its_own_request():
    """Satellite regression (the engine.py:345 whole-batch failure):
    one rider's bad observable raising during demux fails ONLY that
    future — its three batch-mates still get their planes, from the
    same single launch (no split: the launch itself succeeded)."""
    c = _circuit_a()
    states = _random_states(4, seed=37)
    fn = c.compiled_batched(4, donate=False)
    want = [np.asarray(fn(s[None]))[0] for s in states]

    def bad_observable(planes_b):
        raise ValueError("observable shape mismatch")

    reg = metrics.Registry()
    with _engine(max_wait_ms=600_000, max_batch=4, registry=reg) as eng:
        futs = [eng.submit(c, state=states[0],
                           observable=bad_observable)]
        futs += [eng.submit(c, state=s) for s in states[1:]]
        eng.drain(timeout_s=120)
    with pytest.raises(ValueError, match="observable shape"):
        futs[0].result(timeout=60)
    for f, w in zip(futs[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(f.result(timeout=60)), w)
    snap = reg.snapshot()["counters"]
    assert snap["serve_batches_dispatched"] == 1     # never split
    assert snap["serve_demux_failures"] == 1
    assert snap["serve_requests_served"] == 3


def test_traj_demux_error_is_isolated_too():
    from quest_tpu import trajectories as T
    c = _noisy_circuit()
    k1, k2 = jax.random.key(3), jax.random.key(5)
    want = T.run_batched(c, k2, 3)

    def bad_observable(planes_b):
        raise ValueError("bad traj observable")

    with _engine(max_wait_ms=10_000, max_batch=8) as eng:
        fbad = eng.submit(c, shots=3, key=k1, observable=bad_observable)
        fgood = eng.submit(c, shots=3, key=k2)
        eng.drain(timeout_s=300)
    with pytest.raises(ValueError, match="bad traj observable"):
        fbad.result(timeout=60)
    p, d = fgood.result(timeout=60)
    np.testing.assert_array_equal(p, np.asarray(want[0]))
    np.testing.assert_array_equal(d, np.asarray(want[1]))


# ---------------------------------------------------------------------------
# zero-cost acceptance: empty plan, armed-but-silent plan
# ---------------------------------------------------------------------------


def test_empty_fault_plan_adds_zero_retraces_to_warmed_stream(
        compile_auditor):
    """THE zero-cost acceptance gate: with an EMPTY FaultPlan installed
    (and then with sites armed but never firing), the warmed PR-5 mixed
    stream retraces NOTHING — every fault check is host-side, outside
    all traced code."""
    ca, cb = _circuit_a(), _circuit_b()
    states = _random_states(32, seed=41)
    with _engine(max_wait_ms=10_000, max_batch=4) as eng:
        warmup(eng, [ca, cb], buckets=[4])

        def stream():
            futs = [eng.submit(ca if i % 2 == 0 else cb, state=states[i])
                    for i in range(32)]
            eng.drain(timeout_s=300)
            for f in futs:
                f.result(timeout=300)

        stream()                          # warm the demux ops
        with faults.active(FaultPlan()):
            with compile_auditor as aud:
                stream()
        aud.assert_no_retrace("warmed mixed stream, empty fault plan")
        # armed-but-silent: the checks RUN on every site and still
        # trace nothing (after_n pushes the first fire past any hit
        # count this stream can reach)
        armed = FaultPlan()
        for site in ("serve.worker_loop", "serve.compile",
                     "serve.device_put", "serve.dispatch", "serve.demux"):
            armed.inject(site, after_n=10 ** 9)
        with faults.active(armed):
            assert faults.ACTIVE
            with compile_auditor as aud2:
                stream()
        aud2.assert_no_retrace("warmed mixed stream, armed-silent plan")


# ---------------------------------------------------------------------------
# the sharded dispatch site
# ---------------------------------------------------------------------------


def test_sharded_dispatch_site_fires():
    import quest_tpu as qt
    from quest_tpu.parallel.sharded import apply_circuit_sharded

    env = qt.create_quest_env()
    q = qt.create_qureg(N, env=env)
    ops = Circuit(N).h(0).cnot(0, 1).ops
    plan = FaultPlan().inject("sharded.dispatch", times=1)
    with faults.active(plan):
        with pytest.raises(InjectedFault):
            apply_circuit_sharded(q, ops, env.mesh, donate=False)
        # the plan is exhausted: the same call now dispatches normally
        out = apply_circuit_sharded(q, ops, env.mesh, donate=False)
    assert out.num_qubits == N
    assert plan.fired("sharded.dispatch") == 1


# ---------------------------------------------------------------------------
# satellites: native warn-once
# ---------------------------------------------------------------------------


def test_native_degrade_warns_once_and_keeps_working(monkeypatch, capsys):
    """native.py's degrade-to-Python path: with the shared library
    absent (and the build failing), available() turns False with ONE
    stderr warning — repeated probes stay quiet, and the pure-Python
    callers keep working."""
    from quest_tpu import native

    monkeypatch.setattr(native, "_LIB_PATH", "/nonexistent/libq.so")
    monkeypatch.setattr(native, "_build", lambda: False)
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_lib_tried", False)
    monkeypatch.setattr(native, "_degrade_warned", False)
    assert native.available() is False
    assert native.available() is False       # cached degrade, no rebuild
    err = capsys.readouterr().err
    assert err.count("native host library unavailable") == 1
    assert native.init_by_array([1, 2]) is False   # callers degrade
    monkeypatch.setattr(native, "_lib_tried", False)
    assert native.available() is False       # re-probe still warns once
    assert "unavailable" not in capsys.readouterr().err


def test_serve_stats_renders_resilience_section():
    """Satellite: scripts/serve_stats.py surfaces the resilience
    counters/gauges in their own section (healthy = all zero), with
    absent metrics defaulting to 0."""
    import importlib.util
    import io
    import os
    spec = importlib.util.spec_from_file_location(
        "serve_stats", os.path.join(os.path.dirname(__file__), "..",
                                    "scripts", "serve_stats.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    snap = {"counters": {"serve_requests_served": 3,
                         "serve_worker_restarts": 2},
            "gauges": {"serve_breakers_open": 1.0},
            "histograms": {}}
    buf = io.StringIO()
    mod.render(snap, out=buf)
    text = buf.getvalue()
    assert "resilience" in text
    assert "serve_worker_restarts" in text
    assert "serve_breakers_open" in text
    assert "serve_batches_split" in text     # absent -> rendered as 0


# ---------------------------------------------------------------------------
# chaos soak (CI's slow lane): random plan over a mixed stream
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_chaos_soak_every_future_resolves_and_engine_never_hangs():
    """A randomized-but-seeded FaultPlan over a 200-request mixed
    stream: every future must RESOLVE (result or typed error), the
    engine must end healthy or FAILED — never hung (the bounded drain
    below is the hang detector)."""
    ca, cb, cn = _circuit_a(), _circuit_b(), _noisy_circuit()
    states = _random_states(200, seed=43)
    plan = FaultPlan()
    plan.inject("serve.worker_loop", every_n=50, times=3)
    plan.inject("serve.compile", error=RuntimeError("mosaic"),
                every_n=7, times=10)
    plan.inject("serve.dispatch", every_n=11, times=8)
    plan.inject("serve.device_put", every_n=23, times=4)
    plan.inject("serve.demux", p=0.02, seed=5)
    reg = metrics.Registry()
    with faults.active(plan):
        eng = _engine(max_wait_ms=2, max_batch=8, restart_max=10,
                      breaker_threshold=3, breaker_cooldown_s=0.05,
                      registry=reg)
        try:
            futs = []
            for i in range(200):
                try:
                    if i % 5 == 4:
                        futs.append(eng.submit(
                            cn, shots=1 + i % 4, key=jax.random.key(i)))
                    else:
                        futs.append(eng.submit(
                            ca if i % 2 == 0 else cb, state=states[i]))
                except RejectedError:
                    pass                     # FAILED mid-stream is legal
            eng.drain(timeout_s=600)         # TimeoutError here == hung
            for f in futs:
                assert f.done() or f.exception(timeout=60) is not None \
                    or f.result(timeout=0) is not None
            assert eng.state in ("running", "failed")
            resolved = sum(1 for f in futs if f.done())
            assert resolved == len(futs)
        finally:
            eng.close(timeout_s=120)
    snap = reg.snapshot()["counters"]
    assert snap.get("serve_faults_injected", 0) > 0, snap


# ---------------------------------------------------------------------------
# submit under concurrency while a restart is happening
# ---------------------------------------------------------------------------


def test_submits_racing_a_restart_all_complete():
    """Client threads submitting THROUGH a worker crash+restart: every
    future resolves with the right result (queued work survives, new
    work lands in the recovered queues)."""
    c = _circuit_a()
    states = _random_states(12, seed=47)
    fn = c.compiled_batched(1, donate=False)
    want = [np.asarray(fn(s[None]))[0] for s in states]
    plan = FaultPlan().inject("serve.worker_loop", times=2,
                              match=lambda ctx: ctx["phase"] == "popped")
    results: dict = {}
    with faults.active(plan):
        with _engine(max_wait_ms=1, max_batch=4) as eng:
            def client(i):
                results[i] = np.asarray(
                    eng.submit(c, state=states[i]).result(timeout=300))
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(states))]
            for t in threads:
                t.start()
                time.sleep(0.002)
            for t in threads:
                t.join(timeout=300)
    for i, w in enumerate(want):
        np.testing.assert_allclose(results[i], w, rtol=1e-5, atol=1e-6)
