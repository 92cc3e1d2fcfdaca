"""quest_tpu.serve (ISSUE 6): the continuous-batching execution service.

Pins the serving contracts from docs/SERVING.md: demux correctness
(N concurrent submits == N sequential library calls, bit-identical),
bucket coalescing under the CompileAuditor (a warmed mixed stream
retraces NOTHING — one compiled program per bucket), loud overflow
rejection, deadline expiry strictly BEFORE dispatch, cancellation,
drain-flushes-partial-bucket, the metrics snapshot schema, and the
satellite fixes that ride along: `measurement.sample` shot-count
bucketing (one compiled program across shots=100/120/128) and
`enable_compile_cache`'s hit/miss tallies as structured counters.
"""

import threading
import time

import numpy as np
import pytest

import jax

from quest_tpu.circuit import Circuit
from quest_tpu.serve import (DeadlineExceeded, RejectedError, ServeEngine,
                             default_buckets, metrics, warmup)

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
    return ServeEngine(**kw)


# ---------------------------------------------------------------------------
# demux correctness
# ---------------------------------------------------------------------------


def test_apply_demux_matches_sequential_library_calls():
    """N concurrent submits, coalesced into one shared launch, resolve
    to exactly what N sequential library calls through the same bucket
    program produce — the results demux to the right futures,
    bit-identical (padding states are zero and every engine op is a
    linear map, so a state's output never depends on its batch
    neighbours; distinct BUCKETS are distinct XLA programs and may
    differ at the ULP level, which is why the sequential reference
    rides the same bucket)."""
    c = _circuit_a()
    states = _random_states(8)
    fn = c.compiled_batched(8, donate=False)
    seq = [np.asarray(fn(s[None]))[0] for s in states]
    with _engine(max_wait_ms=10_000, max_batch=8) as eng:
        futs = [eng.submit(c, state=s) for s in states]
        outs = [np.asarray(f.result(timeout=120)) for f in futs]
    for got, want in zip(outs, seq):
        np.testing.assert_array_equal(got, want)


def test_apply_demux_from_many_client_threads():
    """Submissions racing from many client threads still demux each
    future to its own request's result (each state carries a distinct
    recognizable payload)."""
    c = _circuit_a()
    states = _random_states(16, seed=3)
    fn = c.compiled_batched(8, donate=False)
    seq = [np.asarray(fn(s[None]))[0] for s in states]
    results: dict = {}
    with _engine(max_wait_ms=10_000, max_batch=8) as eng:
        def client(i):
            results[i] = np.asarray(
                eng.submit(c, state=states[i]).result(timeout=120))
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(states))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
    for i, want in enumerate(seq):
        np.testing.assert_array_equal(results[i], want)


def test_traj_demux_matches_run_batched():
    """A coalesced trajectory request reproduces its standalone
    run_batched result exactly: the per-request key chain
    (split(key, shots)) is preserved through coalescing."""
    from quest_tpu import trajectories as T
    c = _noisy_circuit()
    k1, k2 = jax.random.key(7), jax.random.key(11)
    want1 = T.run_batched(c, k1, 5)
    want2 = T.run_batched(c, k2, 3)
    with _engine(max_wait_ms=20, max_batch=8) as eng:
        f1 = eng.submit(c, shots=5, key=k1)
        f2 = eng.submit(c, shots=3, key=k2)
        p1, d1 = f1.result(timeout=300)
        p2, d2 = f2.result(timeout=300)
    np.testing.assert_array_equal(p1, np.asarray(want1[0]))
    np.testing.assert_array_equal(d1, np.asarray(want1[1]))
    np.testing.assert_array_equal(p2, np.asarray(want2[0]))
    np.testing.assert_array_equal(d2, np.asarray(want2[1]))


def test_traj_mixed_key_styles_never_coalesce():
    """A typed key (jax.random.key) and a raw uint32 PRNGKey are
    different traced inputs whose key data cannot stack into one
    array: the key STYLE rides the queue key, so mixed-style requests
    dispatch separately and each reproduces its standalone run_batched
    result."""
    from quest_tpu import trajectories as T
    c = _noisy_circuit()
    kt, kr = jax.random.key(5), jax.random.PRNGKey(5)
    # 4 shots = exactly the bucket-4 program, ONE launch per style
    # queue (a non-bucket count would cap down and chunk: >1 launch)
    want_t = T.run_batched(c, kt, 4)
    want_r = T.run_batched(c, kr, 4)
    reg = metrics.Registry()
    with _engine(max_wait_ms=10_000, max_batch=8, registry=reg) as eng:
        ft = eng.submit(c, shots=4, key=kt)
        fr = eng.submit(c, shots=4, key=kr)
        eng.drain(timeout_s=300)
        pt, dt = ft.result(timeout=300)
        pr, dr = fr.result(timeout=300)
    assert reg.counter("serve_batches_dispatched").value == 2
    np.testing.assert_array_equal(pt, np.asarray(want_t[0]))
    np.testing.assert_array_equal(dt, np.asarray(want_t[1]))
    np.testing.assert_array_equal(pr, np.asarray(want_r[0]))
    np.testing.assert_array_equal(dr, np.asarray(want_r[1]))


def test_traj_request_larger_than_max_batch_chunks_and_matches():
    """A single request with shots > max_batch chunks through the
    max_batch-bounded bucket program and still demuxes to exactly the
    standalone run_batched result (per-state math and the per-shot key
    chain are batch-size-invariant, pinned per engine)."""
    from quest_tpu import trajectories as T
    c = _noisy_circuit()
    k = jax.random.key(13)
    want_p, want_d = T.run_batched(c, k, 10)
    reg = metrics.Registry()
    with _engine(max_wait_ms=0, max_batch=4, registry=reg) as eng:
        p, d = eng.submit(c, shots=10, key=k).result(timeout=300)
    np.testing.assert_array_equal(p, np.asarray(want_p))
    np.testing.assert_array_equal(d, np.asarray(want_d))
    # 10 slots through the bucket-4 program = 3 launches
    assert reg.snapshot()["counters"]["serve_batches_dispatched"] == 3


def test_traj_observable_matches_run_batched():
    """A trajectory request with `observable=` reduces each chunk on
    device — run_batched's memory contract — and resolves to exactly
    what the standalone run_batched(observable=) call returns."""
    from quest_tpu import trajectories as T

    def z0(planes_b):
        import jax.numpy as jnp
        v = (planes_b[:, 0] ** 2 + planes_b[:, 1] ** 2).reshape(
            planes_b.shape[0], 2, -1)
        return jnp.sum(v[:, 0], axis=1) - jnp.sum(v[:, 1], axis=1)

    c = _noisy_circuit()
    k = jax.random.key(9)
    want_v, want_d = T.run_batched(c, k, 5, observable=z0)
    with _engine(max_wait_ms=5, max_batch=8) as eng:
        got_v, got_d = eng.submit(c, shots=5, key=k,
                                  observable=z0).result(timeout=300)
    # an UNCOALESCED request mirrors run_batched exactly: same capped
    # bucket, same chunk sequence, observable reduces the same padded
    # bucket-shaped chunk with values sliced after — bit-identical
    np.testing.assert_array_equal(got_v, np.asarray(want_v))
    np.testing.assert_array_equal(got_d, np.asarray(want_d))


def test_observable_reduction_applies_per_request():
    """`observable=` reduces each request's planes on the server side:
    the future resolves to the reduced value, never the full planes."""
    c = _circuit_a()

    def z0(planes_b):
        v = (planes_b[:, 0] ** 2 + planes_b[:, 1] ** 2).reshape(
            planes_b.shape[0], 2, -1)
        return np.asarray(v[:, 0].sum(axis=1) - v[:, 1].sum(axis=1))

    s = _random_states(1)[0]
    want = z0(np.asarray(c.compiled_batched(1, donate=False)(s[None])))[0]
    with _engine(max_wait_ms=5) as eng:
        got = eng.submit(c, state=s, observable=z0).result(timeout=120)
    np.testing.assert_allclose(got, want, rtol=1e-6)


# ---------------------------------------------------------------------------
# bucket coalescing: one compiled program per bucket (the acceptance pin)
# ---------------------------------------------------------------------------


def test_mixed_stream_zero_retrace_after_warmup(compile_auditor):
    """THE acceptance gate: a warmed 100-request mixed stream (two
    circuit families interleaved, full buckets) retraces NOTHING — each
    bucket compiled exactly once, every later launch a cache hit."""
    ca, cb = _circuit_a(), _circuit_b()
    states = _random_states(100, seed=5)
    with _engine(max_wait_ms=10_000, max_batch=4) as eng:
        warmup(eng, [ca, cb], buckets=[4])

        def stream():
            futs = []
            for i in range(100):
                c = ca if i % 2 == 0 else cb
                futs.append(eng.submit(c, state=states[i]))
            # 50 requests/family = 12 full bucket-4 launches plus a
            # 2-request tail: drain() flushes the tails NOW (the same
            # padded bucket-2 program in both passes — deterministic
            # shapes, no pad variance between the warm pass and the
            # audited pass) instead of sitting out the wait window
            eng.drain(timeout_s=300)
            for f in futs:
                f.result(timeout=300)

        stream()                      # warms the eager demux ops too
        with compile_auditor as aud:
            stream()
        aud.assert_no_retrace("warmed mixed serve stream")


def test_batches_coalesce_and_occupancy_recorded():
    """Requests arriving within the wait window share launches: 8
    requests at max_batch=8 dispatch as ONE batch with occupancy 1.0."""
    c = _circuit_a()
    reg = metrics.Registry()
    states = _random_states(8, seed=9)
    with _engine(max_wait_ms=10_000, max_batch=8, registry=reg) as eng:
        futs = [eng.submit(c, state=s) for s in states]
        for f in futs:
            f.result(timeout=120)
    snap = reg.snapshot()
    assert snap["counters"]["serve_batches_dispatched"] == 1
    occ = snap["histograms"]["serve_batch_occupancy"]
    assert occ["count"] == 1 and occ["mean"] == pytest.approx(1.0)
    assert snap["counters"]["serve_requests_served"] == 8


def test_no_coalescing_mode_launches_alone():
    """max_wait_ms=0 is the documented no-batching mode (the bench's
    baseline column): every request dispatches as its own launch."""
    c = _circuit_a()
    reg = metrics.Registry()
    states = _random_states(4, seed=13)
    with _engine(max_wait_ms=0, max_batch=8, registry=reg) as eng:
        futs = [eng.submit(c, state=s) for s in states]
        for f in futs:
            f.result(timeout=120)
    assert reg.snapshot()["counters"]["serve_batches_dispatched"] == 4


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


def test_overflow_rejects_loudly():
    """The bounded queue rejects the overflowing submit with
    RejectedError at submit time — and counts it."""
    c = _circuit_a()
    reg = metrics.Registry()
    s = _random_states(1)[0]
    with _engine(max_wait_ms=60_000, max_queue=2, max_batch=64,
                 registry=reg) as eng:
        f1 = eng.submit(c, state=s)
        f2 = eng.submit(c, state=s)
        with pytest.raises(RejectedError, match="queue is full"):
            eng.submit(c, state=s)
        assert reg.counter("serve_requests_rejected").value == 1
        eng.drain(timeout_s=120)
        assert f1.done() and f2.done()


def test_deadline_expires_before_dispatch():
    """An expired request fails with DeadlineExceeded and never occupies
    a launch: zero batches dispatched for it."""
    c = _circuit_a()
    reg = metrics.Registry()
    s = _random_states(1)[0]
    with _engine(max_wait_ms=60_000, registry=reg) as eng:
        f = eng.submit(c, state=s, deadline_s=0.0)
        with pytest.raises(DeadlineExceeded, match="deadline"):
            f.result(timeout=60)
        assert reg.counter("serve_requests_expired").value == 1
        assert reg.counter("serve_batches_dispatched").value == 0


def test_drain_returns_only_after_expired_futures_complete():
    """drain()'s flush contract covers expired requests too: when it
    returns, their futures are DONE (DeadlineExceeded set), not merely
    removed from the queue — the worker completes them before waking
    the drain waiter."""
    c = _circuit_a()
    s = _random_states(1)[0]
    with _engine(max_wait_ms=60_000) as eng:
        f = eng.submit(c, state=s, deadline_s=0.0)
        eng.drain(timeout_s=60)
        assert f.done()
        assert isinstance(f.exception(timeout=0), DeadlineExceeded)


def test_live_requests_survive_a_neighbours_deadline():
    """One expired request must not take down the live requests queued
    behind the same program key."""
    c = _circuit_a()
    states = _random_states(2, seed=21)
    want = np.asarray(c.compiled_batched(1, donate=False)(
        states[1][None]))[0]
    with _engine(max_wait_ms=150, max_batch=8) as eng:
        dead = eng.submit(c, state=states[0], deadline_s=0.0)
        live = eng.submit(c, state=states[1])
        with pytest.raises(DeadlineExceeded):
            dead.result(timeout=60)
        np.testing.assert_array_equal(
            np.asarray(live.result(timeout=120)), want)


def test_cancel_before_dispatch():
    """Future.cancel() succeeds while queued; the sweep drops the
    request without charging a launch."""
    c = _circuit_a()
    reg = metrics.Registry()
    s = _random_states(1)[0]
    with _engine(max_wait_ms=60_000, registry=reg) as eng:
        f = eng.submit(c, state=s)
        assert f.cancel()
        eng.drain(timeout_s=60)
        assert f.cancelled()
        assert reg.counter("serve_requests_cancelled").value == 1
        assert reg.counter("serve_batches_dispatched").value == 0


def test_drain_flushes_partial_bucket():
    """drain() launches waiting partial buckets immediately instead of
    sitting out the wait window; close() refuses new work afterwards."""
    c = _circuit_a()
    reg = metrics.Registry()
    states = _random_states(3, seed=17)
    eng = _engine(max_wait_ms=600_000, max_batch=8, registry=reg)
    try:
        futs = [eng.submit(c, state=s) for s in states]
        t0 = time.monotonic()
        eng.drain(timeout_s=120)
        assert time.monotonic() - t0 < 590        # not the wait window
        assert all(f.done() for f in futs)
        snap = reg.snapshot()
        assert snap["counters"]["serve_batches_dispatched"] == 1
        # 3 states pad to the bucket-4 program: occupancy 3/4
        occ = snap["histograms"]["serve_batch_occupancy"]
        assert occ["mean"] == pytest.approx(0.75)
    finally:
        eng.close(timeout_s=120)
    # post-close the engine is deterministically rejecting: submit AND
    # drain raise typed RejectedError ("engine closed") instead of
    # racing the dying worker, and close() stays idempotent
    with pytest.raises(RejectedError, match="engine closed"):
        eng.submit(c, state=states[0])
    with pytest.raises(RejectedError, match="engine closed"):
        eng.drain(timeout_s=5)
    eng.close(timeout_s=60)                       # idempotent
    assert eng.state == "closed"
    with pytest.raises(RejectedError, match="engine closed"):
        eng.submit(c, state=states[0])


def test_concurrent_drains_both_flush():
    """drain() is safe to call from several threads at once: each
    drainer holds the flush mode open until its own predicate turns
    true (a drainer COUNT, not a bool a finishing drain could clear
    from under a still-waiting one)."""
    c = _circuit_a()
    states = _random_states(3, seed=27)
    with _engine(max_wait_ms=600_000, max_batch=8) as eng:
        futs = [eng.submit(c, state=s) for s in states]
        errs: list = []

        def do_drain():
            try:
                eng.drain(timeout_s=120)
            except Exception as e:      # noqa: BLE001 - surfaced below
                errs.append(e)

        threads = [threading.Thread(target=do_drain) for _ in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errs
        assert all(f.done() for f in futs)


def test_submit_validates_inputs():
    c = _circuit_a()
    s = _random_states(1)[0]
    with _engine(max_wait_ms=0) as eng:
        with pytest.raises(ValueError, match="exactly one"):
            eng.submit(c)
        with pytest.raises(ValueError, match="exactly one"):
            eng.submit(c, state=s, shots=4)
        with pytest.raises(ValueError, match="planes"):
            eng.submit(c, state=s[:, :4])
        with pytest.raises(ValueError, match="shots"):
            eng.submit(c, shots=0)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_metrics_snapshot_schema():
    """snapshot() is the stable machine-readable feed: counters are
    ints, histograms carry count/mean/p50/p95/p99 floats — the schema
    scripts/serve_stats.py renders and dashboards scrape."""
    c = _circuit_a()
    reg = metrics.Registry()
    with _engine(max_wait_ms=5, registry=reg) as eng:
        eng.submit(c, state=_random_states(1)[0]).result(timeout=120)
    snap = reg.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    for name, v in snap["counters"].items():
        assert isinstance(name, str) and isinstance(v, int), (name, v)
    for name, v in snap["gauges"].items():
        assert isinstance(name, str) and isinstance(v, float), (name, v)
    for needed in ("serve_requests_submitted", "serve_requests_served",
                   "serve_batches_dispatched"):
        assert snap["counters"][needed] >= 1, snap
    for name, h in snap["histograms"].items():
        assert set(h) == {"count", "mean", "p50", "p95", "p99"}, (name, h)
        assert isinstance(h["count"], int)
        assert all(isinstance(h[k], float)
                   for k in ("mean", "p50", "p95", "p99"))
    for needed in ("serve_batch_occupancy", "serve_queue_wait_s",
                   "serve_e2e_latency_s"):
        assert snap["histograms"][needed]["count"] >= 1, snap
    import json
    json.dumps(snap)                              # JSON-serializable


def test_histogram_percentiles():
    h = metrics.Histogram("t")
    for x in range(1, 101):
        h.observe(float(x))
    s = h.summary()
    assert s["count"] == 100
    assert s["mean"] == pytest.approx(50.5)
    assert s["p50"] == pytest.approx(50.0, abs=1.5)
    assert s["p95"] == pytest.approx(95.0, abs=1.5)
    assert s["p99"] == pytest.approx(99.0, abs=1.5)


def test_compile_cache_counters_are_structured():
    """Satellite: enable_compile_cache's hit/miss tallies are counters
    in the process-wide registry (stderr is derived from them), so the
    numbers are programmatically readable instead of log-scrape-only."""
    from quest_tpu import precision
    # conftest already called enable_compile_cache: the listener is
    # installed and feeds the process-wide registry
    assert precision._cache_listener_installed
    hits, misses = precision._cache_counters()
    snap = metrics.snapshot()
    assert snap["counters"]["compile_cache_hits"] == hits.value
    assert snap["counters"]["compile_cache_misses"] == misses.value
    before = hits.value
    c = Circuit(3).h(0).cnot(0, 1)
    c.compiled_batched(2, donate=False)(_random_states(2, n=3, seed=29))
    assert hits.value + misses.value >= before    # tallies move, not logs


@pytest.mark.parametrize("outside", [True, False])
def test_compile_cache_placed_from_outside(monkeypatch, tmp_path, outside):
    """JAX_COMPILATION_CACHE_DIR, when set, is the cache and wins over a
    caller's path=; unset, the cache is <repo>/.jax_cache. The plan
    cache sits next to whichever it is."""
    import os

    import jax

    from quest_tpu import plan, precision
    prior = jax.config.jax_compilation_cache_dir
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = str(tmp_path / "x") if outside else os.path.join(repo,
                                                            ".jax_cache")
    if outside:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("QUEST_PLAN_CACHE_DIR", raising=False)
    try:
        precision.enable_compile_cache(
            path=str(tmp_path / "ignored") if outside else None,
            min_compile_secs=0.1)
        assert jax.config.jax_compilation_cache_dir == want
        assert plan.plan_cache_dir(create=False) == want + ".plans"
    finally:
        jax.config.update("jax_compilation_cache_dir", prior)
        precision._CACHE_STATS["dir"] = prior


# ---------------------------------------------------------------------------
# warmup
# ---------------------------------------------------------------------------


def test_default_buckets_cover_the_pow2_grid():
    assert default_buckets(8) == (1, 2, 4, 8)
    assert default_buckets(6) == (1, 2, 4, 6)
    assert default_buckets(1) == (1,)


def test_warmup_reports_compile_seconds_and_prevents_cold_start(
        compile_auditor):
    """warmup() pre-compiles the declared (circuit, bucket) grid and
    reports per-program compile_s; the first real request afterwards
    traces nothing."""
    c = _circuit_a()
    with _engine(max_wait_ms=0, max_batch=4) as eng:
        rep = warmup(eng, [c], buckets=[1])
        assert set(rep) == {"programs", "plans", "plan_cache", "total_s"}
        assert rep["programs"] and all(
            isinstance(v, float) and v >= 0 for v in rep["programs"].values())
        s = _random_states(1, seed=23)[0]
        eng.submit(c, state=s).result(timeout=120)    # warm demux ops
        with compile_auditor as aud:
            eng.submit(c, state=s).result(timeout=120)
        aud.assert_no_retrace("warmed serve engine first request")


def test_warmup_noisy_circuit_warms_trajectory_program(compile_auditor):
    c = _noisy_circuit()
    with _engine(max_wait_ms=0, max_batch=4) as eng:
        warmup(eng, [c], buckets=[4])
        f = eng.submit(c, shots=4, key=jax.random.key(3))
        f.result(timeout=300)                         # warm demux ops
        with compile_auditor as aud:
            eng.submit(c, shots=4, key=jax.random.key(3)).result(
                timeout=300)
        aud.assert_no_retrace("warmed trajectory serve request")


def test_warmup_buckets_ride_the_dispatch_bucket_rule(compile_auditor):
    """A declared batch size maps through the SAME bucket rule the
    dispatch side uses: buckets=[3] for a trajectory workload warms
    the CAPPED bucket-2 program (run_batched's largest-that-fits
    rule), not batch_bucket(3)=4 — so a shots=3 request after warmup
    retraces nothing."""
    c = _noisy_circuit()
    with _engine(max_wait_ms=0, max_batch=8) as eng:
        rep = warmup(eng, [c], buckets=[3])
        assert "c0:b2" in rep["programs"], rep     # capped, not b4
        f = eng.submit(c, shots=3, key=jax.random.key(4))
        f.result(timeout=300)                      # warm demux ops
        with compile_auditor as aud:
            eng.submit(c, shots=3, key=jax.random.key(4)).result(
                timeout=300)
        aud.assert_no_retrace("capped-bucket warmed shots=3 request")


def test_warmup_kind_overrides_the_noisiness_heuristic(compile_auditor):
    """The request kind is the CALLER's choice at submit(): shots= is
    valid for a unitary circuit (zero channels), so kind='traj' must
    warm the trajectory program where the heuristic would have warmed
    only the apply one."""
    c = _circuit_a(4)                                  # unitary
    with _engine(max_wait_ms=0, max_batch=4) as eng:
        warmup(eng, [c], buckets=[4], kind="traj")
        eng.submit(c, shots=4, key=jax.random.key(2)).result(timeout=300)
        with compile_auditor as aud:
            eng.submit(c, shots=4, key=jax.random.key(2)).result(
                timeout=300)
        aud.assert_no_retrace("kind='traj' warmed unitary circuit")
    with pytest.raises(ValueError, match="kind"):
        warmup(eng, [c], kind="bogus")


def test_warmup_matches_raw_key_style(compile_auditor):
    """The PRNG key STYLE is part of the queue key (a raw uint32
    PRNGKey is a different traced input than a typed key), so warming a
    raw-key workload means passing warmup a raw key — afterwards the
    first raw-key submit traces nothing."""
    c = _noisy_circuit()
    with _engine(max_wait_ms=0, max_batch=4) as eng:
        warmup(eng, [c], buckets=[4], key=jax.random.PRNGKey(0))
        f = eng.submit(c, shots=4, key=jax.random.PRNGKey(3))
        f.result(timeout=300)                         # warm demux ops
        with compile_auditor as aud:
            eng.submit(c, shots=4, key=jax.random.PRNGKey(3)).result(
                timeout=300)
        aud.assert_no_retrace("warmed raw-key trajectory serve request")


# ---------------------------------------------------------------------------
# satellite: measurement.sample shot-count bucketing
# ---------------------------------------------------------------------------


def test_sample_shot_counts_share_one_compiled_program(compile_auditor):
    """shots=100/120/128 all pad to the 128 bucket inside the traced
    draw and slice after: ONE compiled sampling program across the
    sweep (the serving workload shape), pinned two ways — the jit cache
    grows by exactly one entry, and a warmed rerun retraces nothing."""
    from quest_tpu import measurement as meas
    from quest_tpu import state as st
    from quest_tpu.ops import gates

    q = st.create_qureg(N)
    for t in range(N):
        q = gates.hadamard(q, t)
    key = jax.random.PRNGKey(42)

    cache_size = meas._sample_traced._cache_size
    before = cache_size()
    outs = {s: np.asarray(meas.sample(q, s, key=key))
            for s in (100, 120, 128)}
    assert cache_size() == before + 1, (
        "distinct shot counts in one bucket must share one compiled "
        "sampling program")
    with compile_auditor as aud:
        for s in (100, 120, 128):
            meas.sample(q, s, key=key)
    aud.assert_no_retrace("bucketed sample() shot sweep")

    for s, got in outs.items():
        assert got.shape == (s,)
        assert got.dtype == np.int32
        assert (got >= 0).all() and (got < (1 << N)).all()
    # a shared key + shared bucket means the padded draw is one stream:
    # the shorter counts are prefixes of the longest
    np.testing.assert_array_equal(outs[100], outs[128][:100])
    np.testing.assert_array_equal(outs[120], outs[128][:120])


# ---------------------------------------------------------------------------
# knob registry coverage
# ---------------------------------------------------------------------------


def test_serve_knobs_registered_runtime_scope():
    """Every QUEST_SERVE_* knob is registry-backed (QL004), runtime
    scope (read once at engine construction, never inside a compiled
    path — QL001), layer 'serve', and parses loudly."""
    from quest_tpu.env import KNOBS
    names = {n for n in KNOBS if n.startswith("QUEST_SERVE_")}
    assert names == {"QUEST_SERVE_MAX_WAIT_MS", "QUEST_SERVE_MAX_QUEUE",
                     "QUEST_SERVE_MAX_BATCH", "QUEST_SERVE_RESTART_MAX",
                     "QUEST_SERVE_BREAKER_THRESHOLD",
                     # the fleet layer (ISSUE 12, docs/SERVING.md §fleet)
                     "QUEST_SERVE_REPLICAS", "QUEST_SERVE_TENANT_QUOTA",
                     "QUEST_SERVE_SHED_THRESHOLD",
                     "QUEST_SERVE_PRIORITIES"}
    for n in names:
        k = KNOBS[n]
        assert k.scope == "runtime" and k.layer == "serve", k
        assert k.malformed is not None
        with pytest.raises(ValueError):
            k.parse(k.malformed)


def test_serve_knobs_configure_engine(monkeypatch):
    monkeypatch.setenv("QUEST_SERVE_MAX_WAIT_MS", "0")
    monkeypatch.setenv("QUEST_SERVE_MAX_QUEUE", "1")
    monkeypatch.setenv("QUEST_SERVE_MAX_BATCH", "2")
    eng = _engine()
    try:
        assert eng.max_wait_s == 0.0
        assert eng.max_batch == 2
        assert eng._admission.max_queue == 1
    finally:
        eng.close(timeout_s=60)
