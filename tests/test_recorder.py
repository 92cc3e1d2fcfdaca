"""The program's span and counter recorder (quest_tpu.profiling): spans and
counters only while a recording is active, JAX's compile phases through
the one monitoring hookup, the clock anchor against a profiler trace, and
the spans, counters and kernel names the fused engine records."""

import glob
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quest_tpu import profiling


def _fresh_jit(k):
    """A function JAX has never traced: every call of this helper makes
    a new one, so the first call of the result traces, lowers and
    compiles (or loads from the persistent cache)."""
    return jax.jit(lambda x: jnp.sin(x) * k + x @ x.T)


# -- off ---------------------------------------------------------------------


def test_annotate_off_records_nothing():
    assert profiling._ACTIVE is None
    ctx = profiling.annotate("quest.plan")
    assert isinstance(ctx, jax.profiler.TraceAnnotation)
    with ctx:
        profiling.count("quest.plan")
    assert profiling._ACTIVE is None


def test_records_close_when_the_block_ends():
    with profiling.recording() as rec:
        with profiling.annotate("in"):
            pass
    with profiling.annotate("after"):
        profiling.count("after")
    assert [s.name for s in rec.spans] == ["in"]
    assert rec.counts == {}
    assert rec._on_compile not in profiling._compile_listeners


def test_recording_ends_on_an_exception():
    with pytest.raises(RuntimeError):
        with profiling.recording() as rec:
            with profiling.annotate("failing"):
                raise RuntimeError("boom")
    assert profiling._ACTIVE is None
    # the span that raised still closed
    assert [s.name for s in rec.spans] == ["failing"]


# -- on ----------------------------------------------------------------------


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_nested_spans_carry_their_parent(depth):
    names = [f"level{i}" for i in range(depth)]
    with profiling.recording() as rec:
        def enter(i):
            if i == depth:
                return
            with profiling.annotate(names[i]):
                enter(i + 1)
        enter(0)
    by_name = {s.name: s for s in rec.spans}
    assert set(by_name) == set(names)
    for i, name in enumerate(names):
        s = by_name[name]
        assert s.parent == (names[i - 1] if i else None)
        assert s.start_ns <= s.end_ns
        if i:
            outer = by_name[names[i - 1]]
            assert outer.start_ns <= s.start_ns <= s.end_ns <= outer.end_ns
    # inner spans close first
    assert [s.name for s in rec.spans] == names[::-1]


def test_spans_in_threads_take_their_own_parents():
    seen = []

    def worker():
        with profiling.annotate("worker"):
            seen.append(True)

    with profiling.recording() as rec:
        with profiling.annotate("main"):
            t = threading.Thread(target=worker)
            t.start()
            t.join(timeout=30)
    assert not t.is_alive() and seen
    parents = {s.name: s.parent for s in rec.spans}
    assert parents == {"main": None, "worker": None}


def test_inner_recording_takes_the_spans_then_hands_back():
    with profiling.recording() as outer:
        with profiling.annotate("a"):
            pass
        with profiling.recording() as inner:
            with profiling.annotate("b"):
                pass
        with profiling.annotate("c"):
            pass
    assert [s.name for s in outer.spans] == ["a", "c"]
    assert [s.name for s in inner.spans] == ["b"]


def test_counters_sum():
    with profiling.recording() as rec:
        for n in (1, 5, -2, 10):
            profiling.count("quest.test", n)
        profiling.count("quest.other")
    assert rec.counts == {"quest.test": 14, "quest.other": 1}


def test_span_seconds_sum_the_named_spans():
    with profiling.recording() as rec:
        for _ in range(3):
            with profiling.annotate("quest.sleep"):
                time.sleep(0.01)
    assert rec.span_seconds("quest.sleep") >= 0.03
    assert rec.span_seconds("quest.none") == 0


@pytest.mark.parametrize("spans, want", [
    ([], 0.0),
    ([(0.0, 1.0), (2.0, 3.0)], 2.0),            # disjoint
    ([(0.0, 2.0), (1.0, 3.0)], 3.0),            # overlapping
    ([(0.0, 4.0), (1.0, 2.0), (1.5, 3.0)], 4.0),  # nested traces
    ([(5.0, 6.0), (0.0, 1.0), (0.5, 5.5)], 6.0),  # unsorted
])
def test_phase_seconds_are_a_union(spans, want):
    rec = profiling.Recording()
    for s, e in spans:
        rec._on_compile("jaxpr_trace", s, e)
    assert rec.seconds("jaxpr_trace") == pytest.approx(want)
    assert rec.counts.get("jaxpr_trace", 0) == len(spans)


# -- compile phases through the one monitoring hookup ------------------------


@pytest.mark.parametrize("phase", ["jaxpr_trace", "mlir_lower",
                                   "backend_compile"])
def test_first_call_records_each_compile_phase(phase):
    f = _fresh_jit(float(len(phase)))
    x = jnp.ones((8, 8), jnp.float32)
    with profiling.recording() as rec:
        f(x).block_until_ready()
    assert rec.counts.get(phase, 0) >= 1
    assert rec.seconds(phase) > 0
    # a warm call compiles nothing
    with profiling.recording() as warm:
        f(x).block_until_ready()
    assert warm.counts.get(phase, 0) == 0


@pytest.mark.parametrize("event, name", sorted(
    profiling.CACHE_EVENTS.items()))
def test_cache_events_reach_the_recording(event, name):
    with profiling.recording() as rec:
        profiling._on_event(event)
        profiling._on_event("/jax/compilation_cache/unrelated")
    assert rec.counts == {name: 1}


def test_one_monitoring_registration_serves_every_listener():
    from jax._src import monitoring

    from quest_tpu import precision
    from quest_tpu.analysis.audit import CompileAuditor
    def noop(*args):
        pass
    profiling.on_compile_event(noop)            # installs, once
    profiling.on_compile_event(noop)
    assert profiling._compile_listeners.count(noop) == 1
    profiling.off_compile_event(noop)
    spans = monitoring.get_event_time_span_listeners()
    events = monitoring.get_event_listeners()
    assert spans.count(profiling._on_time_span) == 1
    assert events.count(profiling._on_event) == 1
    durations = monitoring.get_event_duration_listeners()
    for fn in list(spans) + list(events) + list(durations):
        assert getattr(fn, "__module__", "") not in (
            "quest_tpu.analysis.audit", "quest_tpu.precision"), fn
    # the persistent-cache counters listen through the same hookup
    assert precision._cache_listener_installed
    hits, _ = precision._cache_counters()
    before = hits.value
    profiling._on_event("/jax/compilation_cache/cache_hits")
    assert hits.value == before + 1
    with CompileAuditor() as aud:
        assert aud._on_compile in profiling._compile_listeners
    assert aud._on_compile not in profiling._compile_listeners


def test_compile_auditor_counts_through_the_shared_listener():
    from quest_tpu.analysis.audit import CompileAuditor, StaleCacheError
    f = _fresh_jit(3.0)
    x = jnp.ones((8, 8), jnp.float32)
    with CompileAuditor() as aud, profiling.recording() as rec:
        f(x).block_until_ready()
    assert aud.traces >= 1 and aud.backend_compiles >= 1
    assert aud.traces == rec.counts["jaxpr_trace"]
    assert aud.backend_compiles == rec.counts["backend_compile"]
    with pytest.raises(StaleCacheError):
        aud.assert_no_retrace()
    with CompileAuditor() as warm:
        f(x).block_until_ready()
    warm.assert_no_retrace()


# -- the clock anchor --------------------------------------------------------


def test_offset_needs_an_anchor():
    rec = profiling.Recording()
    with pytest.raises(ValueError, match="anchor"):
        rec.offset_ns(None)


def test_recorded_spans_align_with_the_profiler_trace(tmp_path):
    from jax.profiler import ProfileData
    d = str(tmp_path / "trace")
    with profiling.recording() as rec:
        with profiling.trace(d):        # takes the anchor first
            time.sleep(0.02)
            with profiling.annotate("quest.aligned"):
                time.sleep(0.01)
                with profiling.annotate("quest.aligned_inner"):
                    time.sleep(0.005)
    assert rec.anchor_ns is not None
    pd = ProfileData.from_file(glob.glob(
        os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0])
    events = {e.name: e for plane in pd.planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("quest.")}
    on_trace = {s.name: s for s in rec.spans_on(pd)}
    for name in ("quest.aligned", "quest.aligned_inner"):
        e, s = events[name], on_trace[name]
        assert abs(s.start_ns - e.start_ns) < 1e6, name
        assert abs(s.end_ns - (e.start_ns + e.duration_ns)) < 1e6, name


# -- the fused engine's spans, counters and kernel names ---------------------


@pytest.fixture(scope="module", params=["statevector", "density"])
def fused_records(request):
    """The first and the second compiled_fused call of a small circuit
    (10 state bits, interpret mode), each under a recording; the first
    is lowered and compiled."""
    from quest_tpu.circuit import Circuit, random_circuit
    from quest_tpu.state import fused_state_shape
    density = request.param == "density"
    if density:
        c = Circuit(5).h(0).cnot(0, 3).damping(1, 0.1).rz(4, 0.3)
        c.depolarising(2, 0.05).cz(1, 4)
    else:
        c = random_circuit(10, 2, seed=5, entangler="cz")
    with profiling.recording() as first:
        fn = c.compiled_fused(10, density, donate=False, interpret=True)
        fn.lower(jax.ShapeDtypeStruct(fused_state_shape(10),
                                      jnp.float32)).compile()
    with profiling.recording() as second:
        assert c.compiled_fused(10, density, donate=False,
                                interpret=True) is fn
    return first, second


def test_fused_first_call_records_one_plan_span(fused_records):
    first, _ = fused_records
    plans = [s for s in first.spans if s.name == "quest.plan"]
    assert len(plans) == 1 and plans[0].end_ns > plans[0].start_ns


@pytest.mark.parametrize("phase", ["jaxpr_trace", "mlir_lower"])
def test_fused_first_call_traces_and_lowers(fused_records, phase):
    first, _ = fused_records
    assert first.seconds(phase) > 0


def test_fused_cached_call_records_a_hit_and_no_plan(fused_records):
    first, second = fused_records
    assert "quest.fused_cache_hit" not in first.counts
    assert not second.spans
    assert second.counts == {"quest.fused_cache_hit": 1}


def test_build_plan_records_a_plan_span():
    from quest_tpu.circuit import random_circuit
    c = random_circuit(10, 2, seed=3)
    with profiling.recording() as rec:
        c.plan_stats()
    assert [s.name for s in rec.spans] == ["quest.plan"]


NAME = re.compile(r"^quest_seg_([a-z]+[0-9]+_)+r\d+s\d+(_b\d+)?_[0-9a-f]{8}$")


def _segments(c, n):
    from quest_tpu.ops import fusion as F
    from quest_tpu.ops import pallas_band as PB
    items = F.plan(c._planned_flat(n, False), n, bands=PB.plan_bands(n))
    return [p for p in PB.maybe_sweep(PB.segment_plan(items, n), n)
            if p[0] == "segment"]


@pytest.mark.parametrize("n, depth", [(10, 1), (12, 3), (14, 4)])
def test_kernel_names_are_stable_signatures(n, depth):
    from quest_tpu.circuit import random_circuit
    from quest_tpu.ops import pallas_band as PB
    segs = _segments(random_circuit(n, depth, seed=9, entangler="cz"), n)
    assert segs
    for _, stages, _ in segs:
        geo = PB.segment_geometry(stages, n)
        name = PB.kernel_name(stages, geo)
        assert NAME.match(name), name
        # the same structure names the same, whatever the operands
        assert PB.kernel_name(list(stages), PB.segment_geometry(
            list(stages), n)) == name
        assert PB.kernel_name(stages, geo, batch=4) != name
        assert "_b4_" in PB.kernel_name(stages, geo, batch=4)
        for kind in {type(st).__name__.removesuffix("Stage").lower()
                     for st in stages}:
            assert f"_{kind}" in name


@pytest.mark.parametrize("n", [12, 16])
def test_kernel_names_differ_exactly_when_structures_do(n):
    """One name per kernel structure: an rx on any qubit of one band is
    the same kernel (the operator rides as an operand), on another band
    another kernel."""
    from quest_tpu.circuit import Circuit
    from quest_tpu.ops import pallas_band as PB
    by_name = {}
    for q in range(n):
        for angle in (0.3, 0.7):
            (_, stages, _), = _segments(Circuit(n).rx(q, angle), n)
            name = PB.kernel_name(stages, PB.segment_geometry(stages, n))
            by_name.setdefault(name, set()).add(tuple(stages))
    assert len(by_name) >= 2
    assert all(len(v) == 1 for v in by_name.values()), by_name


def test_copy_kernel_is_named_copy():
    from quest_tpu.ops import pallas_band as PB
    geo = PB.segment_geometry((), 12)
    assert PB.kernel_name((), geo).startswith("quest_seg_copy_r")


def test_fused_program_runs_under_a_recording():
    """The sweep scopes and kernel names leave the result alone."""
    from quest_tpu.circuit import random_circuit
    from quest_tpu.state import fused_state_shape
    n = 10
    c = random_circuit(n, 2, seed=21, entangler="cz")
    x = np.zeros(fused_state_shape(n), np.float32)
    x[0, 0, 0] = 1.0
    with profiling.recording():
        got = c.compiled_fused(n, False, donate=False,
                               interpret=True)(jnp.asarray(x))
    want = c.compiled_banded(n, False, donate=False)(
        jnp.asarray(x).reshape(2, -1))
    np.testing.assert_allclose(np.asarray(got).reshape(2, -1),
                               np.asarray(want), atol=2e-5)
