"""Profiling and tracing: the program's one span and counter recorder.

The reference has NO profiling support (SURVEY.md §5: the only
introspection is reportQuregParams/getEnvironmentString). On TPU the
platform tooling is first-class; this module packages it:

  * `trace(dir)` — context manager capturing a profiler trace viewable in
    TensorBoard / Perfetto (wraps jax.profiler).
  * `annotate(name)` — THE span entry point: a named region on the
    profiler's timeline and, while a `recording()` is active, a
    (name, parent, start, end) span in that record.
  * `count(name)` — a program counter, kept by the active recording.
  * `recording()` — collect spans, counters and JAX's compile phases
    (tracing, jaxpr->MLIR lowering with the Mosaic kernels, backend
    compile or cache load) in memory, for the caller to read.
  * `on_compile_event(fn)` — the one jax.monitoring hookup: recordings,
    analysis.audit.CompileAuditor and the persistent-cache counters of
    quest_tpu.precision all listen through it.

With no recording active, `annotate` costs one check of a module global
beyond the TraceAnnotation, and `count` that check alone.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a device trace: `with profiling.trace("/tmp/trace"): ...`.
    Under an active recording, its clock anchor is taken first, so the
    record's spans can be placed on the trace's time base
    (Recording.spans_on)."""
    jax.profiler.start_trace(log_dir)
    try:
        if _ACTIVE is not None:
            _ACTIVE.anchor()
        yield
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# compile-phase events: the one jax.monitoring registration
# ---------------------------------------------------------------------------

# JAX's compile-phase duration events, by the counter name a record keeps
# (jax._src.dispatch: *_EVENT); backend_compile covers a persistent-cache
# load as well as a compile
PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "mlir_lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_misses": "cache_miss",
}

# fn(name, start_s, end_s) on time.time()'s clock; a cache event has
# start == end
_compile_listeners: List[Callable[[str, float, float], None]] = []
_monitoring_installed = False


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    name = PHASES.get(event)
    if name is not None:
        for fn in tuple(_compile_listeners):
            fn(name, start, end)


def _on_event(event: str, **kw) -> None:
    name = CACHE_EVENTS.get(event)
    if name is not None:
        now = time.time()
        for fn in tuple(_compile_listeners):
            fn(name, now, now)


def on_compile_event(fn: Callable[[str, float, float], None]) -> None:
    """Call fn(name, start_s, end_s) for every compile-phase event
    (PHASES) and persistent-cache hit or miss (CACHE_EVENTS). The
    monitoring listeners are registered once per process and left
    installed; off_compile_event stops the calls."""
    global _monitoring_installed
    if not _monitoring_installed:
        from jax._src import monitoring
        monitoring.register_event_time_span_listener(_on_time_span)
        monitoring.register_event_listener(_on_event)
        _monitoring_installed = True
    if fn not in _compile_listeners:
        _compile_listeners.append(fn)


def off_compile_event(fn) -> None:
    with contextlib.suppress(ValueError):
        _compile_listeners.remove(fn)


# ---------------------------------------------------------------------------
# the recorder
# ---------------------------------------------------------------------------

ANCHOR = "quest.clock_anchor"


class Span(NamedTuple):
    name: str
    parent: Optional[str]     # the enclosing span on the same thread
    start_ns: int             # time.perf_counter_ns(), or the trace's
    end_ns: int               # time base after Recording.spans_on


def _union_s(intervals) -> float:
    total, hi = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > hi:
            total += e - max(s, hi)
            hi = e
    return total


class Recording:
    """Spans, counters and compile phases collected while active.

    `spans` are the annotate() regions that closed; `counts` hold count()
    and one per compile-phase or cache event, and peak()'s largest
    values; `seconds(phase)` is the wall time covered by a phase's events
    (a union: a jit traced inside another's trace is not counted
    twice)."""

    _GUARDED_BY = {"_lock": ("counts", "_phases"),
                   "<owner-thread>": ("anchor_ns",)}

    def __init__(self):
        self.spans: List[Span] = []
        self.counts: Dict[str, float] = {}
        self._phases: Dict[str, List[Tuple[float, float]]] = {}
        self._lock = threading.Lock()
        self._tls = threading.local()
        self.anchor_ns: Optional[int] = None

    def seconds(self, phase: str) -> float:
        with self._lock:
            return _union_s(self._phases.get(phase, ()))

    def span_seconds(self, name: str) -> float:
        return 1e-9 * sum(s.end_ns - s.start_ns for s in self.spans
                          if s.name == name)

    def _count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counts[name] = self.counts.get(name, 0) + n

    def _peak(self, name: str, value: float) -> None:
        with self._lock:
            self.counts[name] = max(self.counts.get(name, value), value)

    def _on_compile(self, name: str, start: float, end: float) -> None:
        with self._lock:
            self.counts[name] = self.counts.get(name, 0) + 1
            if end > start:
                self._phases.setdefault(name, []).append((start, end))

    @contextlib.contextmanager
    def span(self, name: str):
        stack = self._tls.__dict__.setdefault("stack", [])
        parent = stack[-1] if stack else None
        stack.append(name)
        start = time.perf_counter_ns()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            end = time.perf_counter_ns()
            stack.pop()
            self.spans.append(Span(name, parent, start, end))

    def anchor(self) -> None:
        """Record the clock anchor: an empty span that is also annotated,
        so that while the profiler runs it lands in both clocks."""
        with self.span(ANCHOR):
            pass
        self.anchor_ns = self.spans[-1].start_ns

    def offset_ns(self, profile) -> int:
        """Trace time minus record time, from the anchor span's host event
        in `profile` (a jax.profiler.ProfileData, whose host events count
        from the profile's start)."""
        if self.anchor_ns is None:
            raise ValueError("the recording holds no clock anchor: take "
                             "one with anchor() while the profiler runs")
        for plane in profile.planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name == ANCHOR:
                            return int(e.start_ns) - self.anchor_ns
        raise ValueError(f"the profile holds no {ANCHOR!r} event")

    def spans_on(self, profile) -> List[Span]:
        """The spans on `profile`'s time base."""
        off = self.offset_ns(profile)
        return [s._replace(start_ns=s.start_ns + off, end_ns=s.end_ns + off)
                for s in self.spans]


_ACTIVE: Optional[Recording] = None


@contextlib.contextmanager
def recording():
    """`with profiling.recording() as rec:` — collect spans, counters and
    compile phases into `rec` until the block ends. Nothing is written
    anywhere; read `rec`."""
    global _ACTIVE
    rec, prior = Recording(), _ACTIVE
    on_compile_event(rec._on_compile)
    _ACTIVE = rec
    try:
        yield rec
    finally:
        _ACTIVE = prior
        off_compile_event(rec._on_compile)


def annotate(name: str):
    """Named trace region: `with profiling.annotate("quest.plan"): ...`,
    also a span of the active recording."""
    if _ACTIVE is None:
        return jax.profiler.TraceAnnotation(name)
    return _ACTIVE.span(name)


def count(name: str, n: int = 1) -> None:
    """Add n to the active recording's counter `name`."""
    if _ACTIVE is not None:
        _ACTIVE._count(name, n)


def peak(name: str, value: float) -> None:
    """Raise the active recording's `name` to `value` if it is below."""
    if _ACTIVE is not None:
        _ACTIVE._peak(name, value)


def active() -> bool:
    """Whether a recording is collecting (count and peak are no-ops
    otherwise, so a caller may skip the work of a value)."""
    return _ACTIVE is not None


# ---------------------------------------------------------------------------
# self-auditing stage report (VERDICT r3/r4 carried item: the measured
# stage costs behind the cost model must be reproducible by a SHIPPED
# command, not ad-hoc probe scripts)
# ---------------------------------------------------------------------------


def _single_segment(ops, n):
    """(stages, arrays) of the ONE kernel segment a tiny circuit plans
    into — the report measures real planner output, not hand-built
    stages, so it cannot drift from what the engine runs."""
    from quest_tpu.circuit import flatten_ops
    from quest_tpu.ops import fusion as F
    from quest_tpu.ops import pallas_band as PB

    items = F.plan(flatten_ops(ops, n, False), n, bands=PB.plan_bands(n))
    parts = PB.segment_plan(items, n)
    segs = [p for p in parts if p[0] == "segment"]
    if len(segs) != 1:
        raise RuntimeError(
            f"probe circuit planned into {len(segs)} segments (want 1)")
    return segs[0][1], segs[0][2]


def _stage_cases(n):
    """Probe circuits, one per stage family of docs/KERNELS.md: a lone
    phase (the DMA floor — its compute adder is tiny, so steady time ~
    one HBM pass), and full-width band operators in each band position
    (b0 lanes / b1 sublanes / scb scattered tiles), plus the width-1
    remainder band (sc) when this n has one."""
    from quest_tpu.circuit import Circuit
    from quest_tpu.ops import pallas_band as PB

    rng_angles = [0.3 + 0.1 * i for i in range(7)]

    def rot_band(ql, w):
        c = Circuit(n)
        for i in range(w):
            c.rx(ql + i, rng_angles[i % 7])
        return c

    cases = [("phase (DMA floor)", Circuit(n).cphase(0.37, 0, 1))]
    bands = PB.plan_bands(n)
    kinds = {0: "b0", 1: "b1"}
    for bi, (ql, w) in enumerate(bands):
        label = kinds.get(bi, "sc" if w == 1 else "scb")
        if label in dict(cases):
            continue
        cases.append((label, rot_band(ql, w)))
    return cases


def stage_report(n: int = None, reps: int = 5, out=None) -> dict:
    """Measure the kernel tier's per-stage costs ON THE ATTACHED BACKEND
    and print the comparison against the chip cost model's constants
    (quest_tpu.circuit._COST_MODELS) — the shipped, repeatable form of
    the round-3/4 probe scripts behind docs/KERNELS.md. Returns the
    record {case: {"measured_ms", "model_lo_ms", "model_hi_ms"}, ...}.

    On a TPU the numbers ARE the cost-model audit (run at n=30 to
    compare against the calibration constants directly). On a CPU host
    the kernels run in the Pallas interpreter — the command still
    exercises the whole path (CI smoke), but the times say nothing
    about chip constants and the report says so loudly.

    CLI: python -m quest_tpu.profiling [--n N] [--reps R]"""
    import sys
    import time

    import jax.numpy as jnp
    import numpy as np

    from quest_tpu.circuit import (_COST_MODELS, _cost_model_for,
                                   _estimate_ms)
    from quest_tpu.ops import pallas_band as PB
    from quest_tpu.state import basis_planes, fused_state_shape

    out = out or sys.stdout
    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if n is None:
        n = 30 if on_tpu else 12
    if not PB.usable(n):
        raise ValueError(f"n={n} is below the kernel tier's minimum")
    interpret = not on_tpu
    kind = str(getattr(jax.devices()[0], "device_kind", "?"))
    model, matched = _cost_model_for(kind)
    chip = "v5p" if model is _COST_MODELS["v5p"] else "v5e"
    print(f"[stage_report] backend={platform} device_kind={kind!r} "
          f"n={n} reps={reps} model={chip} "
          f"({model['provenance']})", file=out)
    if interpret:
        print("[stage_report] CAUTION: CPU host — kernels run in the "
              "Pallas INTERPRETER; times exercise the path but are NOT "
              "chip constants. Run on the TPU for the real audit.",
              file=out)

    rec = {}
    for label, circ in _stage_cases(n):
        stages, arrays = _single_segment(circ.ops, n)
        fn = PB.compile_segment(stages, n, interpret=interpret)
        arrays = [jnp.asarray(a) for a in arrays]
        jfn = jax.jit(lambda a: fn(a, arrays), donate_argnums=(0,))
        amps = basis_planes(0, n=n, rdt=jnp.float32,
                            shape=fused_state_shape(n))
        amps = jfn(amps)
        _ = np.asarray(amps[0, 0, :4])          # true completion
        t0 = time.perf_counter()
        for _ in range(reps):
            amps = jfn(amps)
        _ = np.asarray(amps[0, 0, :4])
        ms = (time.perf_counter() - t0) / reps * 1e3
        del amps    # free this case's state BEFORE the next case
                    # allocates its own — two live 30q states (8 GiB
                    # each) exceed v5e HBM (seen as ResourceExhausted
                    # while the next jit baked its operand constants)
        lo, hi = _estimate_ms([("segment", stages, arrays)], n, model)
        rec[label] = {"measured_ms": round(ms, 2),
                      "model_lo_ms": round(lo, 2),
                      "model_hi_ms": round(hi, 2),
                      "stages": [type(s).__name__ for s in stages]}
        verdict = ("OK" if lo * 0.8 <= ms <= hi * 1.3 else "DRIFT")
        if interpret:
            verdict = "n/a (interpreter)"
        print(f"[stage_report] {label:<18} measured {ms:8.2f} ms   "
              f"model [{lo:.1f}, {hi:.1f}] ms   {verdict}", file=out)

    # DMA vs MXU split: the phase case is ~pure DMA; a band case's
    # compute adder is (measured - DMA floor)
    if "phase (DMA floor)" in rec:
        dma = rec["phase (DMA floor)"]["measured_ms"]
        for label, r in rec.items():
            if label != "phase (DMA floor)":
                r["compute_adder_ms"] = round(max(0.0, r["measured_ms"]
                                                  - dma), 2)
        print(f"[stage_report] DMA floor {dma:.2f} ms; per-stage compute "
              f"adders: "
              + ", ".join(f"{k}={v['compute_adder_ms']:.1f}"
                          for k, v in rec.items()
                          if "compute_adder_ms" in v), file=out)
    return rec


def sweep_dma_report(n: int = None, reps: int = 5, circuit=None,
                     iters: int = None, out=None) -> dict:
    """Per-sweep DMA-stream vs compute-time split of a fused plan ON
    THE ATTACHED BACKEND — the host-side half of the pipeline's stall
    attribution (ISSUE 11 profiling hook). For each kernel sweep of
    the plan it measures

      * the full sweep launch (stage chain under the decoupled
        multi-buffer pipeline), and
      * ONE stage-free copy kernel — the same slot/semaphore schedule
        streaming the same state bytes with an empty stage chain: the
        plan's raw HBM in+out DMA floor —

    and reports per sweep `total_ms`, the shared `dma_ms` floor and
    `compute_adder_ms = total - dma`. A sweep whose adder is ~0 is
    DMA-bound (the pipeline hides its compute entirely); a large adder
    says the MXU chain overruns the stream and is where the residual
    stall lives. The IN-KERNEL attribution rides the named-scope
    labels the decoupled driver wraps its DMA waits in
    ('quest:dma_in_wait' / 'quest:dma_out_wait' / 'quest:stages',
    pallas_band._decoupled_kernel); they reach a device trace only from
    kernels compiled with libtpu's --xla_enable_custom_call_region_trace
    (docs/SWEEPS.md), which slows the kernels: this report needs no
    flag.

    Defaults: the bench headline step (bench._build_circuit) unrolled
    `iters` = INNER_STEPS applications, n = 30 on TPU / 12 on a CPU
    host (where kernels run in the Pallas INTERPRETER — the command
    exercises the path, the times are not chip constants; the report
    says so loudly, like stage_report).

    CLI: python -m quest_tpu.profiling --sweeps [--n N] [--reps R]"""
    import sys
    import time

    import jax.numpy as jnp
    import numpy as np

    from quest_tpu.ops import fusion as F
    from quest_tpu.ops import pallas_band as PB
    from quest_tpu.state import basis_planes, fused_state_shape

    out = out or sys.stdout
    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if n is None:
        n = 30 if on_tpu else 12
    if not PB.usable(n):
        raise ValueError(f"n={n} is below the kernel tier's minimum")
    interpret = not on_tpu
    if circuit is None:
        import bench
        circuit = bench._build_circuit(n)
        if iters is None:
            iters = bench.INNER_STEPS
    iters = iters or 1
    print(f"[sweep_dma_report] backend={platform} n={n} reps={reps} "
          f"iters={iters} pipeline="
          f"{'decoupled' if PB.decoupled_active() else 'legacy'}",
          file=out)
    if interpret:
        print("[sweep_dma_report] CAUTION: CPU host — kernels run in "
              "the Pallas INTERPRETER; the split exercises the path "
              "but the times are NOT chip constants.", file=out)

    items = F.plan(circuit._planned_flat(n, False), n,
                   bands=PB.plan_bands(n))
    parts = PB.maybe_sweep(PB.segment_plan(items, n) * iters, n)

    def time_launch(stages, arrays):
        fn = PB.compile_segment(list(stages), n, interpret=interpret)
        arrays = [jnp.asarray(a) for a in arrays]
        jfn = jax.jit(lambda a: fn(a, arrays), donate_argnums=(0,))
        amps = basis_planes(0, n=n, rdt=jnp.float32,
                            shape=fused_state_shape(n))
        amps = jfn(amps)
        _ = np.asarray(amps[0, 0, :4])
        t0 = time.perf_counter()
        for _ in range(reps):
            amps = jfn(amps)
        _ = np.asarray(amps[0, 0, :4])
        ms = (time.perf_counter() - t0) / reps * 1e3
        del amps                 # one live full state at a time
        return ms

    # the plan's DMA floor: the identical slot schedule with an empty
    # stage chain — same state bytes through the same rings. Measured
    # once (block geometry differences between sweeps move the DMA
    # stream second-order; the bytes are the whole state either way).
    dma_ms = time_launch((), ())
    rec = {"platform": platform, "n": n, "dma_ms": round(dma_ms, 2),
           "sweeps": []}
    print(f"[sweep_dma_report] DMA floor (stage-free copy kernel): "
          f"{dma_ms:.2f} ms", file=out)
    for i, part in enumerate(parts):
        if part[0] != "segment":
            rec["sweeps"].append({"sweep": i, "kind": "xla_passthrough"})
            print(f"[sweep_dma_report] sweep {i}: XLA passthrough "
                  f"(not a kernel launch)", file=out)
            continue
        ms = time_launch(part[1], part[2])
        adder = max(0.0, ms - dma_ms)
        rec["sweeps"].append({
            "sweep": i, "kind": "kernel", "stages": len(part[1]),
            "total_ms": round(ms, 2),
            "compute_adder_ms": round(adder, 2),
            # interpreter timings are not chip constants: the record
            # mirrors the printed verdict and refuses a verdict off-chip
            "dma_bound": None if interpret
            else bool(adder <= 0.15 * dma_ms),
        })
        verdict = "DMA-bound" if adder <= 0.15 * dma_ms else \
            f"compute overruns stream by {adder:.1f} ms"
        if interpret:
            verdict = "n/a (interpreter)"
        print(f"[sweep_dma_report] sweep {i}: {len(part[1])} stages, "
              f"{ms:8.2f} ms total, compute adder {adder:6.2f} ms   "
              f"{verdict}", file=out)
    return rec


def _main():
    import argparse

    ap = argparse.ArgumentParser(description=stage_report.__doc__)
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--sweeps", action="store_true",
                    help="per-sweep DMA-vs-compute split "
                         "(sweep_dma_report) instead of the per-stage "
                         "cost-model audit")
    args = ap.parse_args()
    if args.sweeps:
        sweep_dma_report(n=args.n, reps=args.reps)
    else:
        stage_report(n=args.n, reps=args.reps)


if __name__ == "__main__":
    _main()
