"""Precision policy.

The reference fixes precision at compile time (QuEST/include/QuEST_precision.h:
QuEST_PREC in {1,2,4} -> qreal in {float, double, long double}, with
REAL_EPS = 1e-5 / 1e-13 / 1e-14). On TPU, precision is a runtime dtype choice:
complex64 is the fast native path (f32 pairs on the VPU/MXU), complex128 is
available for CPU verification and high-accuracy runs (requires
jax_enable_x64). There is no quad-precision analogue.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_COMPLEX_DTYPES = (jnp.complex64, jnp.complex128)

# Validation/comparison tolerance per precision, mirroring the role of the
# reference's REAL_EPS (QuEST_precision.h:35,48).
_REAL_EPS = {
    np.dtype(np.complex64): 1e-5,
    np.dtype(np.complex128): 1e-13,
    np.dtype(np.float32): 1e-5,
    np.dtype(np.float64): 1e-13,
}

_default_dtype = jnp.complex64


def set_default_dtype(dtype) -> None:
    """Set the default amplitude dtype for newly created Quregs."""
    global _default_dtype
    dtype = jnp.dtype(dtype)
    if dtype not in (np.dtype(np.complex64), np.dtype(np.complex128)):
        raise ValueError(f"amplitude dtype must be complex64 or complex128, got {dtype}")
    if dtype == np.dtype(np.complex128) and not jax.config.jax_enable_x64:
        raise ValueError("complex128 requires jax_enable_x64=True")
    _default_dtype = dtype


def get_default_dtype():
    return _default_dtype


_matmul_precision = None  # lazily resolved from env on first use


def set_matmul_precision(p) -> None:
    """Set the lax.Precision used for every state-amplitude contraction
    (band matmuls, many-target gates, superoperators). Accepts a
    jax.lax.Precision or one of 'default' | 'high' | 'highest'.

    The value is read at TRACE time: Circuit keys its compiled-program
    cache on it (so new compiled()/compiled_fused() calls see a change),
    but already-returned step functions keep the precision they were
    traced with."""
    global _matmul_precision
    if isinstance(p, str):
        # the knob registry's parser is the ONE string validator
        # (env.KNOBS; quest-lint QL004)
        from quest_tpu.env import KNOBS
        p = KNOBS["QUEST_MATMUL_PRECISION"].parse(p)
    _matmul_precision = p


def matmul_precision():
    """lax.Precision for state-amplitude contractions. HIGHEST (6-pass
    bf16 — bit-exact f32) is the default: TPU dots otherwise run single
    bf16 passes and total probability drifts ~1e-3. 'high' (3-pass) keeps
    ~f32 accuracy on well-conditioned unitaries at up to 2x the MXU
    throughput on compute-bound circuits; opt in via
    QUEST_MATMUL_PRECISION=high or set_matmul_precision."""
    global _matmul_precision
    if _matmul_precision is None:
        from quest_tpu.env import knob_value
        set_matmul_precision(knob_value("QUEST_MATMUL_PRECISION"))
    return _matmul_precision


_CACHE_STATS = {"dir": None}
_cache_listener_installed = False


def _cache_counters():
    """The structured persistent-cache tallies: counters
    `compile_cache_hits` / `compile_cache_misses` in the serving metrics
    registry (quest_tpu.serve.metrics.REGISTRY — stdlib-only, safe to
    import from here). What used to be a stderr-scrape-only summary is
    now programmatically readable: `serve.metrics.snapshot()` carries
    the tallies, and the stderr lines below are DERIVED from these
    counters rather than a private dict."""
    from quest_tpu.serve import metrics as M
    return (M.REGISTRY.counter("compile_cache_hits"),
            M.REGISTRY.counter("compile_cache_misses"))


def _install_cache_listener() -> None:
    """Tally persistent-cache hits/misses into serve.metrics counters and
    log them on stderr: every MISS is announced as it happens (a miss is
    when you pay the compile — the f64-26q warmup is ~297 s on chip),
    hits are counted and summarized at exit so repeat bench runs show
    what the cache saved without per-dispatch spam. The events arrive
    through quest_tpu.profiling's one jax.monitoring hookup, for the
    process lifetime."""
    global _cache_listener_installed
    if _cache_listener_installed:
        return
    import atexit
    import sys

    from quest_tpu import profiling
    hits, misses = _cache_counters()

    def on_event(name: str, start: float, end: float) -> None:
        if name == "cache_hit":
            hits.inc()
            if hits.value == 1:
                print(f"[quest_tpu] compile cache HIT "
                      f"({_CACHE_STATS['dir']})", file=sys.stderr,
                      flush=True)
        elif name == "cache_miss":
            misses.inc()
            print(f"[quest_tpu] compile cache MISS "
                  f"#{misses.value} (compiling; cached for "
                  f"the next run)", file=sys.stderr, flush=True)

    profiling.on_compile_event(on_event)

    def summary() -> None:
        if hits.value or misses.value:
            print(f"[quest_tpu] compile cache: {hits.value} "
                  f"hit(s), {misses.value} miss(es) "
                  f"({_CACHE_STATS['dir']})", file=sys.stderr, flush=True)

    atexit.register(summary)
    _cache_listener_installed = True


def compile_cache_dir() -> str:
    """Where the persistent compile cache lives: JAX's own
    JAX_COMPILATION_CACHE_DIR when the environment sets it, otherwise
    `.jax_cache` under the repo — one fixed path, since the path is part
    of what the cache is keyed on."""
    import os
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(repo, ".jax_cache")


def enable_compile_cache(path: str = None,
                         min_compile_secs: float = 1.0) -> None:
    """Turn on JAX's persistent compile cache (one shared location for the
    test suite, bench, probes and the driver entry points — circuit
    programs are compile-dominated on first run). A directory set from
    outside through JAX_COMPILATION_CACHE_DIR wins over `path`;
    otherwise `path`, defaulting to compile_cache_dir(). Hits/misses
    tally into the `compile_cache_hits`/`compile_cache_misses` counters
    of `quest_tpu.serve.metrics` (programmatically readable via
    `metrics.snapshot()`) and are logged on stderr, derived from those
    counters (_install_cache_listener)."""
    import os

    import jax
    if os.environ.get("JAX_COMPILATION_CACHE_DIR") or path is None:
        path = compile_cache_dir()
    _CACHE_STATS["dir"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    _install_cache_listener()


def accum_dtype(plane_dtype=None):
    """Accumulator dtype for full-register reductions (norms, overlaps,
    probability sums, sampling CDFs). The reference Kahan-sums its f64
    reductions (QuEST_cpu_distributed.c:64-117); the TPU-native analogue
    is to accumulate in f64 regardless of the plane dtype — the convert
    fuses into the reduce, so nothing f64-sized is ever materialized.
    Falls back to the plane dtype when x64 is disabled (then the chunked
    CDF in measurement.py still bounds the error pairwise)."""
    if jax.config.jax_enable_x64:
        return np.dtype(np.float64)
    return np.dtype(plane_dtype) if plane_dtype is not None else np.dtype(np.float32)


def real_eps(dtype) -> float:
    """Numerical tolerance for the given amplitude dtype."""
    return _REAL_EPS[np.dtype(dtype)]


def real_dtype_of(dtype):
    """The real scalar dtype paired with a complex amplitude dtype
    (host-side mapping; never touches the device). Anything outside the
    two supported tiers is rejected explicitly — in particular a
    quad/complex256 request, which the framework REFUSES by policy
    (docs/PRECISION.md: TPU f64 is already software-emulated and the
    reference's own GPU build lacks the tier too)."""
    d = np.dtype(dtype)
    if d == np.dtype(np.complex64):
        return np.dtype(np.float32)
    if d == np.dtype(np.complex128):
        return np.dtype(np.float64)
    if d in (np.dtype(np.float32), np.dtype(np.float64)):
        return d
    from quest_tpu.validation import QuESTError
    raise QuESTError(
        f"unsupported amplitude dtype {d}: the precision tiers are "
        f"complex64 (f32 planes) and complex128 (f64 planes); wider "
        f"tiers are explicitly refused (docs/PRECISION.md)")


def complex_dtype_of(dtype):
    """The logical complex dtype for a real plane dtype (inverse of
    real_dtype_of)."""
    d = np.dtype(dtype)
    if d == np.dtype(np.float32):
        return np.dtype(np.complex64)
    if d == np.dtype(np.float64):
        return np.dtype(np.complex128)
    return d
