"""Runtime audit harness: retrace accounting + knob-flip cache audits.

The static rules (quest_tpu.analysis.lint) prove every compiled-path
knob is REGISTERED; this module proves the registration actually works
at run time:

  * CompileAuditor — a context manager hooked into jax's monitoring
    events that counts traces/compiles while it is active. The golden
    retrace check runs a circuit set twice and asserts the second pass
    compiles NOTHING (a nonzero count means some cache key is unstable
    — the silent recompile tax).

  * audit_knob_flips — for every keyed knob in the registry, warms the
    circuit-level compiled cache and the eager per-gate jit workers,
    asserts a same-value rerun does NOT retrace, then flips the knob
    and asserts the caches MISS (a hit means the knob is missing from
    the cache key: the exact stale-program bug of ADVICE r4 item 2 /
    r5 item 2, reintroduced and caught in tests/test_lint.py).

Run from pytest (tier-1: tests/test_lint.py) — the audits build tiny
3-qubit programs, so a full sweep costs seconds, not minutes.
"""

from __future__ import annotations

import contextlib
import os
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np


class StaleCacheError(AssertionError):
    """A compiled-program cache returned a stale program (or retraced
    when it should not have) during a knob-flip audit."""


class CompileAuditor:
    """Counts jit traces while active, from JAX's compile-phase events
    through quest_tpu.profiling.on_compile_event (one 'jaxpr_trace'
    event fires per trace; backend compiles, cache loads included, are
    counted separately). Nestable and re-enterable."""

    def __init__(self):
        self.traces = 0
        self.backend_compiles = 0

    def _on_compile(self, name: str, start: float, end: float) -> None:
        if name == "jaxpr_trace":
            self.traces += 1
        elif name == "backend_compile":
            self.backend_compiles += 1

    def __enter__(self) -> "CompileAuditor":
        from quest_tpu import profiling
        self.traces = 0
        self.backend_compiles = 0
        profiling.on_compile_event(self._on_compile)
        return self

    def __exit__(self, *exc) -> None:
        from quest_tpu import profiling
        profiling.off_compile_event(self._on_compile)

    # -- assertions -------------------------------------------------------
    def assert_no_retrace(self, what: str = "golden circuit set") -> None:
        if self.traces:
            raise StaleCacheError(
                f"{self.traces} unexpected retrace(s) while re-running "
                f"the {what}: some compiled-program cache key is "
                f"unstable (every rerun pays a silent recompile)")


# ---------------------------------------------------------------------------
# golden circuit set
# ---------------------------------------------------------------------------


def golden_circuits():
    """Small circuits covering the per-gate XLA engine and the banded
    fusion engine — the compiled surfaces whose cache discipline the
    audits exercise. Deliberately tiny (3 qubits) so a full audit sweep
    stays in seconds."""
    from quest_tpu.circuit import Circuit
    c1 = Circuit(3).h(0).cnot(0, 1).rz(2, 0.25).cz(1, 2).rx(0, 0.5)
    c2 = Circuit(3)
    for q in range(3):
        c2.h(q)
    c2.cnot(0, 2).t(1)
    return [c1, c2]


def _base_state(n: int = 3) -> np.ndarray:
    amps = np.zeros((2, 1 << n), dtype=np.float32)
    amps[0, 0] = 1.0
    return amps


def run_golden(circuits) -> None:
    """One pass of a golden set through the compiled engines. Callers
    must pass the SAME circuit objects across passes: the compiled
    caches live on the Circuit instances, so a fresh set per pass
    measures construction cost, not cache stability."""
    for c in circuits:
        amps = _base_state(c.num_qubits)
        c.compiled(c.num_qubits, False, donate=False)(amps)
        c.compiled_banded(c.num_qubits, False, donate=False)(amps)


def golden_retrace_check(circuits=None) -> CompileAuditor:
    """THE golden retrace audit: build the set once, warm every engine,
    re-run the identical pass under a CompileAuditor and assert zero
    retraces. Returns the (exited) auditor for inspection. A failure
    means some compiled-program cache key is unstable — every rerun of
    identical work pays a silent recompile."""
    circuits = golden_circuits() if circuits is None else circuits
    run_golden(circuits)
    with CompileAuditor() as aud:
        run_golden(circuits)
    aud.assert_no_retrace()
    return aud


# ---------------------------------------------------------------------------
# knob flipping
# ---------------------------------------------------------------------------


def _apply_flip(name: str, raw: str) -> None:
    """Flip a knob the way its docs say to flip it mid-process: env var
    for env-read knobs; the setter for setter-backed knobs (matmul
    precision resolves the env once, then set_matmul_precision is the
    documented mid-process switch)."""
    if name == "QUEST_MATMUL_PRECISION":
        from quest_tpu import precision
        precision.set_matmul_precision(raw)
    else:
        os.environ[name] = raw


@contextlib.contextmanager
def _knob_guard(name: str):
    """Save/restore the env var AND any setter-backed effective value."""
    saved_env = os.environ.get(name)
    saved_eff = None
    if name == "QUEST_MATMUL_PRECISION":
        from quest_tpu import precision
        saved_eff = precision.matmul_precision()
    try:
        yield
    finally:
        if saved_env is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = saved_env
        if saved_eff is not None:
            from quest_tpu import precision
            precision.set_matmul_precision(saved_eff)


def _eager_cache_size() -> int:
    """Total jit-cache entries across the eager per-gate workers."""
    from quest_tpu.ops import gates
    total = 0
    for worker in (gates._const_gate_worker, gates._dyn_gate_worker):
        size = getattr(worker, "_cache_size", None)
        if size is not None:
            total += size()
    return total


def _run_eager() -> None:
    """One eager-path gate through the const worker (H is a named
    constant gate: static operand, _const_gate_worker)."""
    from quest_tpu import state
    from quest_tpu.ops import gates
    q = state.create_qureg(3)
    gates.hadamard(q, 0)


def audit_knob_flips(names: Optional[Sequence[str]] = None,
                     circuit=None) -> List[Dict]:
    """For each keyed registry knob with registered flip values: assert
    the circuit-level compiled cache and (for apply-layer knobs) the
    eager gate workers MISS when the knob flips, and do NOT retrace
    when it does not. Raises StaleCacheError on the first violation;
    returns a per-knob report on success.

    `circuit` injects the warm subject (tests use it to re-introduce
    the PR-1 stale-eager-worker bug shape and prove the audit trips)."""
    from quest_tpu.env import KNOBS
    from quest_tpu.circuit import Circuit

    targets = [KNOBS[n] for n in names] if names else [
        k for k in KNOBS.values() if k.scope == "keyed" and k.flips]
    report: List[Dict] = []

    for knob in targets:
        if not knob.flips:
            raise ValueError(f"{knob.name} has no registered flip values")
        with _knob_guard(knob.name):
            _apply_flip(knob.name, knob.flips[0])
            c = circuit if circuit is not None \
                else Circuit(3).h(0).cnot(0, 1).rz(2, 0.25)
            amps = _base_state(c.num_qubits)

            # warm, then prove a same-value rerun is cache-stable
            c.compiled(c.num_qubits, False, donate=False)(amps)
            _run_eager()
            with CompileAuditor() as stable:
                c.compiled(c.num_qubits, False, donate=False)(amps)
            stable.assert_no_retrace(
                f"compiled circuit with {knob.name}={knob.flips[0]}")
            eager_before = _eager_cache_size()
            _run_eager()
            if _eager_cache_size() != eager_before:
                raise StaleCacheError(
                    f"eager gate workers retraced on a same-value rerun "
                    f"({knob.name}={knob.flips[0]}): unstable cache key")

            # flip: the circuit-level cache must MISS for every keyed
            # knob, the eager workers for every apply-layer knob
            _apply_flip(knob.name, knob.flips[1])
            with CompileAuditor() as flipped:
                c.compiled(c.num_qubits, False, donate=False)(amps)
            if flipped.traces == 0:
                raise StaleCacheError(
                    f"flipping {knob.name} {knob.flips[0]!r} -> "
                    f"{knob.flips[1]!r} did NOT miss the circuit-level "
                    f"compiled cache: the knob is missing from "
                    f"engine_mode_key() and the engine returned a STALE "
                    f"program (ADVICE r4 item 2 class)")
            eager_missed = None
            if knob.layer == "apply":
                before = _eager_cache_size()
                _run_eager()
                eager_missed = _eager_cache_size() > before
                if not eager_missed:
                    raise StaleCacheError(
                        f"flipping {knob.name} did NOT miss the eager "
                        f"gate workers' jit cache: the apply-layer mode "
                        f"key is not threaded through their static "
                        f"`mode` argument (the PR-1 stale-eager-worker "
                        f"bug, ADVICE r5 item 2)")
            report.append({
                "knob": knob.name,
                "flips": knob.flips,
                "circuit_cache_missed": True,
                "eager_cache_missed": eager_missed,
            })
    return report


def audit_eager_worker(run_gate: Callable[[], None],
                       cache_size: Callable[[], int],
                       knob_name: str) -> None:
    """Knob-flip audit against an INJECTED eager worker: `run_gate`
    dispatches one gate through it, `cache_size` reports its jit cache
    size. Used by the negative test that re-introduces the PR-1
    eager-worker bug (a worker whose static args omit the mode key) and
    asserts this audit catches it. Raises StaleCacheError when flipping
    `knob_name` does not grow the worker's cache."""
    from quest_tpu.env import KNOBS
    knob = KNOBS[knob_name]
    if not knob.flips:
        raise ValueError(f"{knob_name} has no registered flip values")
    with _knob_guard(knob.name):
        _apply_flip(knob.name, knob.flips[0])
        run_gate()
        before = cache_size()
        _apply_flip(knob.name, knob.flips[1])
        run_gate()
        if cache_size() <= before:
            raise StaleCacheError(
                f"flipping {knob.name} did not miss the injected eager "
                f"worker's jit cache: its static arguments omit the "
                f"mode key (the PR-1 stale-eager-worker bug shape)")


# ---------------------------------------------------------------------------
# lock-order auditing (the dynamic half of quest-lint QL005/QL007)
# ---------------------------------------------------------------------------


class LockOrderError(AssertionError):
    """Two audited locks were acquired in opposite orders by different
    threads: a latent ABBA deadlock the static rules cannot see."""


class _AuditedLock:
    """Transparent proxy over a Lock/RLock/Condition that reports every
    acquire/release to its LockOrderAuditor. Forwards everything else
    (`wait`/`notify` on a wrapped Condition still work: during `wait`
    the blocked thread acquires nothing, so the held-stack stays
    truthful for ordering purposes)."""

    def __init__(self, auditor: "LockOrderAuditor", name: str, inner):
        self._auditor = auditor
        self._name = name
        self._inner = inner

    def acquire(self, *args, **kwargs) -> bool:
        got = self._inner.acquire(*args, **kwargs)
        if got:
            self._auditor._note_acquire(self._name)
        return got

    def release(self) -> None:
        self._inner.release()
        self._auditor._note_release(self._name)

    def __enter__(self) -> "_AuditedLock":
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def __getattr__(self, attr):
        return getattr(self._inner, attr)


class LockOrderAuditor:
    """Records the acquisition-order graph of every wrapped lock and
    fails on a cycle.

        auditor = LockOrderAuditor()
        engine._cond = auditor.wrap("engine", engine._cond)
        fleet._lock = auditor.wrap("fleet", fleet._lock)
        ... run the workload ...
        auditor.assert_acyclic()

    Every `acquire` of lock B while a thread already holds lock A adds
    the directed edge A -> B; a cycle in that graph means two threads
    can acquire the same pair in opposite orders — the ABBA deadlock.
    Same-name re-entry (the ServeFleet RLock contract from PR 11) is
    counted, not edged: a reentrant self-acquire cannot deadlock.
    Thread-safe; the held-stack is thread-local."""

    _GUARDED_BY = {"_mu": ("edges", "reentries", "acquisitions")}

    def __init__(self):
        import threading
        self._mu = threading.Lock()
        self._tls = threading.local()
        self.edges: Dict[str, set] = {}           # A -> {B acquired under A}
        self.reentries: Dict[str, int] = {}       # name -> self-reacquires
        self.acquisitions: Dict[str, int] = {}    # name -> total acquires

    def wrap(self, name: str, inner) -> _AuditedLock:
        with self._mu:
            self.edges.setdefault(name, set())
        return _AuditedLock(self, name, inner)

    def _held(self) -> List[str]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _note_acquire(self, name: str) -> None:
        stack = self._held()
        with self._mu:
            self.acquisitions[name] = self.acquisitions.get(name, 0) + 1
            if name in stack:
                self.reentries[name] = self.reentries.get(name, 0) + 1
            else:
                for held in set(stack):
                    self.edges.setdefault(held, set()).add(name)
        stack.append(name)

    def _note_release(self, name: str) -> None:
        stack = self._held()
        # release orders can interleave (Condition.wait releases out of
        # band); drop the innermost matching entry
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] == name:
                del stack[i]
                return

    def find_cycle(self) -> Optional[List[str]]:
        """A lock-name cycle ['a', 'b', 'a'] if one exists, else None."""
        with self._mu:
            edges = {k: sorted(v) for k, v in self.edges.items()}
        WHITE, GREY, BLACK = 0, 1, 2
        color = {n: WHITE for n in edges}
        path: List[str] = []

        def visit(n: str) -> Optional[List[str]]:
            color[n] = GREY
            path.append(n)
            for nxt in edges.get(n, ()):
                c = color.get(nxt, WHITE)
                if c == GREY:
                    return path[path.index(nxt):] + [nxt]
                if c == WHITE:
                    got = visit(nxt)
                    if got:
                        return got
            color[n] = BLACK
            path.pop()
            return None

        for n in sorted(edges):
            if color.get(n, WHITE) == WHITE:
                got = visit(n)
                if got:
                    return got
        return None

    def assert_acyclic(self) -> None:
        cycle = self.find_cycle()
        if cycle:
            raise LockOrderError(
                f"lock acquisition-order cycle {' -> '.join(cycle)}: "
                f"two threads can take these locks in opposite orders "
                f"and deadlock; impose one global order "
                f"(docs/ANALYSIS.md §lock-order)")
