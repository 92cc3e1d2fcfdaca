"""Multi-replica serving: routing, failover, tenancy — the fleet layer.

`ServeEngine` (serve/engine.py) is one worker thread over one set of
queues: supervised, breakered, degradable — but ONE replica. This
module is the production shape above it (docs/SERVING.md §fleet): a
`ServeFleet` owns N ServeEngine replicas and makes the existing
resilience machinery compose across them.

    fleet = ServeFleet(replicas=4)            # knobs: QUEST_SERVE_*
    fut = fleet.submit(circuit, state=planes,
                       tenant="alice", priority=1)
    out = fut.result()

Three contracts, each pinned in tests/test_fleet.py:

  * ROUTING WITH FAILOVER — requests route to the replica that has the
    program warm (a `program_key()` -> replica affinity map; compiled
    programs cache on the Circuit instance, so "warm" here means the
    replica's worker has traced/dispatched this program family before
    and its queues coalesce with like requests). When the affinity
    replica's backlog runs a full launch deeper than the least-loaded
    replica, the request SPILLS to the least-loaded one instead of
    queueing behind the hot spot. When a replica exhausts its restart
    budget and goes FAILED, its queued-but-undispatched requests —
    which the engine resolves with RejectedError under the PR-6
    `_active`-ledger contract — REQUEUE onto surviving replicas in
    arrival order; requests whose launch had already started still
    fail typed (their outcome is unknown — no double-serve), EXCEPT
    durable jobs, whose checkpoint-chain resume makes re-dispatch
    provably serve-once (docs/RESILIENCE.md §durable). The affinity
    map rebuilds as the requeued requests re-route. A fleet with one
    survivor degrades to single-engine behavior; a fleet with none
    goes loudly FAILED — every future resolves typed, never a hang.
  * TENANT ADMISSION + PRIORITY SHED — per-tenant pending quotas
    (`QUEST_SERVE_TENANT_QUOTA`, admission.TenantQuota) bound how much
    of the fleet one tenant's burst can occupy. Fleet PRESSURE is the
    queued fraction of the healthy replicas' capacity plus an
    open-breaker term (each open breaker prices as one max_batch of
    backlog — a program riding the degradation ladder serves slower,
    so its queue is effectively deeper). When pressure crosses
    `QUEST_SERVE_SHED_THRESHOLD`, the LOWEST pending priority class
    sheds with typed `ShedError` naming the pressure cause: an
    incoming request above the lowest queued class EVICTS a queued
    lowest-class victim (cancel-while-queued — an eviction never
    aborts a launch) and takes its place; an incoming request at or
    below the lowest queued class sheds itself. A paying tenant's
    deadline is therefore never burned behind shed-able free traffic.
  * DURABLE LONG JOBS — `submit(..., durable_dir=)` routes the request
    through `resilience.durable.run_durable` at the replica's worker,
    checkpointing at the executor's launch boundaries. A replica crash
    or an injected `durable.preempt` kill mid-job RESUMES the job from
    its checkpoint chain — in place, after a supervised restart, or on
    a failover replica — instead of failing the future, bit-identical
    to an uninterrupted run.

Fault sites `fleet.route` / `fleet.failover` / `fleet.shed`
(resilience.faults) thread through the paths above behind the one
`ACTIVE` flag — zero cost when no plan is armed — so the chaos soak
can kill replicas and force shed decisions deterministically.

Metrics (the fleet's registry, shared by every replica so one
`snapshot()`/`scrape()` covers the whole fleet): counters
`fleet_requests_routed`, `fleet_affinity_hits`, `fleet_affinity_spills`,
`fleet_failovers`, `fleet_requeued_requests`, `fleet_durable_jobs`,
`shed_requests`, `shed_requests_p{N}`, `shed_evictions`,
`tenant_quota_rejections`; gauges `fleet_replicas`,
`fleet_replicas_healthy`, `fleet_pressure`.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from concurrent.futures import Future
from typing import Callable, Dict, List, Optional, Tuple

from quest_tpu.resilience import faults as _F
from quest_tpu.resilience.breaker import CLOSED as _CLOSED
from quest_tpu.serve import metrics as M
from quest_tpu.serve.admission import (DeadlineExceeded, RejectedError,
                                       ShedError, TenantQuota,
                                       TenantQuotaExceeded)
from quest_tpu.serve.engine import ServeEngine


class _Ticket:
    """One fleet request: the user-facing future plus everything needed
    to resubmit it to another replica on failover."""

    __slots__ = ("future", "circuit", "kind", "state", "shots", "key",
                 "observable", "density", "durable_dir", "durable_every",
                 "tenant", "priority", "route_key", "expiry", "submit_t",
                 "replica", "inner", "requeues", "shed_cause", "seq")

    def __init__(self, circuit, kind, state, shots, key, observable,
                 density, durable_dir, durable_every, tenant, priority,
                 route_key, expiry, seq):
        self.future: Future = Future()
        self.circuit = circuit
        self.kind = kind                  # 'apply' | 'traj' | 'durable'
        self.state = state
        self.shots = shots
        self.key = key
        self.observable = observable
        self.density = density
        self.durable_dir = durable_dir
        self.durable_every = durable_every
        self.tenant = tenant
        self.priority = priority
        self.route_key = route_key        # program key for affinity
        self.expiry = expiry              # absolute monotonic or None
        self.submit_t = time.monotonic()
        self.replica: int = -1            # index currently holding it
        self.inner: Optional[Future] = None
        self.requeues = 0                 # failover hops ridden
        self.shed_cause: Optional[BaseException] = None
        self.seq = seq                    # arrival order (requeue order)


class ServeFleet:
    """N supervised ServeEngine replicas behind one submit() — the
    millions-of-users shape of the serving stack (docs/SERVING.md
    §fleet). Thread-safe `submit()`; each replica keeps its own worker
    thread, queues, supervisor, breakers and degradation ladder; the
    fleet adds program-key routing, fleet-level failover, tenant
    quotas, priority load-shedding and durable long jobs.

    Construction keywords override the QUEST_SERVE_* knobs for THIS
    fleet: `replicas` (QUEST_SERVE_REPLICAS), `tenant_quota` (a
    parse_tenant_quota dict or a bare int, QUEST_SERVE_TENANT_QUOTA),
    `shed_threshold` (QUEST_SERVE_SHED_THRESHOLD), `priorities`
    (QUEST_SERVE_PRIORITIES). Every other keyword passes through to
    each ServeEngine replica (max_wait_ms, max_queue, max_batch,
    interpret, traj_engine, restart_max, backoff_base_s,
    breaker_threshold, breaker_cooldown_s, ladder). `registry` defaults
    to the process-wide one and is SHARED with every replica, so one
    snapshot/scrape covers the fleet."""

    # the fleet RLock (reentrant: shed-eviction callbacks re-enter it)
    # and what it guards (quest-lint QL005, docs/ANALYSIS.md)
    _GUARDED_BY = {
        "_lock": ("_affinity", "_pending", "_tenant_pending", "_seq",
                  "_rr", "_failed_noted", "_closed", "_failure_cause",
                  "_retired", "_requeue_cap"),
    }

    def __init__(self, replicas: Optional[int] = None, *,
                 process: Optional[bool] = None,
                 tenant_quota=None,
                 shed_threshold: Optional[float] = None,
                 priorities: Optional[int] = None,
                 registry: Optional[M.Registry] = None,
                 **engine_kw):
        from quest_tpu.env import knob_value
        if replicas is None:
            replicas = knob_value("QUEST_SERVE_REPLICAS")
        if int(replicas) < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        if process is None:
            process = knob_value("QUEST_FLEET_PROC")
        self.process = bool(process)
        if self.process:
            import jax
            if jax.default_backend() == "tpu":
                # workers inherit this environment, and this process
                # already holds the chip: each worker would fail or hang
                # on libtpu's lock (ROADMAP R1 gives each its own chip)
                raise RuntimeError(
                    "ServeFleet(process=True) cannot run on a TPU backend: "
                    "this process holds the chip, so its worker processes "
                    "cannot load it. Use thread replicas (process=False).")
        if tenant_quota is None:
            tenant_quota = knob_value("QUEST_SERVE_TENANT_QUOTA")
        if isinstance(tenant_quota, int):
            tenant_quota = {"default": tenant_quota}
        if shed_threshold is None:
            shed_threshold = knob_value("QUEST_SERVE_SHED_THRESHOLD")
        if not (0.0 < float(shed_threshold) <= 1.0):
            raise ValueError(
                f"shed_threshold must be in (0, 1], got {shed_threshold}")
        if priorities is None:
            priorities = knob_value("QUEST_SERVE_PRIORITIES")
        if int(priorities) < 1:
            raise ValueError(f"priorities must be >= 1, got {priorities}")
        self.registry = registry if registry is not None else M.REGISTRY
        self.tenant_quota = TenantQuota(tenant_quota)
        self.shed_threshold = float(shed_threshold)
        self.priorities = int(priorities)
        # durable_mesh may be a PER-REPLICA list (heterogeneous fleet:
        # a big-mesh replica runs deep jobs sharded, a small survivor
        # resumes them elastically after failover — docs/RESILIENCE.md
        # §elastic); a single mesh (or None) applies to every replica
        meshes = engine_kw.pop("durable_mesh", None)
        if not isinstance(meshes, (list, tuple)):
            meshes = [meshes] * int(replicas)
        if len(meshes) != int(replicas):
            raise ValueError(
                f"durable_mesh list has {len(meshes)} entries for "
                f"{replicas} replicas")
        if self.process and any(m is not None for m in meshes):
            raise ValueError(
                "process replicas build their own mesh from their own "
                "environment; durable_mesh= is a thread-replica option "
                "(docs/SERVING.md §process-fleet)")
        self._engine_kw = dict(engine_kw)
        self._engines: List[ServeEngine] = [
            self._make_replica(i, durable_mesh=meshes[i])
            for i in range(int(replicas))]
        # replicas retired by the elastic scale-down path: closed but
        # kept in _engines as tombstones so ticket indices never dangle
        self._retired: set = set()
        # the requeue bound: a request may hop at most once past every
        # replica and once more (the survivor it lands on may fail
        # later too) before it fails typed — failover can never loop
        self._requeue_cap = 2 * len(self._engines)
        # REENTRANT: a shed eviction cancels the victim's inner future
        # under this lock, and Future.cancel() runs the victim's
        # completion callback synchronously on the cancelling thread —
        # which re-enters the lock to drop the victim from the ledger
        self._lock = threading.RLock()
        # insertion-ordered and BOUNDED: one entry per program family
        # would otherwise grow forever on a fleet serving one-off
        # circuits; beyond the cap the stalest pin falls out (its next
        # request just re-routes least-loaded and re-pins)
        self._affinity: "OrderedDict[tuple, int]" = OrderedDict()
        self._affinity_cap = 4096
        # insertion-ordered pending-ticket ledger: the shed victim scan
        # and the tenant pending counts read it under the fleet lock
        self._pending: "OrderedDict[int, _Ticket]" = OrderedDict()
        self._tenant_pending: Dict[str, int] = {}
        self._seq = 0
        self._rr = 0                      # round-robin tiebreak cursor
        self._failed_noted: set = set()   # replica deaths already tallied
        self._closed = False
        self._failure_cause: Optional[BaseException] = None
        self.registry.gauge("fleet_replicas").set(len(self._engines))
        self.registry.gauge("fleet_replicas_healthy").set(
            len(self._engines))
        # hot-path metric handles, hoisted once (the engine.py pattern)
        self._m_routed = self.registry.counter("fleet_requests_routed")
        self._m_aff = self.registry.counter("fleet_affinity_hits")
        self._m_spill = self.registry.counter("fleet_affinity_spills")
        self._m_pressure = self.registry.gauge("fleet_pressure")

    def _make_replica(self, idx: int, durable_mesh=None):
        """One replica at index `idx`: an in-process ServeEngine, or —
        under `process=True` / QUEST_FLEET_PROC — a serve.ipc
        ReplicaProxy fronting a supervised worker process with its own
        interpreter and JAX runtime (docs/SERVING.md §process-fleet).
        Both expose the same engine surface; the fleet logic above
        never branches on the backend again."""
        if self.process:
            from quest_tpu.serve.ipc import ReplicaProxy
            return ReplicaProxy(registry=self.registry, name=f"r{idx}",
                                **self._engine_kw)
        return ServeEngine(registry=self.registry, name=f"r{idx}",
                           durable_mesh=durable_mesh, **self._engine_kw)

    # -- introspection -----------------------------------------------------

    @property
    def state(self) -> str:
        """'running' while any replica serves | 'failed' (every replica
        exhausted its restart budget) | 'closed'."""
        # quest-lint: disable=QL005(observability fast path: racy flag read, engine.state contract)
        if self._closed:
            return "closed"
        if any(e.state == "running" for e in self._engines):
            return "running"
        return "failed"

    # duck-type attributes serve.warmup() reads off an engine: warming
    # ONE replica warms the whole fleet, because compiled programs
    # cache on the Circuit instance, process-wide (docs/BATCHING.md)
    @property
    def max_batch(self) -> int:
        return self._engines[0].max_batch

    @property
    def interpret(self) -> bool:
        return self._engines[0].interpret

    @property
    def traj_engine(self):
        return self._engines[0].traj_engine

    @property
    def replicas(self) -> int:
        """Live (non-retired) replica count — what the elastic
        autoscaler grows and shrinks; scale-down tombstones stay in
        `_engines` so in-flight ticket indices never dangle."""
        with self._lock:
            return len(self._engines) - len(self._retired)

    def plan(self, circuit, *, batch: Optional[int] = None,
             density: bool = False, dtype=None):
        """ServeEngine.plan for the fleet: one priced ProgramPlan covers
        every replica (plans are content-addressed per circuit + mode,
        not per replica — docs/PLANNING.md)."""
        return self._engines[0].plan(circuit, batch=batch,
                                     density=density, dtype=dtype)

    def stats(self) -> dict:
        """Per-replica health: state, queued depth, restart budget left
        — the figure an operator reads next to the fleet metrics —
        plus the process-wide plan-cache counters (hits vs searches:
        a warm-restarted fleet shows zero searches, docs/PLANNING.md)."""
        from quest_tpu import plan as P
        with self._lock:
            pressure = self._pressure_locked()
            retired = set(self._retired)
        return {
            "pressure": pressure,
            "process": self.process,
            "plan_cache": P.cache_stats(),
            "replicas": [
                {"name": e.name, "state": e.state, "pending": e._pending,
                 "restarts_remaining": e._supervisor.remaining,
                 "retired": i in retired}
                for i, e in enumerate(self._engines)],
        }

    # -- submit ------------------------------------------------------------

    def submit(self, circuit, state=None, shots: Optional[int] = None, *,
               key=None, deadline_s: Optional[float] = None,
               observable: Optional[Callable] = None,
               density: bool = False,
               durable_dir: Optional[str] = None,
               durable_every: Optional[int] = None,
               tenant: Optional[str] = None,
               priority: int = 0) -> Future:
        """ServeEngine.submit semantics plus the fleet layer: `tenant`
        names the submitting tenant for quota accounting (None = the
        shared 'anon' bucket), `priority` its class in
        [0, QUEST_SERVE_PRIORITIES) — higher classes shed later and may
        evict queued lower-class requests under pressure. Raises
        `TenantQuotaExceeded` over quota, `ShedError` when this request
        sheds, `RejectedError` when the fleet is closed/FAILED or every
        replica refuses the request."""
        if not (0 <= int(priority) < self.priorities):
            raise ValueError(
                f"priority must be in [0, {self.priorities}) "
                f"(QUEST_SERVE_PRIORITIES), got {priority}")
        tenant = "anon" if tenant is None else str(tenant)
        kind, route_key = self._route_key(circuit, state, shots, key,
                                          density, durable_dir)
        now = time.monotonic()
        expiry = None if deadline_s is None else now + float(deadline_s)
        with self._lock:
            if self._closed:
                self.registry.counter("serve_requests_rejected").inc()
                raise RejectedError(
                    "Invalid operation: fleet closed — submit() after "
                    "ServeFleet.close(); create a new fleet "
                    "(docs/SERVING.md §fleet).")
            healthy = self._healthy_locked()
            if not healthy:
                self.registry.counter("serve_requests_rejected").inc()
                raise RejectedError(
                    f"Invalid operation: ServeFleet is FAILED — every "
                    f"replica exhausted its restart budget; last cause: "
                    f"{self._failure_cause!r} (docs/SERVING.md §fleet)."
                ) from self._failure_cause
            try:
                self.tenant_quota.admit(
                    tenant, self._tenant_pending.get(tenant, 0))
            except TenantQuotaExceeded:
                self.registry.counter("tenant_quota_rejections").inc()
                raise
            pressure = self._pressure_locked()
            self._m_pressure.set(pressure)
            evict = None
            if pressure >= self.shed_threshold:
                evict = self._shed_locked(pressure, int(priority))
            ticket = _Ticket(circuit, kind, state, shots, key,
                             observable, density, durable_dir,
                             durable_every, tenant, int(priority),
                             route_key, expiry, self._seq)
            self._seq += 1
            idx = self._pick_replica_locked(route_key, healthy)
            ticket.replica = idx
            self._pending[id(ticket)] = ticket
            n_tenant = self._tenant_pending.get(tenant, 0) + 1
            self._tenant_pending[tenant] = n_tenant
            self.registry.gauge(f"tenant_pending_{tenant}").set(n_tenant)
        # the evicted victim's inner future was cancelled under the
        # lock; its callback (fleet lock again) may run on this thread
        # via cancel() — complete bookkeeping happens there
        if _F.ACTIVE:
            try:
                _F.check("fleet.route", program=route_key, replica=idx,
                         tenant=tenant, priority=int(priority))
            except BaseException:
                self.registry.counter("serve_faults_injected").inc()
                with self._lock:
                    self._forget_locked(ticket)
                raise
        try:
            self._submit_to(ticket, idx)
        except BaseException:
            with self._lock:
                self._forget_locked(ticket)
            raise
        self._m_routed.inc()
        if kind == "durable":
            self.registry.counter("fleet_durable_jobs").inc()
        if evict is not None:
            # tallied after the admit so the victim's shed never masks
            # a failed submit of the evictor
            self.registry.counter("shed_evictions").inc()
        # cancel-while-queued propagates to the replica: attached last,
        # so no cancel can race the submit path above (the caller only
        # holds the future once we return)
        ticket.future.add_done_callback(
            lambda f, t=ticket: self._on_outer_done(t, f))
        return ticket.future

    def _on_outer_done(self, ticket: _Ticket, f: Future) -> None:
        """Outer-future completion hook; only cancellation needs work:
        propagate it to the queued inner request (best-effort — a
        dispatched launch is never aborted, its result is simply
        discarded) and release the ledger/quota slot."""
        if not f.cancelled():
            return
        inner = ticket.inner
        if inner is not None and inner.cancel():
            self._engines[ticket.replica].reap_cancelled()
        with self._lock:
            self._forget_locked(ticket)

    def _route_key(self, circuit, state, shots, key, density,
                   durable_dir) -> Tuple[str, tuple]:
        """(kind, program key) for affinity routing — the SAME program
        identities the engines queue by (Circuit.program_key /
        trajectories.program_key), so "routed to the warm replica"
        means routed to the replica whose queues already coalesce this
        family."""
        if (state is None) == (shots is None):
            raise ValueError(
                "submit() takes exactly one of state= (apply request) "
                "or shots= (trajectory request)")
        if state is not None:
            import numpy as np
            dtype = getattr(state, "dtype", np.float32)
            base = circuit.program_key(density=density,
                                       interpret=self.interpret,
                                       dtype=dtype)
            if durable_dir is not None:
                return "durable", base + ("durable",)
            return "apply", base
        from quest_tpu import trajectories as T
        _, qkey = T.program_key(circuit, engine=self.traj_engine,
                                interpret=self.interpret)
        return "traj", qkey

    # -- routing -----------------------------------------------------------

    def _healthy_locked(self) -> List[int]:
        return [i for i, e in enumerate(self._engines)
                if e.state == "running" and i not in self._retired]

    def _pick_replica_locked(self, route_key: tuple,
                             healthy: List[int]) -> int:
        """Affinity if warm and not overloaded; else least-loaded.
        Overload = the affinity replica's queued depth runs at least a
        full launch (max_batch) deeper than the least-loaded healthy
        replica — at that point queueing behind the warm program costs
        more than a cold trace elsewhere, so the request SPILLS (the
        affinity pin stays: the next uncongested request still routes
        warm)."""
        depth = {i: self._engines[i]._pending for i in healthy}
        aff = self._affinity.get(route_key)
        least = min(healthy, key=lambda i: (depth[i], i))
        if aff is not None and aff in depth:
            self._affinity.move_to_end(route_key)
            if depth[aff] - depth[least] < self._engines[aff].max_batch:
                self._m_aff.inc()
                return aff
            self._m_spill.inc()
            return least
        # new program family: least-loaded, round-robin on ties so
        # program families spread across the fleet instead of piling
        # onto replica 0 at startup
        min_depth = depth[least]
        ties = [i for i in healthy if depth[i] == min_depth]
        idx = ties[self._rr % len(ties)]
        self._rr += 1
        self._affinity[route_key] = idx
        while len(self._affinity) > self._affinity_cap:
            self._affinity.popitem(last=False)
        return idx

    def _submit_to(self, ticket: _Ticket, idx: int) -> None:
        """Hand `ticket` to replica `idx`; tries the other healthy
        replicas on a synchronous RejectedError (that replica's queue
        is full or it failed between the pick and the submit). Raises
        only when every healthy replica refused."""
        with self._lock:
            retired = set(self._retired)
        order = [idx] + [i for i in range(len(self._engines)) if i != idx]
        last: Optional[BaseException] = None
        for i in order:
            if i in retired:
                continue
            eng = self._engines[i]
            if eng.state != "running":
                continue
            remaining = (None if ticket.expiry is None
                         else ticket.expiry - time.monotonic())
            if remaining is not None and remaining <= 0:
                raise DeadlineExceeded(
                    "Invalid operation: the request's deadline elapsed "
                    "before it could be routed to a replica "
                    "(docs/SERVING.md §fleet).")
            try:
                inner = eng.submit(
                    ticket.circuit,
                    state=ticket.state, shots=ticket.shots,
                    key=ticket.key, deadline_s=remaining,
                    observable=ticket.observable, density=ticket.density,
                    durable_dir=ticket.durable_dir,
                    durable_every=ticket.durable_every)
            except RejectedError as e:
                last = e
                continue
            ticket.replica = i
            ticket.inner = inner
            inner.add_done_callback(
                lambda fut, t=ticket: self._on_inner_done(t, fut))
            return
        with self._lock:
            self._forget_locked(ticket)
        raise last if last is not None else RejectedError(
            "Invalid operation: no replica accepted the request "
            "(docs/SERVING.md §fleet).")

    # -- completion + failover ---------------------------------------------

    def _forget_locked(self, ticket: _Ticket) -> None:
        if self._pending.pop(id(ticket), None) is not None:
            n = self._tenant_pending.get(ticket.tenant, 1) - 1
            if n:
                self._tenant_pending[ticket.tenant] = n
            else:
                self._tenant_pending.pop(ticket.tenant, None)
            self.registry.gauge(
                f"tenant_pending_{ticket.tenant}").set(n)

    def _resolve(self, ticket: _Ticket, result=None,
                 exc: Optional[BaseException] = None) -> None:
        with self._lock:
            self._forget_locked(ticket)
        f = ticket.future
        if f.done():
            return
        if not f.set_running_or_notify_cancel():
            return
        if exc is not None:
            f.set_exception(exc)
        else:
            f.set_result(result)

    def _on_inner_done(self, ticket: _Ticket, fut: Future) -> None:
        """Runs on the owning replica's worker thread (or the evicting
        submitter's, for a cancel): transfer the inner result/error to
        the user-facing future, or REQUEUE onto a survivor when the
        replica died with the request still safe to re-serve."""
        if ticket.future.cancelled():
            # the caller walked away: drop the ledger slot and never
            # failover/re-serve abandoned work
            with self._lock:
                self._forget_locked(ticket)
            return
        if fut.cancelled():
            # inner-only cancel = the shed eviction (queued-only)
            exc = ticket.shed_cause or ShedError(
                "Invalid operation: the request was load-shed while "
                "queued (docs/SERVING.md §fleet).")
            self._resolve(ticket, exc=exc)
            return
        exc = fut.exception()
        if exc is None:
            self._resolve(ticket, result=fut.result())
            return
        replica_failed = (
            self._engines[ticket.replica].state == "failed")
        # REQUEUE-SAFE: the engine resolves queued-but-undispatched
        # requests of a FAILED worker with RejectedError (the _active
        # ledger contract) — those never launched, so re-serving them
        # elsewhere cannot double-serve. Durable jobs are requeue-safe
        # even past dispatch (their retry is a checkpoint-chain resume,
        # docs/RESILIENCE.md §durable) — the engine converges them to
        # the same RejectedError on death. Everything else that died
        # WITH the replica had an unknown launch outcome: it fails
        # typed, exactly like the single-engine contract.
        requeueable = (replica_failed
                       and isinstance(exc, RejectedError)
                       and not isinstance(exc, DeadlineExceeded))
        if not requeueable:
            self._resolve(ticket, exc=exc)
            return
        with self._lock:
            self._note_failed_locked(ticket.replica)
            healthy = self._healthy_locked()
            ticket.requeues += 1
            if not healthy:
                # only a true no-survivors state defines the fleet's
                # failure cause; a single ticket exhausting its hop cap
                # while peers serve must not pollute it
                self._failure_cause = exc
            if not healthy or ticket.requeues > self._requeue_cap:
                cause = exc
                healthy = []
            else:
                target = self._pick_replica_locked(ticket.route_key,
                                                   healthy)
        if not healthy:
            self._resolve(ticket, exc=RejectedError(
                f"Invalid operation: request lost its replica and no "
                f"survivor could take it (hops: {ticket.requeues}); "
                f"last cause: {cause!r} (docs/SERVING.md §fleet)."))
            return
        if _F.ACTIVE:
            try:
                _F.check("fleet.failover", replica=ticket.replica,
                         target=target)
            except BaseException as e:  # noqa: BLE001 - typed resolve
                self.registry.counter("serve_faults_injected").inc()
                self._resolve(ticket, exc=e)
                return
        if _F.ACTIVE:
            # the requeue site proper (vs fleet.failover, the decision
            # point above): fires as the ticket is RE-SUBMITTED to its
            # survivor, so chaos plans can fail the requeue hop itself
            # — e.g. while a durable chain waits on disk — without
            # touching first-time routing (docs/RESILIENCE.md)
            try:
                _F.check("fleet.requeue", replica=ticket.replica,
                         target=target, hops=ticket.requeues,
                         durable=ticket.kind == "durable")
            except BaseException as e:  # noqa: BLE001 - typed resolve
                self.registry.counter("serve_faults_injected").inc()
                self._resolve(ticket, exc=e)
                return
        self.registry.counter("fleet_requeued_requests").inc()
        try:
            self._submit_to(ticket, target)
        except BaseException as e:      # noqa: BLE001 - typed resolve
            self._resolve(ticket, exc=e)

    def _note_failed_locked(self, idx: int) -> None:
        """A replica went FAILED: tally the failover event ONCE (the
        per-ticket tally is fleet_requeued_requests), drop its affinity
        pins (requeued and future requests re-route, rebuilding the map
        on survivors) and refresh the health gauge."""
        if idx not in self._failed_noted:
            self._failed_noted.add(idx)
            self.registry.counter("fleet_failovers").inc()
        for k in [k for k, v in self._affinity.items() if v == idx]:
            del self._affinity[k]
        self.registry.gauge("fleet_replicas_healthy").set(
            len(self._healthy_locked()))

    # -- elasticity (serve/autoscaler.py drives these) -----------------------

    def add_replica(self) -> int:
        """Grow the fleet by one replica (thread or process per the
        fleet's backend). Returns its index. The spawn happens OUTSIDE
        the fleet lock — a process boot takes seconds and submits must
        keep flowing — so two concurrent callers simply add two
        replicas."""
        with self._lock:
            if self._closed:
                raise RejectedError(
                    "Invalid operation: add_replica() after "
                    "ServeFleet.close() (docs/SERVING.md "
                    "§process-fleet).")
        eng = self._make_replica(len(self._engines))
        with self._lock:
            if self._closed:
                closed_race = True
            else:
                closed_race = False
                self._engines.append(eng)
                self._requeue_cap = 2 * len(self._engines)
                live = len(self._engines) - len(self._retired)
                self.registry.gauge("fleet_replicas").set(live)
                self.registry.gauge("fleet_replicas_healthy").set(
                    len(self._healthy_locked()))
        if closed_race:
            eng.close(timeout_s=5.0)
            raise RejectedError(
                "Invalid operation: fleet closed while the new replica "
                "was booting (docs/SERVING.md §process-fleet).")
        self.registry.counter("fleet_scale_ups").inc()
        return len(self._engines) - 1

    def remove_replica(self, timeout_s: Optional[float] = 30.0) -> int:
        """Shrink the fleet by one replica: the least-loaded running
        one retires — new requests stop routing to it immediately, its
        queued requests DRAIN (never shed by a scale-down), then it
        closes. Returns the retired index. Refuses to remove the last
        live replica."""
        with self._lock:
            if self._closed:
                raise RejectedError(
                    "Invalid operation: remove_replica() after "
                    "ServeFleet.close() (docs/SERVING.md "
                    "§process-fleet).")
            healthy = self._healthy_locked()
            if len(healthy) <= 1:
                raise ValueError(
                    "cannot retire the last live replica — scale-down "
                    "floors at 1 (QUEST_FLEET_MIN_REPLICAS governs the "
                    "autoscaler's own floor)")
            # least-loaded retires (cheapest drain); newest breaks ties
            # so long-lived warm replicas keep their affinity pins
            idx = min(healthy,
                      key=lambda i: (self._engines[i]._pending, -i))
            self._retired.add(idx)
            for k in [k for k, v in self._affinity.items() if v == idx]:
                del self._affinity[k]
            live = len(self._engines) - len(self._retired)
            self.registry.gauge("fleet_replicas").set(live)
            self.registry.gauge("fleet_replicas_healthy").set(
                len(self._healthy_locked()))
        eng = self._engines[idx]
        try:
            eng.drain(timeout_s=timeout_s)
        except RejectedError:
            pass        # already failed/closed: nothing left to drain
        except TimeoutError:
            # the drain window expired with requests still incomplete:
            # closing now would resolve them rejected, and a scale-down
            # must NEVER lose accepted work — roll the retirement back
            # (routing resumes) and let the caller retry a later tick
            with self._lock:
                self._retired.discard(idx)
                live = len(self._engines) - len(self._retired)
                self.registry.gauge("fleet_replicas").set(live)
                self.registry.gauge("fleet_replicas_healthy").set(
                    len(self._healthy_locked()))
            raise TimeoutError(
                f"scale-down of replica {idx} aborted: its drain did "
                f"not complete within timeout_s={timeout_s} — the "
                f"retirement rolled back so no accepted request is "
                f"lost (docs/SERVING.md §process-fleet)")
        eng.close(timeout_s=timeout_s)
        self.registry.counter("fleet_scale_downs").inc()
        return idx

    def scrape(self) -> str:
        """One Prometheus exposition for the whole fleet. Thread
        replicas share the fleet registry, so this is its scrape;
        process replicas keep their registries in their own
        interpreters, so the fleet merges the per-replica heartbeat
        snapshots into the fleet-level metrics (docs/SERVING.md
        §process-fleet: counters/gauges sum, histogram quantiles take
        the worst replica — the alerting-conservative merge)."""
        if not self.process:
            return self.registry.scrape()
        snaps = [self.registry.snapshot()]
        for e in self._engines:
            snap = getattr(e, "snapshot", None)
            if snap is not None:
                snaps.append(snap())
        return M.render_snapshot(M.merge_snapshots(snaps))

    # -- pressure + shedding -----------------------------------------------

    def _pressure_locked(self) -> float:
        """Fleet pressure in [0, ~1+]: queued depth over the healthy
        replicas' bounded capacity, plus each not-CLOSED breaker priced
        as one max_batch of extra backlog (a program on the degradation
        ladder serves slower, so its queue is effectively deeper).
        Breakers are counted from THIS fleet's own replicas — the
        registry's serve_breakers_open gauge is process-wide, and an
        unrelated engine sharing the default registry must not shed
        this fleet's traffic."""
        healthy = self._healthy_locked()
        if not healthy:
            return 1.0
        capacity = sum(self._engines[i]._admission.max_queue
                       for i in healthy)
        queued = sum(self._engines[i]._pending for i in healthy)
        open_breakers = sum(
            1 for i in healthy
            for br in list(self._engines[i]._breakers.values())
            if br.state != _CLOSED)
        max_batch = max(self._engines[i].max_batch for i in healthy)
        return (queued + open_breakers * max_batch) / max(capacity, 1)

    def _shed_locked(self, pressure: float,
                     priority: int) -> Optional[_Ticket]:
        """The shed decision under pressure (docs/SERVING.md §fleet):
        find the lowest-priority QUEUED ticket that can still be
        cancelled. If the incoming request outranks it, evict it (the
        victim sheds, the incoming is admitted) and return it; if the
        incoming request is itself in the lowest class, raise ShedError
        for the incoming. Either way 100% of sheds land on the lowest
        pending class until it is exhausted."""
        cause = (f"fleet pressure {pressure:.3f} >= "
                 f"QUEST_SERVE_SHED_THRESHOLD={self.shed_threshold} "
                 f"(queued depth + open-breaker backlog over healthy "
                 f"capacity)")
        victim = None
        for t in self._pending.values():
            if t.priority < priority and (
                    victim is None or t.priority < victim.priority):
                victim = t
                if victim.priority == 0:
                    break
        if _F.ACTIVE:
            try:
                _F.check("fleet.shed", pressure=pressure,
                         priority=priority, evict=victim is not None)
            except BaseException:
                self.registry.counter("serve_faults_injected").inc()
                raise
        if victim is not None:
            # cancel succeeds only while the victim is still queued at
            # its replica (admission contract); a dispatched victim is
            # not shed-able — walk on to the next lowest. The typed
            # cause is built per candidate: the ticket that actually
            # sheds must be the one the message names.
            for t in sorted(
                    (t for t in self._pending.values()
                     if t.priority < priority),
                    key=lambda t: (t.priority, t.seq)):
                t.shed_cause = ShedError(
                    f"Invalid operation: request (priority "
                    f"{t.priority}, tenant {t.tenant!r}) was load-shed "
                    f"for a priority-{priority} request: {cause} "
                    f"(docs/SERVING.md §fleet).")
                if t.inner is not None and t.inner.cancel():
                    # free the victim's queue slot NOW: the engine
                    # worker would only sweep the cancelled request at
                    # its next wake, and at the hard queue bound the
                    # evicting submit would still see a full queue and
                    # be rejected — shedding the victim for nothing
                    self._engines[t.replica].reap_cancelled()
                    self.registry.counter("shed_requests").inc()
                    self.registry.counter(
                        f"shed_requests_p{t.priority}").inc()
                    return t
                t.shed_cause = None
            # nothing evictable (all dispatched): the incoming request
            # is admitted — launches are never aborted
            return None
        self.registry.counter("shed_requests").inc()
        self.registry.counter(f"shed_requests_p{priority}").inc()
        raise ShedError(
            f"Invalid operation: request (priority {priority}) was "
            f"load-shed — it sits in the lowest pending priority class "
            f"and {cause} (docs/SERVING.md §fleet).")

    # -- drain / close -----------------------------------------------------

    def drain(self, timeout_s: Optional[float] = None) -> None:
        """Flush every queued request on every replica and block until
        each fleet future has resolved — including requests that
        failover mid-drain (the requeue lands on a survivor whose own
        worker flushes it). TimeoutError when `timeout_s` elapses with
        futures still unresolved; on a fully FAILED fleet it returns
        once every future has resolved typed (never hangs)."""
        deadline = (None if timeout_s is None
                    else time.monotonic() + timeout_s)
        with self._lock:
            closed = self._closed
        if closed:
            raise RejectedError(
                "Invalid operation: fleet closed — drain() after "
                "ServeFleet.close() (docs/SERVING.md §fleet).")
        self._drain(deadline)

    def _drain(self, deadline: Optional[float]) -> None:
        from concurrent.futures import wait as _wait
        while True:
            with self._lock:
                futures = [t.future for t in self._pending.values()]
                inners = [t.inner for t in self._pending.values()
                          if t.inner is not None]
            if not futures and not inners:
                return
            for eng in self._engines:
                if eng.state != "running":
                    continue
                step = (0.25 if deadline is None
                        else max(0.0, min(0.25,
                                          deadline - time.monotonic())))
                try:
                    eng.drain(timeout_s=step)
                except TimeoutError:
                    pass
                except RejectedError:
                    pass
            # wait on the INNER futures: the outer ones resolve from
            # inner callbacks, and waiting here (briefly) avoids a busy
            # spin while a requeued request rides a survivor's queue
            done_wait = 0.05
            if inners:
                _wait(inners, timeout=done_wait)
            else:
                time.sleep(done_wait)
            with self._lock:
                remaining = len(self._pending)
            if not remaining:
                return
            if deadline is not None and time.monotonic() >= deadline:
                raise TimeoutError(
                    f"ServeFleet.drain() timed out with {remaining} "
                    f"request(s) unresolved")

    def close(self, timeout_s: Optional[float] = None) -> None:
        """Drain, then close every replica. Idempotent. `timeout_s` is
        ONE overall budget: the drain and every engine close share it
        (a wedged 4-replica fleet closes within ~timeout_s, not 5x)."""
        deadline = (None if timeout_s is None
                    else time.monotonic() + timeout_s)
        with self._lock:
            already = self._closed
            self._closed = True
        if not already:
            try:
                self._drain(deadline)
            except TimeoutError:
                pass
        for eng in self._engines:
            step = (None if deadline is None
                    else max(0.0, deadline - time.monotonic()))
            eng.close(timeout_s=step)

    def __enter__(self) -> "ServeFleet":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
