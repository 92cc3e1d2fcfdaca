"""One queryable program-plan IR + the stack-wide priced autotuner and
its persistent plan cache (docs/PLANNING.md).

The stack grew seven-plus plan representations — fusion-plan items,
segment/sweep plans with pipeline slot geometry, topology-weighted comm
plans, Trotter frame plans, batch buckets, f64 chunk capacity, serve
program keys — each with its own stats/explain plumbing. `ProgramPlan`
is the ONE typed structure they all roll up into: the scheduled op
stream's counters, the chosen engine, fusion/segment/sweep geometry,
comm events with link attribution, chunk capacity and the pipeline slot
schedule. `Circuit.plan_stats()` now builds this IR and re-emits its
historical dict shape bit-for-bit (`ProgramPlan.stats()`), so every
existing golden keeps gating the same numbers while new consumers query
one object.

`autotune()` generalises `comm.choose_plan` (docs/DISTRIBUTED.md)
stack-wide: enumerate priced alternatives (engine x scheduler stream x
comm strategy x batch/chunk geometry) through each subsystem's OWN cost
model — segment/sweep estimates from the chip-keyed `_estimate_ms`
constants, weighted comm element-bytes from `comm._cost` (via
choose_plan's candidate table), capacity from `apply.f64_capacity_stats`
— and pick the cheapest with INCUMBENT-WINS-TIES: the engine the stack
dispatched before the autotuner existed is always in the candidate set
and only loses to a STRICTLY cheaper plan, so no golden circuit can
regress by construction (the comm planner's tie-break contract,
scripts/check_plan_golden.py).

The chosen plan is PERSISTENT: a content-addressed cache
(sha256 over the op stream's values + register kind + dtype + batch
bucket + mesh/topology + engine_mode_key -> one JSON file, versioned and
self-digested like checkpoints) stored next to the XLA compile cache
(`.jax_cache.plans`), so `serve.warmup` and ServeFleet replica start
re-price from disk: a warm restart is a LOAD, not a search — and a
corrupted or stale-version entry is skipped LOUDLY to a fresh price,
never silently consumed (the checkpoint discipline, quest_tpu/
checkpoint.py).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import sys
from typing import Any, Dict, List, Optional

import numpy as np

PLAN_FORMAT_VERSION = 3   # 3: transpile axis (raw vs rewritten stream)

# every engine the autotuner can choose between; "pergate" is the
# semantic-oracle XLA chain, the rest are the fusing/sharded families
# (docs/COMPONENTS.md)
ENGINES = ("pergate", "banded", "fused", "sharded-banded", "sharded-fused")

# projected interconnect throughput (GB/s) used to fold the comm
# planner's weighted element-bytes into the same per-application ms
# scale as the fused-engine cost model. RELATIVE, not absolute — like
# _COST_MODELS["v5p"] it only has to rank candidates consistently; the
# ab_silicon.py autotune leg prices the chooser's picks on real silicon.
_COMM_GBPS = 90.0

_CACHE_STATS = {"hits": 0, "misses": 0, "stale": 0, "corrupt": 0,
                "searches": 0, "stores": 0, "unkeyed": 0}


def cache_stats() -> dict:
    """Snapshot of the plan-cache counters: hits/misses (disk lookups),
    searches (full candidate enumerations priced this process), stores,
    and the loud-skip tallies (stale/corrupt) — the observability the
    warm-restart gate pins to zero searches (tests/test_plan.py)."""
    return dict(_CACHE_STATS)


def reset_cache_stats() -> None:
    """Zero the counters (test/bench hook — the cache files stay)."""
    for k in _CACHE_STATS:
        _CACHE_STATS[k] = 0


# ---------------------------------------------------------------------------
# the IR
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ProgramPlan:
    """The one queryable program plan: everything the engines compile
    from and the introspectors report, in JSON-native fields so the
    whole object round-trips through the persistent cache by value
    (tests/test_plan.py pins serialize->load equality)."""
    version: int               # PLAN_FORMAT_VERSION at build time
    key: Optional[str]         # content-addressed identity; None when an
    #                            operand is unrenderable (traced params)
    num_qubits: int
    n: int                     # register qubits (2x num_qubits if density)
    density: bool
    dtype: str                 # numpy dtype str of the real planes
    batch: Optional[int]
    devices: Optional[int]
    engine: str                # chosen engine (ENGINES)
    incumbent: str             # what the stack dispatched pre-autotuner
    source: str                # 'search' | 'cache' | 'build'
    cost: dict                 # chosen candidate's priced record
    candidates: dict           # name -> priced record (advisory included)
    scheduled: bool
    flat_ops: int
    planned_ops: int
    scheduler: dict            # fusion.schedule counters + enabled
    banded: dict               # fusion.plan_stats record
    fused: Optional[dict]      # pallas_band.fused_record (kernel tier only)
    batched: Optional[dict]    # pallas_band.batched_stats (batch= only)
    f64: dict                  # apply.f64_capacity_stats chunk capacity
    comm: Optional[dict]       # predicted collective schedule (devices=)
    extra: dict                # subsystem extensions (Trotter frames ...)
    grad: Optional[dict] = None  # adjoint.grad_record: differentiation
    #                              engine pricing (None: no parameters)
    transpile: Optional[dict] = None  # transpile axis: ops_in/ops_out,
    #                              sweeps_in/sweeps_out, per-pass
    #                              attribution (None: QUEST_TRANSPILE=0)

    def stats(self) -> dict:
        """The historical `Circuit.plan_stats()` dict, bit-compatible:
        same keys, same values, same insertion order as the
        pre-IR per-subsystem assembly (goldens unchanged —
        scripts/check_sweep_golden.py, check_comm_golden.py)."""
        rec = {
            "scheduled": self.scheduled,
            "flat_ops": self.flat_ops,
            "planned_ops": self.planned_ops,
            "scheduler": dict(self.scheduler),
            "banded": dict(self.banded),
        }
        if self.fused is not None:
            rec["fused"] = dict(self.fused)
        if self.batched is not None:
            rec["batched"] = dict(self.batched)
        rec["f64"] = dict(self.f64)
        if self.comm is not None:
            rec["comm"] = dict(self.comm)
        if self.grad is not None:
            rec["grad"] = dict(self.grad)
        if self.transpile is not None:
            rec["transpile"] = dict(self.transpile)
        return rec

    def to_meta(self) -> dict:
        """JSON-native serialisation, self-digested (the digest field
        itself excluded, canonical key order — checkpoint._meta_digest's
        discipline) so one flipped byte on disk is a LOUD skip."""
        meta = dataclasses.asdict(self)
        meta["plan_digest"] = _self_digest(meta)
        return meta

    @classmethod
    def from_meta(cls, meta: dict) -> "ProgramPlan":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in meta.items() if k in fields})

    def line(self) -> str:
        """The one unified plan line `explain()` emits."""
        tot = (self.cost or {}).get("total_ms")
        cost_s = (f"~{tot:.3g} ms/app" if tot is not None else "unpriced")
        src = {"cache": "cache hit", "search": "searched",
               "build": "unsearched"}.get(self.source, self.source)
        grad_s = ""
        if self.grad is not None:
            grad_s = f", grad={self.grad.get('engine', 'taped')}"
        if self.transpile is not None:
            t = self.transpile
            grad_s += (f", transpile={t['ops_in']}->{t['ops_out']} ops"
                       f"{' (chosen)' if t.get('chosen') else ''}")
        return (f"plan: engine={self.engine} {cost_s} "
                f"(incumbent={self.incumbent}{grad_s}, "
                f"{len(self.candidates)} candidate(s), {src}; "
                f"docs/PLANNING.md)")


# ---------------------------------------------------------------------------
# subsystem record assembly (the one home plan_stats reports from)
# ---------------------------------------------------------------------------

def _subsystem_records(circuit, n: int, density: bool,
                       batch: Optional[int],
                       devices: Optional[int]) -> dict:
    """Every subsystem's plan record for one circuit, through each
    subsystem's OWN planner — the single assembly `plan_stats()`,
    `build_plan()` and the autotuner all read, so the reported and the
    priced geometry cannot drift."""
    from quest_tpu.ops import apply as A
    from quest_tpu.ops import fusion as F
    from quest_tpu.ops import pallas_band as PB

    flat = circuit._flat_ops(n, density)
    enabled = F._schedule_enabled()
    # ONE scheduler run serves the stats, the planned list and pricing
    sched_ops, sstats = F.schedule(flat, n)
    sstats["enabled"] = enabled
    planned = sched_ops if enabled else flat
    rec: Dict[str, Any] = {
        "flat": flat, "sched_ops": sched_ops, "planned": planned,
        "enabled": enabled, "scheduler": sstats,
        "banded": F.plan_stats(F.plan(planned, n)),
        "fused": None, "batched": None, "swept": None,
    }
    if PB.usable(n):
        items = F.plan(planned, n, bands=PB.plan_bands(n))
        parts = PB.segment_plan(items, n)
        swept = PB.maybe_sweep(parts, n)
        rec["swept"] = swept
        rec["fused"] = PB.fused_record(parts, swept, n)
        if batch is not None:
            from quest_tpu.env import batch_bucket
            rec["batched"] = PB.batched_stats(
                swept, int(batch), batch_bucket(batch))
    elif batch is not None:
        # below the kernel tier compiled_batched rides the vmapped
        # banded program: still one dispatch per banded pass for the
        # whole bucket (the documented `batch=` parameter never
        # KeyErrors on small registers)
        from quest_tpu.env import batch_bucket
        bucket = batch_bucket(batch)
        rec["batched"] = {
            "batch": int(batch), "bucket": bucket,
            "states_per_sweep": bucket,
            "hbm_sweeps": rec["banded"]["full_state_passes"],
            "kernel_sweeps": 0, "batched_stages": 0,
        }
    rec["f64"] = A.f64_capacity_stats(n)
    rec["comm"] = None
    if devices is not None:
        from quest_tpu.parallel import sharded as S
        rec["comm"] = S.comm_plan_record(circuit.ops, n, density,
                                         int(devices))
    return rec


def build_plan(circuit, *, density: bool = False,
               batch: Optional[int] = None,
               devices: Optional[int] = None,
               dtype=np.float32) -> ProgramPlan:
    """Assemble the ProgramPlan IR for `circuit` under the CURRENT keyed
    knobs, unpriced (engine = the incumbent route, no candidate search):
    the cheap path `Circuit.plan_stats()` rides on every call. Use
    `autotune()` for the priced search + persistent cache."""
    from quest_tpu import profiling
    with profiling.annotate("quest.plan"):
        return _build_plan(circuit, density, batch, devices, dtype)


def _build_plan(circuit, density, batch, devices, dtype) -> ProgramPlan:
    n = circuit.num_qubits * 2 if density else circuit.num_qubits
    recs = _subsystem_records(circuit, n, density, batch, devices)
    incumbent = _incumbent_engine(len(circuit.ops), devices)
    return ProgramPlan(
        version=PLAN_FORMAT_VERSION,
        key=None, num_qubits=circuit.num_qubits, n=n,
        density=bool(density), dtype=np.dtype(dtype).str,
        batch=None if batch is None else int(batch),
        devices=None if devices is None else int(devices),
        engine=incumbent, incumbent=incumbent, source="build",
        cost={}, candidates={},
        scheduled=recs["enabled"], flat_ops=len(recs["flat"]),
        planned_ops=len(recs["planned"]), scheduler=recs["scheduler"],
        banded=recs["banded"], fused=recs["fused"],
        batched=recs["batched"], f64=recs["f64"], comm=recs["comm"],
        extra=_plan_extra(circuit, density),
        grad=_grad_record(circuit, density, dtype, devices),
        transpile=_transpile_record(circuit, n, density, recs)[0])


def _grad_record(circuit, density: bool, dtype,
                 devices: Optional[int]) -> Optional[dict]:
    """The plan IR's grad axis: adjoint vs taped differentiation-engine
    pricing for this circuit (adjoint.grad_record — capacity rows for
    both engines plus the engine QUEST_ADJOINT resolves to,
    incumbent-wins-ties on 'taped'). None when the circuit carries no
    parametric ops. Imported lazily like every subsystem planner so
    plan.py stays import-light."""
    from quest_tpu import adjoint as AD
    return AD.grad_record(circuit, density=density, dtype=dtype,
                          devices=devices)


_transpile_warned = False


def _transpile_record(circuit, n: int, density: bool, recs: dict):
    """The plan IR's transpile axis: (record, transpiled Circuit | None).
    The record carries the rewrite attribution plus the predicted sweep
    delta under the SAME schedule+fusion pipeline the raw stream was
    priced with; the circuit is returned only when the rewrite changed
    the stream (so autotune can enumerate its candidates). None record
    when QUEST_TRANSPILE=0 — stats() then omits the key entirely, so the
    knob-off record is bit-for-bit the pre-transpiler one
    (scripts/check_transpile_golden.py gates this)."""
    from quest_tpu.env import knob_value
    knob = knob_value("QUEST_TRANSPILE")
    if knob == "0":
        return None, None
    from quest_tpu.ops import fusion as F
    try:
        from quest_tpu import transpile as T
        tc, rep = T.transpile_cached(circuit)
    except Exception as e:             # never fatal to planning
        global _transpile_warned
        if not _transpile_warned:
            _transpile_warned = True
            print(f"[quest_tpu.plan] transpile axis skipped: {e!r}",
                  file=sys.stderr, flush=True)
        return None, None
    sweeps_in = recs["banded"]["full_state_passes"]
    rec = {"knob": knob, "ops_in": rep["ops_in"], "ops_out": rep["ops_out"],
           "sweeps_in": sweeps_in, "sweeps_out": sweeps_in,
           "passes": dict(rep["passes"]), "chosen": False}
    if not rep["changed"]:
        return rec, None
    flat_t = tc._flat_ops(n, density)
    sched_t, _ = F.schedule(flat_t, n)
    planned_t = sched_t if recs["enabled"] else flat_t
    rec["sweeps_out"] = F.plan_stats(F.plan(planned_t, n))[
        "full_state_passes"]
    return rec, tc


def _plan_extra(circuit, density: bool) -> dict:
    fn = getattr(circuit, "_plan_extra", None)
    return dict(fn(density)) if callable(fn) else {}


def _reject_dynamic(circuit, what: str) -> None:
    # mid-circuit measurements have no static plan (the measured
    # engines re-plan per branch) — same loud refusal as plan_stats
    rej = getattr(circuit, "_reject_measure", None)
    if callable(rej):
        rej(what)


def _incumbent_engine(num_ops: int, devices: Optional[int]) -> str:
    """The engine the stack dispatches WITHOUT the autotuner — the
    candidate that wins ties. Sharded registers ride the banded sharded
    engine (explain_sharded's default); unsharded applies ride the
    per-gate oracle below PERGATE_COMPILE_WARN_OPS and the banded
    auto-route above it (QUEST_APPLY_AUTOROUTE, the PR-13 footgun fix;
    0 restores the warn-only per-gate incumbent). `num_ops` is the
    circuit's op count — the same measure Circuit.apply routes on."""
    if devices is not None:
        return "sharded-banded"
    from quest_tpu.circuit import PERGATE_COMPILE_WARN_OPS
    from quest_tpu.env import knob_value
    if (num_ops > PERGATE_COMPILE_WARN_OPS
            and knob_value("QUEST_APPLY_AUTOROUTE")):
        return "banded"
    return "pergate"


# ---------------------------------------------------------------------------
# pricing (each subsystem's own cost model, folded to one ms scale)
# ---------------------------------------------------------------------------

def _pass_scale(n: int, dtype) -> float:
    # _estimate_ms's per-pass DMA constants are calibrated at 30q f32;
    # f64 planes move twice the bytes per full-state pass
    return (1 << n) / (1 << 30) * (np.dtype(dtype).itemsize / 4.0)


def _cost_rec(lo: float, hi: float, passes: int, *, compile_ops: int,
              comm_elem_bytes: float = 0.0, comm_steps: int = 0,
              bytes_per_real: int = 4, selectable: bool = True) -> dict:
    comm_ms = (comm_elem_bytes * bytes_per_real
               / (_COMM_GBPS * (1 << 30)) * 1e3)
    return {"est_ms_lo": round(float(lo), 6),
            "est_ms_hi": round(float(hi), 6),
            "hbm_passes": int(passes),
            "compile_ops": int(compile_ops),
            "comm_elem_bytes": float(comm_elem_bytes),
            "comm_steps": int(comm_steps),
            "comm_ms": round(comm_ms, 6),
            "total_ms": round((float(lo) + float(hi)) / 2 + comm_ms, 6),
            "selectable": bool(selectable)}


def _rank(cost: dict):
    """Total order over priced candidates, cheapest first: estimated
    per-application ms (compute + comm), then HBM passes, then compiled
    program size (the PR-13 pathology axis — the per-gate engine's HLO
    chain length is what compiles in minutes). The incumbent wins ties:
    selection uses STRICT <."""
    return (cost["total_ms"], cost["hbm_passes"], cost["compile_ops"])


def _price_pergate(num_flat: int, n: int, model: dict, dtype) -> dict:
    # one full-state HBM pass per routed op — the per-gate engine's
    # memory model; its compiled size IS its op chain (the footgun axis)
    ms = num_flat * model["base_pass"] * _pass_scale(n, dtype)
    return _cost_rec(ms, ms, num_flat, compile_ops=num_flat)


def _price_banded(banded_stats: dict, n: int, model: dict, dtype,
                  selectable: bool = True, comm_elem_bytes: float = 0.0,
                  comm_steps: int = 0, bytes_per_real: int = 4) -> dict:
    # fusion.plan_stats's pass model: each band/pass/diag-run is one
    # full-state pass; the XLA band einsum moves ~1.8x the state bytes
    # (_estimate_ms's passthrough multiplier)
    passes = banded_stats["full_state_passes"]
    ms = passes * 1.8 * model["base_pass"] * _pass_scale(n, dtype)
    return _cost_rec(ms, ms, passes, compile_ops=passes,
                     comm_elem_bytes=comm_elem_bytes,
                     comm_steps=comm_steps, bytes_per_real=bytes_per_real,
                     selectable=selectable)


def _price_fused(swept, n: int, model: dict, dtype,
                 selectable: bool = True) -> dict:
    # the fused engine's own chip-keyed estimate over the ACTUAL sweep
    # plan (pallas_band.sweep_plan geometry through _estimate_ms)
    from quest_tpu.circuit import _estimate_ms
    lo, hi = _estimate_ms(swept, n, model)
    passes = len(swept)
    segs = sum(1 for p in swept if p[0] == "segment")
    return _cost_rec(lo, hi, passes, compile_ops=passes + segs,
                     selectable=selectable)


def _enumerate_candidates(circuit, n: int, density: bool, dtype,
                          devices: Optional[int], topology,
                          recs: dict) -> dict:
    """Every priced alternative. Advisory candidates (the scheduler
    stream the current knob does NOT execute) are priced with
    selectable=False: the knobs stay user-owned — the autotuner reports
    what a flip would buy (the explain() discipline) but only selects
    among plans the dispatch layer can actually run."""
    from quest_tpu.circuit import _COST_MODELS
    from quest_tpu.ops import fusion as F
    from quest_tpu.ops import pallas_band as PB

    model = _COST_MODELS["v5e"]   # selection is relative; measured entry
    f32 = np.dtype(dtype).itemsize <= 4
    flat, planned = recs["flat"], recs["planned"]
    cands: Dict[str, dict] = {}
    if devices is None:
        cands["pergate"] = _price_pergate(len(flat), n, model, dtype)
        cands["banded"] = _price_banded(recs["banded"], n, model, dtype)
        if recs["swept"] is not None:
            # the kernels are f32-only: an f64 register rides the banded
            # program (compiled_batched's fallback), so the fused
            # candidate prices but cannot be selected
            cands["fused"] = _price_fused(recs["swept"], n, model, dtype,
                                          selectable=f32)
        # the OTHER scheduler stream, priced but not selectable (flip
        # QUEST_SCHEDULE to execute it)
        other = flat if recs["enabled"] else recs["sched_ops"]
        tag = "nosched" if recs["enabled"] else "sched"
        cands[f"banded:{tag}"] = _price_banded(
            F.plan_stats(F.plan(other, n)), n, model, dtype,
            selectable=False)
        return cands

    # sharded families: local pass pricing on the per-device shard plus
    # the comm planner's weighted element-bytes (comm._cost via
    # choose_plan's candidate table) folded to ms
    from quest_tpu import precision
    from quest_tpu.parallel import comm as C
    from quest_tpu.parallel import sharded as S

    g = devices.bit_length() - 1
    local_n = n - g
    topo = topology if topology is not None else C.topology(devices)
    bands = S._shard_bands(n, local_n)
    chosen, cinfo = C.choose_plan(planned, n, local_n, engine="banded",
                                  bands=bands, topo=topo)
    strategy = cinfo["strategy"]
    comm_cost = cinfo["candidates"][strategy]
    rdt = precision.real_dtype_of(precision.get_default_dtype())
    bpr = np.dtype(rdt).itemsize
    items = cinfo.get("items")
    if items is None:
        items = F.plan(chosen, n, bands=bands)
    bstats = F.plan_stats(items)
    sb = _price_banded(bstats, local_n, model, dtype,
                       comm_elem_bytes=comm_cost["elem_bytes"],
                       comm_steps=comm_cost["exchanges"],
                       bytes_per_real=bpr)
    # every comm strategy the planner priced rides along as an advisory
    # candidate (choose_plan already applied incumbent-wins-ties on
    # this axis — docs/DISTRIBUTED.md)
    for name, cc in cinfo["candidates"].items():
        if name == strategy:
            continue
        cands[f"sharded-banded:comm={name}"] = _price_banded(
            bstats, local_n, model, dtype,
            comm_elem_bytes=cc["elem_bytes"], comm_steps=cc["exchanges"],
            bytes_per_real=bpr, selectable=False)
    cands["sharded-banded"] = sb
    if PB.usable(local_n) and recs["fused"] is not None:
        # projected from the unsharded fused/banded pass ratio on the
        # local shard: the sharded fused engine runs the same segment
        # geometry per shard between the identical exchanges
        ratio = (recs["fused"]["hbm_sweeps"]
                 / max(1, recs["banded"]["full_state_passes"]))
        lo = sb["est_ms_lo"] * ratio
        cands["sharded-fused"] = _cost_rec(
            lo, sb["est_ms_hi"] * ratio,
            max(1, int(round(bstats["full_state_passes"] * ratio))),
            compile_ops=recs["fused"]["hbm_sweeps"],
            comm_elem_bytes=comm_cost["elem_bytes"],
            comm_steps=comm_cost["exchanges"], bytes_per_real=bpr,
            selectable=f32)
    return cands


# ---------------------------------------------------------------------------
# the autotuner
# ---------------------------------------------------------------------------

def autotune(circuit, state_kind: str = "pure", mesh=None, topology=None,
             dtype=np.float32, batch: Optional[int] = None,
             devices: Optional[int] = None,
             persist: Optional[bool] = None) -> ProgramPlan:
    """Price every executable (engine x comm strategy) alternative for
    `circuit` through each subsystem's own cost model and return the
    cheapest as a ProgramPlan — incumbent-wins-ties, so the chosen
    plan's priced cost is NEVER above what the stack dispatched before
    the autotuner existed (scripts/check_plan_golden.py gates this on
    every golden circuit).

    `state_kind` is 'pure' or 'density'; `mesh` (a jax Mesh) or
    `devices` selects the sharded families; `topology` overrides the
    QUEST_COMM_TOPOLOGY resolution for comm pricing. `persist=None`
    follows the QUEST_PLAN_CACHE knob: content-addressed plans load
    from / store to the persistent cache (plan_cache_dir()), so a warm
    restart prices from disk with zero searches. Circuits with
    unrenderable operands (traced parameters) cannot be
    content-addressed and always search."""
    if state_kind not in ("pure", "density"):
        raise ValueError(
            f"state_kind must be 'pure' or 'density', got {state_kind!r}")
    _reject_dynamic(circuit, "plan.autotune")
    density = state_kind == "density"
    if mesh is not None:
        if devices is not None:
            raise ValueError("pass mesh= or devices=, not both")
        devices = int(np.asarray(mesh.devices).size)
    n = circuit.num_qubits * 2 if density else circuit.num_qubits
    if persist is None:
        from quest_tpu.env import knob_value
        persist = bool(knob_value("QUEST_PLAN_CACHE"))
    key = plan_key(circuit, density=density, dtype=dtype, batch=batch,
                   devices=devices, topology=topology)
    if key is None:
        _CACHE_STATS["unkeyed"] += 1
    elif persist:
        cached = load_plan(key)
        if cached is not None:
            _CACHE_STATS["hits"] += 1
            return cached
        _CACHE_STATS["misses"] += 1
    _CACHE_STATS["searches"] += 1
    recs = _subsystem_records(circuit, n, density, batch, devices)
    cands = _enumerate_candidates(circuit, n, density, dtype, devices,
                                  topology, recs)
    incumbent = _incumbent_engine(len(circuit.ops), devices)
    # the transpile axis: price the rewritten stream's candidates
    # alongside the raw ones ("<engine>:transpiled"). Under 'auto' the
    # RAW incumbent stays the tie-winner, so no golden circuit can
    # regress by construction; '1' prefers the transpiled family
    # whenever the rewrite changed the stream.
    tr_rec, tr_c = _transpile_record(circuit, n, density, recs)
    if tr_c is not None:
        recs_t = _subsystem_records(tr_c, n, density, batch, devices)
        for cname, cval in _enumerate_candidates(
                tr_c, n, density, dtype, devices, topology,
                recs_t).items():
            cands[f"{cname}:transpiled"] = cval
    selectable = {k: v for k, v in cands.items() if v["selectable"]}
    assert incumbent in selectable, (incumbent, sorted(cands))
    best = incumbent
    pool = selectable
    if tr_rec is not None and tr_rec["knob"] == "1" and tr_c is not None:
        inc_t = _incumbent_engine(len(tr_c.ops), devices) + ":transpiled"
        pool_t = {k: v for k, v in selectable.items()
                  if k.endswith(":transpiled")}
        if inc_t in pool_t:
            best, pool = inc_t, pool_t
    for name in sorted(pool):
        if _rank(pool[name]) < _rank(pool[best]):
            best = name
    if tr_rec is not None:
        tr_rec["chosen"] = best.endswith(":transpiled")
    plan = ProgramPlan(
        version=PLAN_FORMAT_VERSION,
        key=key, num_qubits=circuit.num_qubits, n=n,
        density=density, dtype=np.dtype(dtype).str,
        batch=None if batch is None else int(batch),
        devices=None if devices is None else int(devices),
        engine=best, incumbent=incumbent, source="search",
        cost=cands[best], candidates=cands,
        scheduled=recs["enabled"], flat_ops=len(recs["flat"]),
        planned_ops=len(recs["planned"]), scheduler=recs["scheduler"],
        banded=recs["banded"], fused=recs["fused"],
        batched=recs["batched"], f64=recs["f64"], comm=recs["comm"],
        extra=_plan_extra(circuit, density),
        grad=_grad_record(circuit, density, dtype, devices),
        transpile=tr_rec)
    if persist and key is not None:
        save_plan(plan)
    return plan


# ---------------------------------------------------------------------------
# content addressing
# ---------------------------------------------------------------------------

def _render_operand(x) -> Optional[list]:
    """JSON-native fingerprint of a gate operand, or None when the
    value cannot be content-addressed (a traced parameter): such
    circuits still autotune, they just never cache."""
    if x is None:
        return ["none"]
    try:
        arr = np.asarray(x)
        if arr.dtype == object:
            return None
        return ["arr", list(arr.shape), arr.dtype.str,
                hashlib.sha256(
                    np.ascontiguousarray(arr).tobytes()).hexdigest()[:16]]
    except Exception:
        return None


def _op_fingerprint(op) -> Optional[list]:
    operand = _render_operand(op.operand)
    if operand is None:
        return None
    return [op.kind, list(op.targets), list(op.controls),
            list(op.cstates or []), operand]


def plan_key(circuit, *, density: bool, dtype, batch: Optional[int],
             devices: Optional[int], topology=None) -> Optional[str]:
    """Content-addressed plan identity: sha256 over the op stream's
    VALUES plus everything the priced answer depends on — register
    kind, plane dtype, batch bucket, device count, the topology model
    and engine_mode_key() (a keyed-knob flip is a different plan, the
    compiled-program cache-key discipline). Returns None when an
    operand is unrenderable (traced parameters) — never a wrong key."""
    from quest_tpu.env import batch_bucket, engine_mode_key
    ops_fp: List[list] = []
    for op in circuit.ops:
        fp = _op_fingerprint(op)
        if fp is None:
            return None
        ops_fp.append(fp)
    topo_desc = None
    if devices is not None:
        from quest_tpu.parallel import comm as C
        topo = topology if topology is not None else C.topology(devices)
        topo_desc = topo.describe(devices)
    ident = {
        "format_version": PLAN_FORMAT_VERSION,
        "num_qubits": circuit.num_qubits,
        "ops": ops_fp,
        "density": bool(density),
        "dtype": np.dtype(dtype).str,
        "bucket": None if batch is None else batch_bucket(int(batch)),
        "devices": devices,
        "topology": topo_desc,
        "mode": [[k, repr(v)] for k, v in engine_mode_key()],
    }
    return hashlib.sha256(json.dumps(
        ident, sort_keys=True, separators=(",", ":")).encode()).hexdigest()


# ---------------------------------------------------------------------------
# the persistent cache (versioned + self-digested, loud-skip on damage)
# ---------------------------------------------------------------------------

def _self_digest(meta: dict) -> str:
    clean = {k: v for k, v in meta.items() if k != "plan_digest"}
    return hashlib.sha256(json.dumps(
        clean, sort_keys=True, separators=(",", ":")).encode()).hexdigest()


def plan_cache_dir(create: bool = True) -> Optional[str]:
    """The plan cache directory: QUEST_PLAN_CACHE_DIR, defaulting to
    `<compile cache>.plans` — literally next to the XLA compile cache
    (precision.compile_cache_dir), so the two warm-restart stores
    travel together. None when the location is unwritable (callers
    fall back to searching, loudly counted)."""
    from quest_tpu.env import knob_value
    from quest_tpu.precision import compile_cache_dir
    path = knob_value("QUEST_PLAN_CACHE_DIR")
    if path is None:
        path = compile_cache_dir().rstrip(os.sep) + ".plans"
    if create:
        try:
            os.makedirs(path, exist_ok=True)
            if not os.access(path, os.W_OK):
                return None
        except OSError:
            return None
    return path


def _loud_skip(path: str, why: str, counter: str) -> None:
    _CACHE_STATS[counter] += 1
    print(f"[quest_tpu.plan] {counter.upper()} plan-cache entry "
          f"{path!r} skipped to a fresh price: {why} (never silently "
          f"consumed — docs/PLANNING.md)", file=sys.stderr, flush=True)


def save_plan(plan: ProgramPlan) -> Optional[str]:
    """Persist a searched plan (atomic tmp+rename; versioned and
    self-digested). Returns the path, or None when the cache directory
    is unavailable."""
    if plan.key is None:
        return None
    d = plan_cache_dir()
    if d is None:
        return None
    path = os.path.join(d, f"plan-{plan.key}.json")
    tmp = path + f".tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(plan.to_meta(), f, sort_keys=True)
        os.replace(tmp, path)
    except OSError as e:
        print(f"[quest_tpu.plan] could not persist plan {path!r}: "
              f"{e!r}", file=sys.stderr, flush=True)
        try:
            os.remove(tmp)
        except OSError:
            pass
        return None
    _CACHE_STATS["stores"] += 1
    return path


def load_plan(key: str) -> Optional[ProgramPlan]:
    """Load a persisted plan by content key. A missing entry returns
    None quietly (a cold cache is normal); a CORRUPTED or
    STALE-VERSION entry returns None LOUDLY (stderr + counter) so the
    caller re-prices — a damaged plan is never silently consumed (the
    checkpoint discipline)."""
    d = plan_cache_dir()
    if d is None:
        return None
    path = os.path.join(d, f"plan-{key}.json")
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            meta = json.load(f)
    except (OSError, ValueError) as e:
        _loud_skip(path, f"unreadable JSON ({e!r})", "corrupt")
        return None
    version = meta.get("version")
    if version != PLAN_FORMAT_VERSION:
        _loud_skip(path, f"format version {version!r} != "
                   f"{PLAN_FORMAT_VERSION}", "stale")
        return None
    digest = meta.get("plan_digest")
    if digest != _self_digest(meta):
        _loud_skip(path, "self-digest mismatch (bytes damaged on disk)",
                   "corrupt")
        return None
    if meta.get("key") != key:
        _loud_skip(path, "content key mismatch (entry filed under the "
                   "wrong identity)", "corrupt")
        return None
    try:
        plan = ProgramPlan.from_meta(meta)
    except TypeError as e:
        _loud_skip(path, f"schema mismatch ({e!r})", "corrupt")
        return None
    return dataclasses.replace(plan, source="cache")


# ---------------------------------------------------------------------------
# geometry helpers for the satellite surfaces
# ---------------------------------------------------------------------------

def sweep_chunk(total: int, num_qubits: int, *, density: bool = False,
                dtype=np.float32) -> int:
    """Priced chunk size for variational.sweep(chunk='auto'): the
    largest batch bucket whose live amplitudes (chunk x both planes x
    2^n at `dtype`, x3 for the ansatz's working set) fit the capacity
    model's HBM budget (apply.f64_capacity_stats — the same chunking
    contract the f64 limb path sizes against), clamped to [1, total]."""
    from quest_tpu.env import batch_bucket
    from quest_tpu.ops import apply as A
    n = num_qubits * 2 if density else num_qubits
    hbm = A.f64_capacity_stats(n)["hbm_bytes"]
    state_bytes = 2 * np.dtype(dtype).itemsize * (1 << n)
    fit = max(1, int(hbm // (3 * state_bytes)))
    chunk = 1
    while chunk * 2 <= min(fit, max(1, int(total))):
        chunk *= 2
    return batch_bucket(min(chunk, max(1, int(total))))
