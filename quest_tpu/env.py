"""Execution environment: device mesh and sharding policy.

The reference's QuESTEnv is {rank, numRanks} over MPI (QuEST.h:199-203,
QuEST_cpu_distributed.c:129-160, power-of-2 ranks required). The TPU-native
equivalent is a 1-D `jax.sharding.Mesh` over the amplitude axis: a register
whose amplitude count is divisible by the mesh size is laid out with its
top log2(num_devices) qubits "global" (one contiguous chunk per device),
exactly the reference's chunk layout (QuEST_cpu.c:1280-1312) — so gates on
low qubits are embarrassingly local and gates on global qubits lower to XLA
collectives over ICI.

Multi-host pods: pass `distributed=True` to have jax.distributed.initialize
wire up DCN before the mesh is built (the analogue of MPI_Init).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AMP_AXIS = "amp"


# ---------------------------------------------------------------------------
# QUEST_* knob registry — the single source of truth for every runtime
# environment knob (ISSUE 2 satellite; the analogue of the reference's
# one-table validation front-end, QuEST_validation.c). Each entry records
# the validating parser (raises ValueError on malformed input — knobs
# parse LOUDLY), the default, and the knob's compile scope:
#
#   keyed        read at TRACE time inside compiled paths; its effective
#                value is part of engine_mode_key(), so every compiled-
#                program cache (circuit-level engines AND the eager
#                per-gate jit workers) misses when it flips (the
#                stale-program class of ADVICE r4 item 2 / r5 item 2)
#   import_once  resolved once per process (module import or first
#                compile) and deliberately never re-read — stale-proof
#                by construction; mid-process flips are ignored, sweeps
#                go through subprocesses (pallas_band's block knobs)
#   runtime      read outside any compiled path (host tooling, bench,
#                test harness); can never return a stale program
#
# quest-lint enforces the registry statically: QL001 checks that every
# knob read reachable from a jitted/fused/Pallas path is keyed or
# import_once, QL004 that every read routes through knob_value()'s
# validating parser (quest_tpu/analysis/). The knob-flip audit
# (quest_tpu/analysis/audit.py) checks the keyed contract dynamically.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Knob:
    """One registered QUEST_* environment knob."""
    name: str                       # full variable name, e.g. QUEST_SCHEDULE
    parse: Callable[[str], Any]     # raw string -> value; ValueError if bad
    default: Any                    # value when unset (callable = dynamic)
    scope: str                      # "keyed" | "import_once" | "runtime"
    layer: str                      # subsystem: apply|planner|host|kernel|
                                    #            infra|bench|test|build|serve
    doc: str                        # one-liner (docs/CONFIG.md parity)
    malformed: Optional[str] = None     # sample raw value parse() must
                                        # reject (None: every string parses)
    flips: Optional[Tuple[str, str]] = None  # two raw values with distinct
                                             # effective values (flip audit)
    current: Optional[Callable[[], Any]] = None  # effective-value getter
                                                 # override (setter-backed
                                                 # knobs); default reads env


def _bool01(name: str) -> Callable[[str], bool]:
    def parse(raw: str) -> bool:
        if raw not in ("0", "1"):
            raise ValueError(f"{name} must be '0' or '1', got {raw!r}")
        return raw == "1"
    return parse


def _int_range(name: str, lo: Optional[int] = None,
               hi: Optional[int] = None) -> Callable[[str], int]:
    def parse(raw: str) -> int:
        try:
            v = int(raw)
        except ValueError:
            raise ValueError(f"{name} must be an integer, got {raw!r}")
        if (lo is not None and v < lo) or (hi is not None and v > hi):
            raise ValueError(
                f"{name} must be in [{lo}, {'inf' if hi is None else hi}], "
                f"got {v}")
        return v
    return parse


def _parse_f64_chunk(raw: str) -> int:
    try:
        c = int(raw)
    except ValueError:
        raise ValueError(
            f"QUEST_F64_CHUNK must be an integer element count, got {raw!r}")
    if c < 0 or (c and c & (c - 1)):
        raise ValueError(
            f"QUEST_F64_CHUNK must be 0 (chunking off) or a positive "
            f"power of two (state sizes are powers of two, so any other "
            f"chunk cannot divide the row axis), got {c}")
    return c


def _parse_matmul_precision(raw: str):
    table = {"default": jax.lax.Precision.DEFAULT,
             "high": jax.lax.Precision.HIGH,
             "highest": jax.lax.Precision.HIGHEST}
    if raw.lower() not in table:
        raise ValueError(
            f"matmul precision must be one of {sorted(table)} "
            f"(via QUEST_MATMUL_PRECISION or set_matmul_precision), "
            f"got {raw!r}")
    return table[raw.lower()]


def _parse_choice(name: str, choices: Tuple[str, ...]) -> Callable[[str], str]:
    def parse(raw: str) -> str:
        if raw not in choices:
            raise ValueError(f"{name} must be one of {sorted(choices)}, "
                             f"got {raw!r}")
        return raw
    return parse


def _parse_engine_ladder(raw: str) -> Tuple[str, ...]:
    ladder = tuple(raw.split(","))
    bad = [e for e in ladder if e not in ("banded", "fused", "xla", "host")]
    if bad:
        raise ValueError(f"unknown engine(s) in QUEST_BENCH_ENGINES: {bad}")
    return ladder


def _parse_exchange_slices(raw: str) -> int:
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(
            f"QUEST_EXCHANGE_SLICES must be an integer, got {raw!r}")
    if v < 1 or v > 1024 or (v & (v - 1)):
        raise ValueError(
            f"QUEST_EXCHANGE_SLICES must be a power of two in [1, 1024] "
            f"(exchange blocks are power-of-two sized, so any other "
            f"slice count cannot divide them), got {v}")
    return v


def _parse_comm_topology(raw: str):
    """QUEST_COMM_TOPOLOGY grammar: '0' (flat — reproduce the PR-8
    planner bit-for-bit) or 'hosts=H[,ici=X][,dci=Y]' — devices grouped
    into H hosts (contiguous, matching jax's host-major device order),
    intra-host links weighted X (default 1) and cross-host links Y
    (default 4). Returns 0 or a (hosts, ici, dci) tuple; comm.topology()
    turns it into the Topology the planner prices with."""
    if raw == "0":
        return 0
    hosts, ici, dci = None, 1.0, 4.0
    for part in raw.split(","):
        if "=" not in part:
            raise ValueError(
                f"QUEST_COMM_TOPOLOGY must be '0' or "
                f"'hosts=H[,ici=X][,dci=Y]', got {raw!r}")
        key, val = part.split("=", 1)
        key = key.strip()
        try:
            if key == "hosts":
                hosts = int(val)
            elif key in ("ici", "dci"):
                v = float(val)
                if not (v > 0):
                    raise ValueError
                if key == "ici":
                    ici = v
                else:
                    dci = v
            else:
                raise KeyError(key)
        except KeyError:
            raise ValueError(
                f"unknown QUEST_COMM_TOPOLOGY key {key!r} in {raw!r} "
                f"(known: hosts, ici, dci)")
        except ValueError:
            raise ValueError(
                f"QUEST_COMM_TOPOLOGY {key}= must be a positive "
                f"{'integer' if key == 'hosts' else 'number'}, "
                f"got {val!r}")
    if hosts is None:
        raise ValueError(
            f"QUEST_COMM_TOPOLOGY must name hosts= (got {raw!r})")
    if hosts < 1 or hosts & (hosts - 1):
        raise ValueError(
            f"QUEST_COMM_TOPOLOGY hosts must be a power of two >= 1 "
            f"(device counts are powers of two, so any other host count "
            f"cannot group them evenly), got {hosts}")
    return (hosts, ici, dci)


def _parse_dci_slices(raw: str) -> int:
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(
            f"QUEST_EXCHANGE_SLICES_DCI must be an integer, got {raw!r}")
    if v < 0 or v > 1024 or (v and v & (v - 1)):
        raise ValueError(
            f"QUEST_EXCHANGE_SLICES_DCI must be 0 (follow "
            f"QUEST_EXCHANGE_SLICES) or a power of two in [1, 1024], "
            f"got {v}")
    return v


def _parse_pos_float(name: str) -> Callable[[str], float]:
    def parse(raw: str) -> float:
        try:
            v = float(raw)
        except ValueError:
            raise ValueError(f"{name} must be a float, got {raw!r}")
        if not (v > 0.0):
            raise ValueError(f"{name} must be > 0, got {v}")
        return v
    return parse


def _parse_nonneg_float(name: str) -> Callable[[str], float]:
    def parse(raw: str) -> float:
        try:
            v = float(raw)
        except ValueError:
            raise ValueError(f"{name} must be a float, got {raw!r}")
        if not (v >= 0.0):
            raise ValueError(f"{name} must be >= 0, got {v}")
        return v
    return parse


def _parse_fault_plan(raw: str):
    # the resilience package is stdlib-only at import time, so the lazy
    # import cannot cycle back into env.py's module load
    from quest_tpu.resilience import faults
    return faults.parse_plan(raw)


def _parse_tenant_quota(raw: str):
    # admission.py imports only stdlib + validation (numpy) — the lazy
    # import cannot cycle back into env.py's module load
    from quest_tpu.serve.admission import parse_tenant_quota
    return parse_tenant_quota(raw)


def _default_tenant_quota():
    from quest_tpu.serve.admission import DEFAULT_TENANT_QUOTA
    return {"default": DEFAULT_TENANT_QUOTA}


def _parse_shed_threshold(raw: str) -> float:
    try:
        v = float(raw)
    except ValueError:
        raise ValueError(
            f"QUEST_SERVE_SHED_THRESHOLD must be a float, got {raw!r}")
    if not (0.0 < v <= 1.0):
        raise ValueError(
            f"QUEST_SERVE_SHED_THRESHOLD must be in (0, 1] — a fraction "
            f"of fleet queue capacity (1.0 disables shedding below the "
            f"hard queue bound), got {v}")
    return v


def _default_f64_mxu() -> bool:
    # on for TPU backends (native f64 dots are software-emulated there —
    # the measured 9 gates/s @ 26q wall, VERDICT r4), off elsewhere
    try:
        return jax.default_backend() == "tpu"
    except Exception:       # pragma: no cover - no backend
        return False


def _current_matmul_precision():
    from quest_tpu import precision
    return precision.matmul_precision()


_KNOB_LIST = (
    Knob("QUEST_MATMUL_PRECISION", _parse_matmul_precision,
         jax.lax.Precision.HIGHEST,
         scope="keyed", layer="apply",
         doc="lax.Precision tier for state-amplitude contractions: "
             "default, high or highest (default: highest — bit-exact f32)",
         malformed="ultra", flips=("highest", "high"),
         current=_current_matmul_precision),
    Knob("QUEST_F64_MXU", _bool01("QUEST_F64_MXU"), _default_f64_mxu,
         scope="keyed", layer="apply",
         doc="f64 band contractions ride the MXU limb scheme: 1/0 "
             "(default: 1 on TPU backends, 0 elsewhere)",
         malformed="yes", flips=("0", "1")),
    Knob("QUEST_F64_CHUNK", _parse_f64_chunk, 1 << 24,
         scope="keyed", layer="apply",
         doc="row-chunk size in elements for the f64 limb path; 0 turns "
             "chunking off (default: 2^24)",
         malformed="1000", flips=(str(1 << 24), str(1 << 12))),
    Knob("QUEST_SCHEDULE", _bool01("QUEST_SCHEDULE"), True,
         scope="keyed", layer="planner",
         doc="commutation-aware gate scheduler in front of the fusing "
             "engines' planners: 1/0 (default: 1)",
         malformed="2", flips=("1", "0")),
    Knob("QUEST_ADJOINT", _parse_choice("QUEST_ADJOINT", ("auto", "0", "1")),
         "auto",
         scope="keyed", layer="planner",
         doc="gradient engine for adjoint.value_and_grad: auto (planner "
             "prices adjoint vs taped per width), 0 = force taped "
             "autodiff, 1 = force the adjoint backward walk "
             "(default: auto)",
         malformed="2", flips=("auto", "1")),
    Knob("QUEST_TRANSPILE",
         _parse_choice("QUEST_TRANSPILE", ("auto", "0", "1")),
         "auto",
         scope="keyed", layer="planner",
         doc="circuit transpiler (docs/TRANSPILE.md): auto (the planner "
             "prices raw vs transpiled per circuit, incumbent-wins-"
             "ties), 0 = never rewrite, 1 = prefer the transpiled "
             "stream whenever it changed (default: auto)",
         malformed="2", flips=("auto", "0")),
    Knob("QUEST_FUSED_SCAN", _bool01("QUEST_FUSED_SCAN"), False,
         scope="keyed", layer="planner",
         doc="lax.scan over repeated-structure kernel segments in the "
             "fused engine (program-size lever): 1/0 (default: 0)",
         malformed="on", flips=("0", "1")),
    Knob("QUEST_SWEEP_FUSION", _bool01("QUEST_SWEEP_FUSION"), True,
         scope="keyed", layer="planner",
         doc="sweep-fusion layer: merge consecutive geometry-compatible "
             "kernel segments (incl. across unrolled iterations) into one "
             "HBM sweep per kernel launch: 1/0 (default: 1)",
         malformed="2", flips=("1", "0")),
    Knob("QUEST_EXPEC_FUSION", _bool01("QUEST_EXPEC_FUSION"), True,
         scope="keyed", layer="planner",
         doc="grouped sweep-fused Pauli-sum expectation engine "
             "(docs/EXPECTATION.md): 1/0 (default: 1; 0 restores the "
             "legacy per-term workspace-pass evaluation)",
         malformed="2", flips=("1", "0")),
    Knob("QUEST_EXPEC_MAX_MASKS",
         _int_range("QUEST_EXPEC_MAX_MASKS", 1), 64,
         scope="keyed", layer="planner",
         doc="max off-diagonal flip-mask groups co-riding one fused "
             "expectation sweep — the expectation engine's stage "
             "budget (default: 64)",
         malformed="0", flips=("64", "1")),
    Knob("QUEST_TROTTER_FUSION", _bool01("QUEST_TROTTER_FUSION"), True,
         scope="keyed", layer="planner",
         doc="pooled Trotter emission + fused-engine dispatch for the "
             "evolution workload (docs/EVOLUTION.md): 1/0 (default: 1; "
             "0 restores the legacy per-term emission dispatched "
             "through the eager per-term workers — one flip-form pass "
             "per term application, the honest bench baseline)",
         malformed="2", flips=("1", "0")),
    Knob("QUEST_COMM_PLAN", _bool01("QUEST_COMM_PLAN"), True,
         scope="keyed", layer="planner",
         doc="communication planner for the sharded engines "
             "(docs/DISTRIBUTED.md): pick the cheapest of plain/"
             "coalesced-reshard/relabel-events/lazy per circuit by "
             "predicted comm_stats bytes: 1/0 (default: 1; 0 restores "
             "the fixed legacy policies)",
         malformed="2", flips=("1", "0")),
    Knob("QUEST_EXCHANGE_SLICES", _parse_exchange_slices, 1,
         scope="keyed", layer="planner",
         doc="collective-permute slices each sharded pair exchange "
             "splits into, so transfer overlaps the consuming compute "
             "on real ICI (default: 1; power of two; NOT "
             "silicon-validated — A/B vs 1 on first chip run)",
         malformed="3", flips=("1", "4")),
    Knob("QUEST_EXCHANGE_SLICES_DCI", _parse_dci_slices, 0,
         scope="keyed", layer="planner",
         doc="collective-permute slices for pair exchanges that CROSS "
             "the host boundary (DCI links under QUEST_COMM_TOPOLOGY); "
             "0 (default) follows QUEST_EXCHANGE_SLICES — slower links "
             "want finer slicing so transfer overlaps compute longer "
             "(power of two; NOT silicon-validated — A/B on first "
             "multi-host run, scripts/ab_silicon.py)",
         malformed="3", flips=("0", "4")),
    Knob("QUEST_COMM_TOPOLOGY", _parse_comm_topology, None,
         scope="keyed", layer="planner",
         doc="hierarchical interconnect model for the comm planner "
             "(docs/DISTRIBUTED.md §topology): 'hosts=H[,ici=X][,dci=Y]' "
             "groups the mesh into H hosts with per-link cost weights "
             "(defaults ici=1, dci=4); 0 forces the flat single-tier "
             "model (bit-for-bit the PR-8 planner); unset auto-derives "
             "host grouping from jax.devices() process ids",
         malformed="hosts=three", flips=("0", "hosts=2")),
    Knob("QUEST_BATCH_BUCKET",
         _parse_choice("QUEST_BATCH_BUCKET", ("pow2", "off")), "pow2",
         scope="keyed", layer="planner",
         doc="batch-size bucketing for the batched engines: pow2 rounds a "
             "requested batch B up to the next power of two so mixed batch "
             "sizes share one compiled program; off compiles exact sizes "
             "(default: pow2)",
         malformed="4", flips=("pow2", "off")),
    Knob("QUEST_APPLY_AUTOROUTE", _bool01("QUEST_APPLY_AUTOROUTE"), True,
         scope="keyed", layer="planner",
         doc="Circuit.apply auto-routes through the banded engine above "
             "PERGATE_COMPILE_WARN_OPS flat ops (the per-gate XLA chain "
             "compiles pathologically slowly there — docs/PLANNING.md): "
             "1/0 (default: 1; 0 restores the legacy warn-only per-gate "
             "dispatch)",
         malformed="2", flips=("1", "0")),
    Knob("QUEST_PLAN_CACHE", _bool01("QUEST_PLAN_CACHE"), True,
         scope="runtime", layer="infra",
         doc="persistent content-addressed plan cache for plan.autotune "
             "(docs/PLANNING.md): 1/0 (default: 1; 0 prices every "
             "autotune call fresh — host-side planning only, never "
             "inside a traced program)"),
    Knob("QUEST_PLAN_CACHE_DIR", str, None,
         scope="runtime", layer="infra",
         doc="plan-cache directory for plan.autotune (default: the "
             "resolved compile-cache directory + '.plans')"),
    Knob("QUEST_HOST_BLOCK", _int_range("QUEST_HOST_BLOCK", 1, 30), 17,
         scope="keyed", layer="host",
         doc="log2 amplitudes per cache block of the native host engine "
             "(default: 17 = 1 MiB blocks)",
         malformed="big", flips=("17", "15")),
    Knob("QUEST_FUSED_NBUF", _int_range("QUEST_FUSED_NBUF", 2, 8), 3,
         scope="import_once", layer="kernel",
         doc="VMEM slot buffers in the manually pipelined Pallas driver "
             "(default: 3); malformed values warn and fall back",
         malformed="9"),
    Knob("QUEST_FUSED_PIPELINE", _bool01("QUEST_FUSED_PIPELINE"), True,
         scope="keyed", layer="kernel",
         doc="decoupled multi-buffer sweep pipeline in the manually "
             "pipelined Pallas driver: separate in-slot and out-slot "
             "rings with independent DMA semaphore chains, so the HBM "
             "read stream, the stage chain and the HBM write stream "
             "each run a full step ahead (docs/SWEEPS.md): 1/0 "
             "(default: 1; 0 restores the legacy in-place NBUF slot "
             "driver for the silicon A/B)",
         malformed="2", flips=("1", "0")),
    Knob("QUEST_ROWS_EFF_BITS", _int_range("QUEST_ROWS_EFF_BITS", 3), None,
         scope="import_once", layer="kernel",
         doc="log2 block rows per Pallas kernel step (default: auto from "
             "VMEM); upper bound checked at first compile",
         malformed="x"),
    Knob("QUEST_FUSED_DRIVER",
         _parse_choice("QUEST_FUSED_DRIVER", ("pipelined", "grid")),
         "pipelined",
         scope="import_once", layer="kernel",
         doc="Pallas segment driver: pipelined (manual slot DMA, default) "
             "or grid (automatic BlockSpec pipeline)",
         malformed="turbo"),
    Knob("QUEST_NATIVE_LIB", str, None,
         scope="runtime", layer="host",
         doc="override path of the native host-engine shared library "
             "(e.g. the ASan build in CI)"),
    Knob("QUEST_HBM_BYTES", _int_range("QUEST_HBM_BYTES", 1), None,
         scope="runtime", layer="bench",
         doc="per-device HBM capacity in bytes for the bench's OOM gate "
             "and the host-side capacity models (default: the device's "
             "bytes_limit in the bench, a v5e in the models)",
         malformed="16G"),
    Knob("QUEST_BENCH_ENGINES", _parse_engine_ladder, None,
         scope="runtime", layer="bench",
         doc="comma-separated engine fallback ladder for bench.py "
             "(default: fused,banded,xla on TPU; host,banded,xla off it)",
         malformed="warp,xla"),
    Knob("QUEST_TEST_PLATFORM", str, "cpu",
         scope="runtime", layer="test",
         doc="JAX platform the test suite pins before importing jax "
             "(conftest.py; tpu on the machine that holds the chip)"),
    Knob("QUEST_SLOW_TESTS", _bool01("QUEST_SLOW_TESTS"), False,
         scope="runtime", layer="test",
         doc="opt into multi-minute subprocess tests (16-device dryrun)",
         malformed="yes"),
    Knob("QUEST_METRICS_FILE", str, "chiprun_out/tpu_smoke_metrics.log",
         scope="runtime", layer="test",
         doc="file collecting on-chip smoke-test measurement lines "
             "(pytest capture swallows stderr of passing tests)"),
    Knob("QUEST_MEMCHECK", _bool01("QUEST_MEMCHECK"), False,
         scope="runtime", layer="build",
         doc="build the native host engine under AddressSanitizer "
             "(native/Makefile, CI job; shell-only)",
         malformed="on"),
    Knob("QUEST_SERVE_MAX_WAIT_MS",
         _int_range("QUEST_SERVE_MAX_WAIT_MS", 0), 5,
         scope="runtime", layer="serve",
         doc="max milliseconds a serve request may wait for its bucket "
             "to fill before the partial batch launches (default: 5); "
             "0 = no coalescing, every request launches alone (the "
             "bench baseline mode)",
         malformed="-1"),
    Knob("QUEST_SERVE_MAX_QUEUE",
         _int_range("QUEST_SERVE_MAX_QUEUE", 1), 1024,
         scope="runtime", layer="serve",
         doc="bounded pending-request depth of ServeEngine; the "
             "overflowing submit raises RejectedError — loud "
             "backpressure, never a silent drop (default: 1024)",
         malformed="0"),
    Knob("QUEST_SERVE_MAX_BATCH",
         _int_range("QUEST_SERVE_MAX_BATCH", 1), 64,
         scope="runtime", layer="serve",
         doc="max states coalesced into one serve launch; a queue "
             "reaching this many pending states dispatches immediately "
             "(default: 64)",
         malformed="0"),
    Knob("QUEST_SERVE_RESTART_MAX",
         _int_range("QUEST_SERVE_RESTART_MAX", 0), 3,
         scope="runtime", layer="serve",
         doc="consecutive worker-crash restarts ServeEngine's "
             "supervisor allows (exponential backoff + jitter) before "
             "the engine transitions to FAILED and rejects submits "
             "(default: 3; docs/RESILIENCE.md)",
         malformed="-1"),
    Knob("QUEST_SERVE_BREAKER_THRESHOLD",
         _int_range("QUEST_SERVE_BREAKER_THRESHOLD", 1), 3,
         scope="runtime", layer="serve",
         doc="consecutive primary-engine failures of one program before "
             "its circuit breaker opens and requests step down the "
             "fused->banded->host degradation ladder (default: 3; "
             "docs/RESILIENCE.md)",
         malformed="0"),
    Knob("QUEST_SERVE_REPLICAS",
         _int_range("QUEST_SERVE_REPLICAS", 1), 2,
         scope="runtime", layer="serve",
         doc="ServeEngine replicas a ServeFleet owns (program-key "
             "affinity routing, fleet-level failover; default: 2; "
             "docs/SERVING.md §fleet)",
         malformed="0"),
    Knob("QUEST_FLEET_PROC", _bool01("QUEST_FLEET_PROC"), False,
         scope="runtime", layer="serve",
         doc="ServeFleet replica backend: 1 = supervised worker "
             "PROCESSES behind the serve.ipc dispatch boundary (own "
             "interpreter + JAX runtime per replica — req/s scales "
             "with cores), 0 = in-process worker threads (default; "
             "docs/SERVING.md §process-fleet)",
         malformed="2"),
    Knob("QUEST_FLEET_MIN_REPLICAS",
         _int_range("QUEST_FLEET_MIN_REPLICAS", 1), 1,
         scope="runtime", layer="serve",
         doc="elastic-autoscaler floor: the fleet never scales below "
             "this many live replicas (serve/autoscaler.py; default: "
             "1; docs/SERVING.md §process-fleet)",
         malformed="0"),
    Knob("QUEST_FLEET_MAX_REPLICAS",
         _int_range("QUEST_FLEET_MAX_REPLICAS", 1), 4,
         scope="runtime", layer="serve",
         doc="elastic-autoscaler ceiling: the fleet never scales above "
             "this many live replicas (serve/autoscaler.py; default: "
             "4; docs/SERVING.md §process-fleet)",
         malformed="0"),
    Knob("QUEST_HEARTBEAT_S", _parse_pos_float("QUEST_HEARTBEAT_S"),
         0.25,
         scope="runtime", layer="serve",
         doc="process-replica heartbeat cadence in seconds: each "
             "worker ships health + a registry snapshot per beat, and "
             "the proxy declares the worker LOST (kill + respawn "
             "under the restart budget) after 4 missed beats "
             "(serve/ipc.py; default: 0.25; docs/SERVING.md "
             "§process-fleet)",
         malformed="0"),
    Knob("QUEST_SERVE_TENANT_QUOTA", _parse_tenant_quota,
         _default_tenant_quota,
         scope="runtime", layer="serve",
         doc="per-tenant pending-request quota for ServeFleet "
             "admission: one integer (every tenant) or "
             "'tenant=quota,...' with an optional default= entry "
             "(default: 256; docs/SERVING.md §fleet)",
         malformed="alice=lots"),
    Knob("QUEST_SERVE_SHED_THRESHOLD", _parse_shed_threshold, 0.75,
         scope="runtime", layer="serve",
         doc="fleet pressure (queued fraction of healthy capacity + "
             "open-breaker weight) above which the lowest priority "
             "class load-sheds with typed ShedError (default: 0.75; "
             "1.0 = shed only at the hard queue bound; "
             "docs/SERVING.md §fleet)",
         malformed="0"),
    Knob("QUEST_SERVE_PRIORITIES",
         _int_range("QUEST_SERVE_PRIORITIES", 1), 2,
         scope="runtime", layer="serve",
         doc="priority classes a ServeFleet accepts (submit priority= "
             "in [0, N); higher sheds later — default: 2, a free/paying "
             "pair; docs/SERVING.md §fleet)",
         malformed="0"),
    Knob("QUEST_FAULT_PLAN", _parse_fault_plan, None,
         scope="runtime", layer="serve",
         doc="deterministic fault-injection plan armed at engine "
             "construction for soak runs: 'site[:key=value]...[;...]' "
             "over the docs/RESILIENCE.md site catalog (keys: error, "
             "after, every, times, p, seed); unset = no injection, "
             "zero hot-path cost",
         malformed="serve.not_a_site"),
    Knob("QUEST_DURABLE_EVERY", _int_range("QUEST_DURABLE_EVERY", 1), 8,
         scope="runtime", layer="serve",
         doc="sweep-plan steps between checkpoints of the durable "
             "executor (resilience/durable.py, docs/RESILIENCE.md "
             "§durable; default: 8)",
         malformed="0"),
    Knob("QUEST_INTEGRITY", _bool01("QUEST_INTEGRITY"), True,
         scope="runtime", layer="serve",
         doc="in-flight corruption sentinels at checkpoint cadence "
             "(statevector norm / density trace+hermiticity drift vs "
             "the run's baseline): 1/0 (default: 1; a trip raises "
             "IntegrityError and refuses to stamp the checkpoint)",
         malformed="2"),
    Knob("QUEST_INTEGRITY_TOL", _parse_pos_float("QUEST_INTEGRITY_TOL"),
         1e-3,
         scope="runtime", layer="serve",
         doc="relative drift budget of the durable integrity sentinels "
             "(absolute for unit-scale invariants; default: 1e-3 — "
             "orders above honest f32 rounding drift, orders below "
             "real corruption)",
         malformed="-1"),
    Knob("QUEST_CHECKPOINT_KEEP",
         _int_range("QUEST_CHECKPOINT_KEEP", 1), 2,
         scope="runtime", layer="serve",
         doc="versioned checkpoints retained per durable run "
             "(checkpoint.prune_steps keep-last-K; default: 2 — a "
             "corrupt newest checkpoint always leaves a valid "
             "predecessor to resume from)",
         malformed="0"),
    Knob("QUEST_DURABLE_ELASTIC", _bool01("QUEST_DURABLE_ELASTIC"),
         False,
         scope="runtime", layer="serve",
         doc="default for run_durable(elastic=): 1 makes durable "
             "resume MESH-INDEPENDENT — a checkpoint chain written by "
             "D devices across H hosts re-enters any mesh that holds "
             "the amplitudes, re-verifying digests and re-deriving the "
             "comm plan (default: 0 — mesh mismatch rejects typed; "
             "docs/RESILIENCE.md §elastic)",
         malformed="yes"),
    Knob("QUEST_DISPATCH_TIMEOUT_S",
         _parse_nonneg_float("QUEST_DISPATCH_TIMEOUT_S"), 0.0,
         scope="runtime", layer="serve",
         doc="serve dispatch watchdog deadline in seconds: a launch "
             "exceeding it fails typed DispatchTimeout, counts toward "
             "the program's breaker, and the supervisor replaces the "
             "wedged worker thread instead of letting drain() hang "
             "(default: 0 = watchdog off; docs/RESILIENCE.md "
             "§watchdog)",
         malformed="-1"),
    Knob("_QUEST_DRYRUN_BOOTSTRAPPED", _parse_choice(
         "_QUEST_DRYRUN_BOOTSTRAPPED", ("1",)), None,
         scope="runtime", layer="infra",
         doc="internal sentinel marking the virtual-mesh bootstrap child "
             "of the driver dryrun / 16-device test (not user-facing)",
         malformed="0"),
)

KNOBS = {k.name: k for k in _KNOB_LIST}


def knob_value(name: str):
    """Effective value of a registered knob: the validating parse of the
    environment when set (raises ValueError on malformed input — knobs
    parse loudly), else the registered default. The ONE read path for
    QUEST_* knobs in package code (quest-lint QL004 flags direct
    os.environ reads)."""
    k = KNOBS[name]
    raw = os.environ.get(name)
    if raw is None:
        return k.default() if callable(k.default) else k.default
    return k.parse(raw)


def batch_bucket(b: int) -> int:
    """Effective COMPILED batch size for a requested batch of `b` states
    (the batched engines' bucketing policy, docs/BATCHING.md): under
    QUEST_BATCH_BUCKET=pow2 (default) `b` rounds UP to the next power of
    two, so serving mixed batch sizes hits one compiled program per
    bucket instead of retracing per size (B=5 and B=8 share the B=8
    program; the caller pads and slices). 'off' compiles exact sizes —
    every distinct B pays its own compile. The knob is keyed: it changes
    which program a batched call resolves to, so engine_mode_key()
    carries it (flip-audited in tests/test_lint.py)."""
    b = int(b)
    if b < 1:
        raise ValueError(f"batch size must be >= 1, got {b}")
    if knob_value("QUEST_BATCH_BUCKET") == "off":
        return b
    return 1 << (b - 1).bit_length()


def knob_current(name: str):
    """Like knob_value, but honoring setter-backed effective values
    (e.g. set_matmul_precision beats the env var once called)."""
    k = KNOBS[name]
    if k.current is not None:
        return k.current()
    return knob_value(name)


# keyed-knob sublists per layer, computed once: the registry is
# immutable and engine_mode_key sits on the eager per-gate dispatch
# path (ops/gates.py feeds A.mode_key() to every worker call), so only
# the knob_current() reads belong in the per-call cost
_KEYED_SORTED = tuple(sorted((k for k in _KNOB_LIST if k.scope == "keyed"),
                             key=lambda k: k.name))
_KEYED_BY_LAYER = {None: _KEYED_SORTED}
for _k in _KEYED_SORTED:
    _KEYED_BY_LAYER.setdefault(_k.layer, ())
    _KEYED_BY_LAYER[_k.layer] += (_k,)
del _k


def engine_mode_key(layer: Optional[str] = None) -> Tuple:
    """The trace-time mode-flag tuple every compiled-program cache key
    must carry, DERIVED from the registry: every keyed knob's effective
    value, sorted by name (omitting any would return stale programs when
    a user flips the knob mid-process — the cache-key discipline of
    ADVICE r4 item 2 / r5 item 2). `layer` restricts to one subsystem's
    knobs: the eager per-gate jit workers carry layer='apply' (all that
    their traces read), the circuit-level engines carry the full key."""
    return tuple((k.name, knob_current(k.name))
                 for k in _KEYED_BY_LAYER.get(layer, ()))


class QuESTEnv:
    """Device environment; analogue of the reference's QuESTEnv."""

    def __init__(self, devices: Optional[Sequence] = None,
                 distributed: bool = False):
        if distributed and jax.process_count() == 1:
            jax.distributed.initialize()
        if devices is None:
            devices = jax.devices()
        # amplitude sharding needs a power-of-2 device count
        # (ref validateNumRanks, QuEST_validation.c:81)
        count = 1 << (len(devices).bit_length() - 1)
        self.devices = list(devices)[:count]
        self.mesh = Mesh(np.array(self.devices), (AMP_AXIS,))

    @property
    def num_ranks(self) -> int:
        return len(self.devices)

    @property
    def rank(self) -> int:
        return jax.process_index()

    def sharding_for(self, num_state_qubits: int):
        """NamedSharding for a (2**n,) amplitude array, or None if the
        register is too small to shard. The floor is TWO amplitudes per
        device — the same local_n >= 1 bound the shard_map engines
        enforce (E_DISTRIB_QUREG_TOO_SMALL): a one-amp-per-device layout
        buys nothing AND miscompiles under GSPMD on this runtime
        (measured: the eager all-ones phase on a 3-qubit register over
        8 devices returned 4x-scaled amplitudes — the seed-red
        test_tutorial_circuit_exact; jax 0.4.37 XLA-CPU reshape of
        fully-degenerate shards)."""
        if (self.num_ranks == 1
                or (1 << num_state_qubits) < 2 * self.num_ranks):
            return None
        return NamedSharding(self.mesh, P(None, AMP_AXIS))

    def sync(self) -> None:
        """Block until all queued device work completes (ref syncQuESTEnv)."""
        jax.effects_barrier()

    def get_environment_string(self, num_state_qubits: int = None) -> str:
        """Benchmark-label tag in the reference's documented format
        "{n}qubits_{PLATFORM}_{r}ranksx{t}threads" (getEnvironmentString,
        QuEST_cpu.c:1358-1364; platform replaces "CPU", device count plays
        the rank role, 1 thread per device core)."""
        plat = self.devices[0].platform.upper() if self.devices else "CPU"
        tag = f"{plat}_{self.num_ranks}ranksx1threads"
        if num_state_qubits is not None:
            tag = f"{num_state_qubits}qubits_{tag}"
        return tag

    def report(self) -> str:
        s = (f"EXECUTION ENVIRONMENT:\nRunning distributed (MPI) version: "
             f"{'yes' if self.num_ranks > 1 else 'no'}\n"
             f"Number of devices: {self.num_ranks}\n"
             f"Platform: {self.devices[0].platform if self.devices else '?'}")
        print(s)
        return s


def create_quest_env(**kwargs) -> QuESTEnv:
    return QuESTEnv(**kwargs)


def destroy_quest_env(env: QuESTEnv) -> None:
    """No resources to free in the functional design; kept for API parity."""


def sync_quest_success(success_code: int = 1) -> int:
    """AND a success code across processes (ref syncQuESTSuccess,
    QuEST_cpu_distributed.c:166-170). Single-process: identity."""
    return int(bool(success_code))
