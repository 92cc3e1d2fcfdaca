#!/usr/bin/env python3
"""Look inside one cell's program: the set-up phases the program records,
and where one traced application's device time goes, sweep by sweep.

    python3 qbench/program_trace.py --workload <cell> --seed <n>
        [--record 0|1] [--trace 0|1]

Set-up is qbench/run.py's (run.setup): the cell's circuit through its
register's program, lower(), compile() or a cache load, the seeded input;
then one warm-up application. With --record 1 the program's
recording (quest_tpu.profiling.recording) is open around all of it. With
--trace 1 one more application runs under the profiler, with the
profiler settings run.py uses. There is no window and no check: this is
not a benchmark run and reports no end-to-end metric.

stderr: the set-up split, the per-sweep table and the in-kernel regions
found on the device planes. stdout, last line: the same as one JSON
object.

The regions (`quest:dma_in_wait`, `quest:stages`, `quest:dma_out_wait`)
reach the trace only from kernels compiled with libtpu's
--xla_enable_custom_call_region_trace=true, which slows them:

    LIBTPU_INIT_ARGS=--xla_enable_custom_call_region_trace=true \
    python3 qbench/program_trace.py --workload sv30_f32.rcs_d20 --seed 1
"""

from __future__ import annotations

import time

T0 = time.perf_counter()    # set-up is timed from here, as in run.py

import argparse  # noqa: E402
import bisect  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".qbench_trace")

SWEEP = re.compile(r"quest\.sweep(\d+)/")
INSTR = re.compile(r"\s*(?:ROOT )?%(\S+) = ")
TPU_CALL = 'custom_call_target="tpu_custom_call"'
REGIONS = ("quest:dma_in_wait", "quest:stages", "quest:dma_out_wait")


def setup_split(rec) -> dict:
    """The set-up phases a quest_tpu.profiling.Recording holds, in seconds
    and counts."""
    return {"plan_s": rec.span_seconds("quest.plan"),
            "jaxpr_trace_s": rec.seconds("jaxpr_trace"),
            "mlir_lower_s": rec.seconds("mlir_lower"),
            "backend_compile_s": rec.seconds("backend_compile"),
            "traces": rec.counts.get("jaxpr_trace", 0),
            "backend_compiles": rec.counts.get("backend_compile", 0),
            "cache_hits": rec.counts.get("cache_hit", 0),
            "cache_misses": rec.counts.get("cache_miss", 0)}


def sweep_map(hlo_text: str) -> dict:
    """{kernel instruction name: sweep index} from the compiled program's
    HLO text, where each custom call's op_name metadata carries the
    `quest.sweepNN` scope of its place in the plan. The device trace
    names a kernel event by its instruction and drops the metadata, so
    this is how an event finds its sweep."""
    out = {}
    for line in hlo_text.splitlines():
        if TPU_CALL not in line:
            continue
        m, scope = INSTR.match(line), SWEEP.search(line)
        if m and scope:
            out[m.group(1)] = int(scope.group(1))
    return out


def _region(name: str):
    return next((r for r in REGIONS if r in name), None)


def kernel_rows(pd, sweeps: dict) -> list:
    """Device ms of each kernel event of the device
    planes' `XLA Ops` lines, with the sweep `sweeps` (sweep_map) gives
    its instruction, and the in-kernel regions that fall inside it:
    [{sweep, kernel, ms, stages_ms, dma_wait_ms}] in sweep order."""
    from qbench import trace as TR
    rows = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        kernels, marks = [], []
        for line in plane.lines:
            for e in line.events:
                if line.name != "XLA Ops":
                    region = _region(e.name)
                    if region:
                        marks.append((e.start_ns, e.duration_ns, region))
                    continue
                name = TR.op_name(e.name)
                if not name.endswith(":kernel"):
                    continue
                instr = name[:-len(":kernel")]
                row = rows.setdefault(instr, {
                    "sweep": sweeps.get(instr),
                    "kernel": re.sub(r"\.\d+$", "", instr),
                    "ms": 0.0, "stages_ms": 0.0, "dma_wait_ms": 0.0})
                row["ms"] += e.duration_ns * 1e-6
                kernels.append((e.start_ns, e.start_ns + e.duration_ns,
                                instr))
        kernels.sort()
        starts = [k[0] for k in kernels]
        for t, dur, region in marks:
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and t < kernels[i][1]:
                key = "stages_ms" if region == "quest:stages" \
                    else "dma_wait_ms"
                rows[kernels[i][2]][key] += dur * 1e-6
    return sorted(rows.values(), key=lambda r: (
        r["sweep"] is None, r["sweep"] or 0, r["kernel"]))


def regions(pd) -> dict:
    """The in-kernel named-scope regions on the device planes: count and
    device ms by region, and the lines they sit on."""
    out = {r: {"count": 0, "ms": 0.0, "lines": []} for r in REGIONS}
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                region = _region(e.name)
                if region is None:
                    continue
                r = out[region]
                r["count"] += 1
                r["ms"] += e.duration_ns * 1e-6
                if line.name not in r["lines"]:
                    r["lines"].append(line.name)
    return out


def planned_steps(circuit, n: int, density: bool) -> list:
    """(stages, loop steps) of each kernel sweep of the fused plan, in
    plan order: the steps each kernel's pipeline walks per application."""
    from quest_tpu.ops import fusion as F
    from quest_tpu.ops import pallas_band as PB
    items = F.plan(circuit._planned_flat(n, density), n,
                   bands=PB.plan_bands(n))
    parts = PB.maybe_sweep(PB.segment_plan(items, n), n)
    return [(len(p[1]), PB.sweep_steps(p[1], n)) for p in parts
            if p[0] == "segment"]


def expected_regions(steps, out_slots: int) -> dict:
    """Region counts per application if every loop step is traced."""
    return {"quest:dma_in_wait": sum(s for _, s in steps),
            "quest:stages": sum(s for _, s in steps),
            "quest:dma_out_wait": sum(max(0, s - out_slots)
                                      for _, s in steps)}


def run(cell: dict, config: dict, traffic: dict, *, seed: int,
        record: bool, trace: bool, require_tpu: bool = True,
        interpret: bool = False) -> dict:
    import contextlib

    import jax

    from qbench import run as RUN, trace as TR
    from quest_tpu import profiling
    from quest_tpu.ops import pallas_band as PB

    device = RUN.device_record(cell["chips"], require_tpu)
    init_s = time.perf_counter() - T0
    with (profiling.recording() if record
          else contextlib.nullcontext()) as rec:
        s = RUN.setup(config, traffic, seed=seed, interpret=interpret)
        reg, nq, compiled = s.reg, s.num_qubits, s.compiled
        t = time.perf_counter()
        state = compiled(s.state)
        state.block_until_ready()
        warmup_s = time.perf_counter() - t
    setup_s = time.perf_counter() - T0
    out = {"device": device, "record": record, "setup_s": setup_s,
           "init_s": init_s, "compile_s": s.compile_s, "warmup_s": warmup_s}
    if record:
        out["setup"] = setup_split(rec)
    if trace:
        steps = planned_steps(s.circuit, reg.state_bits(nq), reg.DENSITY)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        with (profiling.recording() if record
              else contextlib.nullcontext()) as window:
            # what run.py's window does: a fresh input, one application
            factors = reg.random_factors(RUN._rng(seed, 1), nq)
            state = reg.program_input(factors, state, num_qubits=nq)
            state.block_until_ready()
            jax.profiler.start_trace(TRACE_DIR)
            state = compiled(state)
            state.block_until_ready()
            jax.profiler.stop_trace()
        if record:
            out["window"] = {k: window.counts.get(k, 0)
                             for k in ("jaxpr_trace", "backend_compile")}
        pd = TR.load(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        rows = kernel_rows(pd, sweep_map(compiled.as_text()))
        for r in rows:
            if r["sweep"] is not None and r["sweep"] < len(steps):
                r["stages"], r["steps"] = steps[r["sweep"]]
        out["sweeps"] = rows
        for k in ("ms", "stages_ms", "dma_wait_ms"):
            out["kernel_" + k] = sum(r[k] for r in rows)
        out["regions"] = regions(pd)
        out["regions_expected"] = expected_regions(
            steps, PB.PIPELINE_OUT_SLOTS)
    state.delete()
    return out


def report(out: dict, file=sys.stderr) -> None:
    print(f"program_trace: set-up {out['setup_s']:.3f} s: JAX and the "
          f"chip {out['init_s']:.3f} s, lower + compile "
          f"{out['compile_s']:.3f} s, warm-up application "
          f"{out['warmup_s']:.3f} s", file=file)
    for k, v in out.get("setup", {}).items():
        print(f"  {k:<18} {v:.3f}" if isinstance(v, float)
              else f"  {k:<18} {v}", file=file)
    if "sweeps" not in out:
        return
    print(f"{'sweep':>5} {'stages':>6} {'steps':>6} {'ms':>9} "
          f"{'stages_ms':>9} {'dma_ms':>7}  kernel", file=file)
    for r in out["sweeps"]:
        print(f"{str(r['sweep']):>5} {str(r.get('stages', '')):>6} "
              f"{str(r.get('steps', '')):>6} {r['ms']:9.3f} "
              f"{r['stages_ms']:9.3f} {r['dma_wait_ms']:7.3f}  "
              f"{r['kernel']}", file=file)
    if "window" in out:
        print(f"traced application: {out['window']['jaxpr_trace']} traces, "
              f"{out['window']['backend_compile']} backend compiles",
              file=file)
    print(f"kernel_ms {out['kernel_ms']:.3f}, in quest:stages "
          f"{out['kernel_stages_ms']:.3f}, in the DMA waits "
          f"{out['kernel_dma_wait_ms']:.3f}", file=file)
    for name, r in out["regions"].items():
        print(f"  {name:<20} {r['count']:>9} per application "
              f"(planned {out['regions_expected'][name]}), "
              f"{r['ms']:.3f} ms, lines {r['lines']}", file=file)


def main(argv=None) -> int:
    from qbench import run as RUN
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--record", type=int, choices=(0, 1), default=1)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = {w["name"]: w for w in json.load(f)["workloads"]}
    cell = cells[args.workload]
    RUN._cache_setup()
    try:
        out = run(cell, RUN._load("configs", cell["config"] + ".json"),
                  RUN._load("traffic", cell["traffic"] + ".json"),
                  seed=args.seed, record=bool(args.record),
                  trace=bool(args.trace))
    except RUN.NoChip as e:
        print(f"program_trace: {e}", file=sys.stderr)
        return 2
    report(out)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    # as in run.py: the checkout's root goes first on the path, where
    # qbench/trace.py would shadow the standard library's
    sys.path[0] = ROOT
    sys.exit(main())
