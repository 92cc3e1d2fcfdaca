#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chip JAX finds here.

    python3 qbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (qbench/configs/<name>.json: the register,
whose kind is qbench/registers/<register>.py) and a traffic mix
(qbench/traffic/<name>.json: the circuit, made by
qbench/generators/<generator>.py from gates of qbench/gates); per-layer
metrics are read by qbench/metrics/<name>.py, and the limit of each number
compared by qbench/cells/<cell>.json. A cell comes in as such files and
its entry in BENCHMARK.json.

Set-up: JAX and the chip, the circuit through the register's program
(plan, compile or cache load), the seeded input on the device and one
warm-up application. The window is a closed loop with one client: build a
fresh seeded input state on the device in the buffer the last output held
(donated, so no 8 GiB buffer is freed or allocated), apply the compiled
program (donated), await it, until --seconds have passed; apply_s is the
window's length over the applications completed.
Then the last application's output is sketched, freed, and the plain
reference (qbench/reference.py) recomputes it from the same input. With
--trace 1 the per-layer readers then also get the program's own recording
(quest_tpu.profiling.recording) of a second build and lowering of the
circuit: the set-up, which compile_s times, runs without it, as in every
run.

The last line of stdout is the result; the numbers compared, with their
limits, are the last lines of stderr and the last key of the result.
Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()    # set-up is timed from here, before JAX loads

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import NamedTuple  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".qbench_trace")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _load(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def _cache_setup() -> None:
    """One fixed cache directory inside the checkout unless the
    environment names one; the program's own cache (quest_tpu.precision)
    reads the same variable, so both land in one place."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_record(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX's default device is {devs[0].platform!r}, "
                     f"not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def build_circuit(ops, num_qubits):
    """The traffic's ops through the program's public builder."""
    from qbench.gates import gate
    from quest_tpu.circuit import Circuit
    c = Circuit(num_qubits)
    for op in ops:
        gate(op.name).build(c, op.qubits, op.param)
    return c


def _rng(seed: int, j: int):
    import numpy as np
    return np.random.default_rng([seed % (1 << 64), j])


class Setup(NamedTuple):
    reg: object               # the register's module
    num_qubits: int
    ops: list                 # the traffic's ops, generators.Op
    circuit: object           # the program's Circuit
    compiled: object          # the compiled program the window drives
    state: object             # its first input, seeded
    lower_s: float
    compile_s: float          # lower() + compile()


def setup(config: dict, traffic: dict, *, seed: int,
          interpret: bool = False) -> Setup:
    """A cell's configuration and traffic to its compiled program, its
    first input and its op list."""
    from qbench import generators

    reg = importlib.import_module(f"qbench.registers.{config['register']}")
    nq = config["qubits"]
    ops = generators.ops(traffic, nq)
    circuit = build_circuit(ops, nq)
    fn = reg.program(circuit, nq, interpret)
    state = reg.program_input(reg.random_factors(_rng(seed, 0), nq),
                              reg.program_buffer(nq), num_qubits=nq)
    t = time.perf_counter()
    lowered = fn.lower(state)
    lower_s = time.perf_counter() - t
    compiled = lowered.compile()
    return Setup(reg, nq, ops, circuit, compiled, state, lower_s,
                 time.perf_counter() - t)


def program_record(s: Setup, interpret: bool = False):
    """The program's recording (quest_tpu.profiling.recording) of a fresh
    build of the circuit through the register's program, and its lower(),
    a second time in this process. Not compile(): a lowering from here
    has a persistent-cache key of its own (the call site is part of it),
    so it would compile the whole program once more in a fresh
    checkout."""
    import jax
    from quest_tpu import profiling
    with profiling.recording() as rec:
        fn = s.reg.program(build_circuit(s.ops, s.num_qubits), s.num_qubits,
                           interpret)
        fn.lower(jax.ShapeDtypeStruct(s.state.shape, s.state.dtype))
    return rec


def run_cell(cell: dict, config: dict, traffic: dict, metrics: list,
             limits: dict, peaks: dict, *, seed: int, seconds: float,
             trace: bool, require_tpu: bool = True, interpret: bool = False,
             program_hook=None) -> dict:
    """One run; returns the result record. `metrics` are the BENCHMARK.json
    entries this run reports. Tests pass require_tpu=False, interpret=True
    and a `program_hook` that wraps the compiled program with a fault."""
    import jax
    import jax.numpy as jnp

    from qbench import reference as R
    from qbench import trace as TR

    device = device_record(cell["chips"], require_tpu)
    try:
        peak = peaks["devices"][device["kind"]]
    except KeyError:
        raise KeyError(f"qbench/peaks.json has no row for device kind "
                       f"{device['kind']!r}") from None

    def span(name):
        return jax.profiler.TraceAnnotation(TR.SPAN + name)

    s = setup(config, traffic, seed=seed, interpret=interpret)
    reg, nq = s.reg, s.num_qubits
    run = s.compiled if program_hook is None else program_hook(s.compiled)
    state = run(s.state)
    state.block_until_ready()
    setup_s = time.perf_counter() - T0

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    apps, laps = 0, []
    with span("window"):
        t_start = t_end = time.perf_counter()
        while True:
            with span("input"):
                factors = reg.random_factors(_rng(seed, apps + 1), nq)
                state = reg.program_input(factors, state, num_qubits=nq)
            with span("dispatch"):
                state = run(state)
            with span("wait"):
                state.block_until_ready()
            apps += 1
            laps.append(time.perf_counter() - t_end)
            t_end += laps[-1]
            if t_end - t_start >= seconds:
                break
    if trace:
        jax.profiler.stop_trace()
    apply_s = (t_end - t_start) / apps
    device["memory_peak_bytes"] = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.devices()[:cell["chips"]])

    summary, extra = None, {}
    if trace:
        summary = TR.summarize(TR.load(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        extra["breakdown"] = {"device_ops": [list(x) for x in
                                             summary.op_totals],
                              "idle_gaps": [list(x) for x in
                                            summary.idle_gaps]}

    # the check: the last application's output against the reference
    t_check = time.perf_counter()
    with span("check"):
        salt = jnp.uint32(seed % (1 << 32))
        got = jax.device_get(R.sketch(state, salt))
        state.delete()
        passes = R.plan_passes(reg.lower(s.ops, nq), reg.windows(nq))
        ref_fn, arrays = R.reference_program(passes, reg.LANE_BITS)
        x = ref_fn(reg.reference_input(factors, num_qubits=nq), arrays)
        want = jax.device_get(R.sketch(x, salt, layout=reg.layout(nq)))
        x.delete()
    print(f"qbench: set-up {setup_s:.3f} s (lower {s.lower_s:.3f} s + "
          f"compile {s.compile_s - s.lower_s:.3f} s), "
          f"{apps} applications in {t_end - t_start:.3f} s "
          f"({', '.join(f'{lap:.4f}' for lap in laps)}), check "
          f"{time.perf_counter() - t_check:.3f} s", file=sys.stderr)
    check = {"proj_gap": {"value": R.proj_gap(got, want),
                          "limit": limits["proj_gap"]}}
    correct = all(c["value"] <= c["limit"] for c in check.values())

    values = {}
    if trace:
        ctx = {"trace": summary, "compile_s": s.compile_s,
               "state_bytes": reg.state_bytes(nq), "peak": peak,
               "plan_stats": s.circuit.plan_stats(density=reg.DENSITY),
               "program": program_record(s, interpret)}
        for m in metrics:
            v = importlib.import_module(f"qbench.metrics.{m['name']}").read(
                ctx)
            if v is not None:
                values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        got_e2e = {"apply_s": apply_s, "setup_s": setup_s}
        for m in metrics:
            values[m["name"]] = {"value": got_e2e[m["name"]],
                                 "unit": m["unit"]}
    return {"correct": correct, "attempted": apps,
            "failed": 0 if correct else 1, "metrics": values,
            "device": device, **extra, "check": check}


def cell_metrics(bench: dict, name: str, trace: bool) -> list:
    """The BENCHMARK.json metrics this cell reports in this kind of run."""
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    if not trace:
        return e2e
    moves = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in moves)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    cell = cells[args.workload]
    _cache_setup()
    try:
        result = run_cell(
            cell, _load("configs", cell["config"] + ".json"),
            _load("traffic", cell["traffic"] + ".json"),
            cell_metrics(bench, cell["name"], bool(args.trace)),
            _load("cells", cell["name"] + ".json")["limits"],
            _load("peaks.json"), seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace))
    except NoChip as e:
        print(f"qbench: {e}", file=sys.stderr)
        return 2
    for k, c in result["check"].items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    # run as a file, Python puts qbench/ first on the path, where trace.py
    # would shadow the standard library's; the checkout's root goes there
    sys.path[0] = ROOT
    sys.exit(main())
