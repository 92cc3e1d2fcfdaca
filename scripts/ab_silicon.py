#!/usr/bin/env python
"""One-session silicon A/B bundle: every knob the stack shipped with a
"validate on first chip run" note, swept in ONE chip session and
emitted as ONE JSON report (ISSUE 11 satellite; closes the PR 3/4/8
flagged debts plus this round's pipeline knob):

  pipeline   QUEST_FUSED_PIPELINE 1 (decoupled multi-buffer rings) vs
             0 (legacy in-place slots) on the bench step — the
             tentpole's primary A/B
  nbuf       QUEST_FUSED_NBUF 2/3/4 under the LEGACY driver (the
             in-place slot count; 23.8 vs 20.5 ms history)
  sweep      QUEST_SWEEP_FUSION 1 (MAX_SWEEP_STAGES=64 merged sweeps)
             vs 0 (raw segment plan) — the PR 3 Mosaic
             register-pressure debt
  batch      compiled_batched(B) vs jax.lax.map of compiled_fused over
             the same B states — the PR 4 batch-grid debt
  exchange   QUEST_EXCHANGE_SLICES 1 vs 4 on the sharded fused step —
             the PR 8 ICI-overlap debt (needs >= 2 devices; recorded
             as skipped otherwise)
  autotune   the priced plan chooser's pick vs every forced engine
             (QUEST_APPLY_AUTOROUTE 1 vs 0) — whether the CPU cost
             model ranks engines the way silicon does (ISSUE 16,
             docs/PLANNING.md)
  transpile  QUEST_TRANSPILE auto vs 0 on the QASM workload gallery —
             whether the rewriter's predicted-sweep wins survive as
             real per-class requests/s on silicon (ISSUE 20,
             docs/TRANSPILE.md)

Every experiment runs in a SUBPROCESS: the kernel knobs are
import-once/keyed, so a fresh process per value is the only schedule
that cannot hand back a stale program, and one OOM/compile failure
cannot kill the matrix (the sweep_perf.py discipline).

Usage:
  python scripts/ab_silicon.py            # chip session (n=30 bench)
  python scripts/ab_silicon.py 28         # smaller headline size
  python scripts/ab_silicon.py --smoke    # CPU path smoke: tiny n,
                                          # interpret-mode kernels,
                                          # exercises every experiment
The report prints as one `[ab-silicon] {...}` JSON line (and pretty
JSON to stdout), keyed by experiment.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import json, os, sys, time
sys.path.insert(0, %(repo)r)
from quest_tpu.precision import enable_compile_cache
enable_compile_cache()
import jax
import jax.numpy as jnp
import numpy as np

mode = %(mode)r
n = %(n)d
reps = %(reps)d
batch = %(batch)d
interpret = %(interpret)d == 1


def out(**kw):
    print("[ab-result] " + json.dumps(kw), flush=True)


def sync(x):
    jax.block_until_ready(x)


if mode == "bench":
    # the headline step: 16 independent rotations, INNER_STEPS unrolled
    import bench
    from quest_tpu.state import basis_planes, fused_state_shape
    c = bench._build_circuit(n)
    iters = 8 if not interpret else 2
    step = c.compiled_fused(n, density=False, donate=True, iters=iters,
                            interpret=interpret)
    s = basis_planes(0, n=n, rdt=jnp.float32, shape=fused_state_shape(n))
    s = step(s)
    sync(s)
    t0 = time.perf_counter()
    for _ in range(reps):
        s = step(s)
    sync(s)
    dt = (time.perf_counter() - t0) / reps
    rec = c.plan_stats()["fused"]
    out(mode=mode, n=n,
        pipeline=os.environ.get("QUEST_FUSED_PIPELINE", "1"),
        nbuf=os.environ.get("QUEST_FUSED_NBUF", "3"),
        sweep_fusion=os.environ.get("QUEST_SWEEP_FUSION", "1"),
        hbm_sweeps=rec["hbm_sweeps"],
        overlap_steps=rec.get("pipeline_overlap_steps"),
        ms_per_application=round(dt / iters * 1e3, 2),
        gates_per_sec=round(16 * iters / dt, 1))
elif mode == "batch":
    # PR 4 debt: the batch grid dimension vs lax.map of the unbatched
    # program over the same states
    import bench
    c = bench._build_circuit(n)
    rng = np.random.default_rng(0)
    amps_b = jnp.asarray(
        rng.standard_normal((batch, 2, 1 << n)).astype(np.float32))
    fn_b = c.compiled_batched(batch, donate=False, interpret=interpret)
    got = fn_b(amps_b)
    sync(got)
    t0 = time.perf_counter()
    for _ in range(reps):
        got = fn_b(got)
    sync(got)
    dt_b = (time.perf_counter() - t0) / reps
    fused = c.compiled_fused(n, density=False, donate=False,
                             interpret=interpret)
    import functools
    from quest_tpu.ops import pallas_band as PB

    def one(a):
        return fused(a.reshape(2, -1, PB.LANES)).reshape(2, -1)
    fn_m = jax.jit(lambda ab: jax.lax.map(one, ab))
    got_m = fn_m(amps_b)
    sync(got_m)
    t0 = time.perf_counter()
    for _ in range(reps):
        got_m = fn_m(got_m)
    sync(got_m)
    dt_m = (time.perf_counter() - t0) / reps
    out(mode=mode, n=n, batch=batch,
        batched_ms=round(dt_b * 1e3, 2),
        laxmap_ms=round(dt_m * 1e3, 2),
        speedup=round(dt_m / dt_b, 2))
elif mode == "sharded":
    # PR 8 debt: exchange slicing on the sharded fused step
    from quest_tpu.parallel.mesh import make_amp_mesh
    import bench
    ndev = len(jax.devices())
    if ndev < 2:
        out(mode=mode, skipped="needs >= 2 devices", devices=ndev)
        sys.exit(0)
    mesh = make_amp_mesh(2)
    c = bench._build_deep_global_circuit(n, depth=4)
    fn = c.compiled_sharded_fused(n, density=False, mesh=mesh,
                                  donate=False, interpret=interpret)
    rng = np.random.default_rng(1)
    amps = jnp.asarray(rng.standard_normal((2, 1 << n)).astype(np.float32))
    got = fn(amps)
    sync(got)
    t0 = time.perf_counter()
    for _ in range(reps):
        got = fn(got)
    sync(got)
    dt = (time.perf_counter() - t0) / reps
    out(mode=mode, n=n, devices=2,
        slices=os.environ.get("QUEST_EXCHANGE_SLICES", "1"),
        dci_slices=os.environ.get("QUEST_EXCHANGE_SLICES_DCI", "0"),
        topology=os.environ.get("QUEST_COMM_TOPOLOGY", ""),
        ms_per_application=round(dt * 1e3, 2))
elif mode == "autotune":
    # ISSUE 16 satellite: the priced chooser on real silicon — plan
    # search wall time, the chosen engine, and chooser-pick vs every
    # forced engine on the headline circuit (the CPU cost model only
    # has to RANK right; this leg measures whether it did)
    import bench
    from quest_tpu import plan as P
    from quest_tpu.ops import pallas_band as PB
    from quest_tpu.state import basis_planes
    c = bench._build_circuit(n)
    t0 = time.perf_counter()
    plan = P.autotune(c, persist=False)
    search_ms = (time.perf_counter() - t0) * 1e3

    def time_engine(fn):
        amps = basis_planes(0, n=n, rdt=jnp.float32)
        amps = fn(amps)
        sync(amps)
        t0 = time.perf_counter()
        for _ in range(reps):
            amps = fn(amps)
        sync(amps)
        return (time.perf_counter() - t0) / reps * 1e3

    forced = {"pergate": c.compiled(n, False, donate=True),
              "banded": c.compiled_banded(n, False, donate=True)}
    if PB.usable(n):
        fused = c.compiled_fused(n, False, donate=True,
                                 interpret=interpret)
        forced["fused"] = (lambda a: fused(
            a.reshape(2, -1, PB.LANES)).reshape(2, -1))
    ms = {}
    for name, fn in forced.items():
        try:
            ms[name] = round(time_engine(fn), 3)
        except Exception as e:
            ms[name] = f"failed: {e!r}"[:120]
    timed = {k: v for k, v in ms.items() if isinstance(v, float)}
    chosen = ms.get(plan.engine)
    out(mode=mode, n=n,
        autoroute=os.environ.get("QUEST_APPLY_AUTOROUTE", "1"),
        engine=plan.engine, incumbent=plan.incumbent,
        candidates=len(plan.candidates),
        search_ms=round(search_ms, 2),
        forced_ms=ms,
        chooser_ranked_right=(
            chosen == min(timed.values()) if timed and
            isinstance(chosen, float) else None))
elif mode == "transpile":
    # ISSUE 20 satellite: the circuit transpiler's workload gallery on
    # real silicon. QUEST_TRANSPILE resolves at QASM import time in
    # THIS process, so the auto/0 legs exercise the exact routing a
    # real OpenQASM workload gets; per class we report the stream the
    # planner actually prices (op count, predicted HBM sweeps) next to
    # measured requests/s. The dynamic GHZ class rides
    # compiled_measured — serve rejects mid-circuit measurement.
    import bench
    from quest_tpu import transpile as TR
    circs = bench._gallery_circuits(n, None)      # env-resolved knob
    classes = {}
    for cls, c in circs.items():
        sweeps, count = TR.stream_cost(c)
        timer = bench._time_measured if cls == "ghz" \
            else bench._time_serve_apply
        try:
            rps = round(timer(c, n, reps), 2)
        except Exception as e:
            rps = f"failed: {e!r}"[:120]
        classes[cls] = {"ops": count, "sweeps": sweeps, "rps": rps}
    out(mode=mode, n=n,
        transpile=os.environ.get("QUEST_TRANSPILE", "auto"),
        classes=classes)
elif mode == "grad":
    # ISSUE 19 satellite: the adjoint differentiation engine on real
    # silicon — optimizer steps/s of the VQE training step under
    # whatever engine QUEST_ADJOINT resolves to in THIS process
    # (0=taped, 1=adjoint, unset=capacity auto), plus gradient parity
    # against the taped reference so a chip-only numerics drift is
    # caught in the same session that times it
    import bench
    from quest_tpu import adjoint as AD
    from quest_tpu.ops import expec as E
    layers = 2 if interpret else 4
    c = bench._build_vqe_ansatz(n, layers)
    ham = E.PauliSum.of(*bench._build_tfim_sum(n), n)
    fn = AD.value_and_grad(c, ham)            # knob-resolved engine
    th = jnp.asarray(fn.initial_params, jnp.float32)
    v, g = fn(th)
    sync(g)
    steps = 3 if interpret else 10
    t0 = time.perf_counter()
    for _ in range(steps):
        v, g = fn(th)
        th = th - 0.05 * g
    sync(th)
    dt = (time.perf_counter() - t0) / steps
    parity = None
    if fn.engine != "taped":
        ref = AD.value_and_grad(c, ham, engine="taped")
        _, gt = ref(jnp.asarray(fn.initial_params, jnp.float32))
        _, ga = fn(jnp.asarray(fn.initial_params, jnp.float32))
        parity = float(jnp.max(jnp.abs(ga - gt)))
    cap = AD.capacity_stats(n, fn.num_params, len(c.ops), np.float32)
    out(mode=mode, n=n, engine=fn.engine,
        knob=os.environ.get("QUEST_ADJOINT", "auto"),
        params=fn.num_params,
        steps_per_s=round(1.0 / dt, 3),
        ms_per_step=round(dt * 1e3, 2),
        adjoint_peak_bytes=cap["adjoint_peak_bytes"],
        taped_residual_bytes=cap["taped_residual_bytes"],
        grad_parity=parity)
else:
    raise SystemExit(f"unknown mode {mode!r}")
"""


def run(mode, n, env=None, reps=5, batch=8, interpret=False,
        timeout=1800):
    params = dict(repo=REPO, mode=mode, n=n, reps=reps, batch=batch,
                  interpret=1 if interpret else 0)
    code = WORKER % params
    e = dict(os.environ)
    e.update(env or {})
    label = f"mode={mode} env={env}"
    try:
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True,
                           timeout=timeout, env=e, cwd=REPO)
    except subprocess.TimeoutExpired:
        print(f"[ab-silicon] TIMEOUT {label}", flush=True)
        return {"error": "timeout"}
    for line in r.stdout.splitlines():
        if line.startswith("[ab-result]"):
            print(f"[ab-silicon] {label}: {line[len('[ab-result] '):]}",
                  flush=True)
            return json.loads(line[len("[ab-result]"):])
    print(f"[ab-silicon] FAILED {label}: {r.stdout[-400:]} "
          f"{r.stderr[-1200:]}", flush=True)
    return {"error": (r.stderr or r.stdout)[-300:]}


def main():
    args = [a for a in sys.argv[1:]]
    smoke = "--smoke" in args
    args = [a for a in args if a != "--smoke"]
    if smoke:
        n, nb, ns, reps, interpret = 10, 10, 8, 1, True
    else:
        n = int(args[0]) if args else 30
        nb = 24                 # batch size cap: B states must fit HBM
        ns = 28                 # sharded A/B size: the exchange overlap
        # only shows at HBM-scale shards (a small state times dispatch
        # overhead, not ICI) — 2^27 amps/device on a 2-dev mesh
        reps, interpret = 5, False

    report = {"n": n, "smoke": smoke}

    # 1. the tentpole A/B: decoupled pipeline vs legacy in-place slots
    report["pipeline"] = {
        v: run("bench", n, env={"QUEST_FUSED_PIPELINE": v}, reps=reps,
               interpret=interpret)
        for v in ("1", "0")}

    # 2. legacy slot count (only meaningful with the pipeline off)
    report["nbuf"] = {
        v: run("bench", n,
               env={"QUEST_FUSED_PIPELINE": "0", "QUEST_FUSED_NBUF": v},
               reps=reps, interpret=interpret)
        for v in ("2", "3", "4")}

    # 3. MAX_SWEEP_STAGES=64 merged sweeps vs the raw segment plan
    report["sweep_fusion"] = {
        v: run("bench", n, env={"QUEST_SWEEP_FUSION": v}, reps=reps,
               interpret=interpret)
        for v in ("1", "0")}

    # 4. batch grid vs lax.map of the unbatched program
    report["batch_grid"] = run("batch", nb, reps=reps, batch=8 if not
                               smoke else 2, interpret=interpret)

    # 5. exchange slicing on a 2-device mesh (forced host devices off
    # chip so the smoke run exercises the path)
    env2 = {}
    if smoke:
        env2["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                             + " --xla_force_host_platform_device_count=2"
                             ).strip()
    report["exchange_slices"] = {
        v: run("sharded", ns, env={**env2, "QUEST_EXCHANGE_SLICES": v},
               reps=reps, interpret=interpret)
        for v in ("1", "4")}

    # 6. the DCI leg (ISSUE 13 satellite): under a hosts=2 topology a
    # 2-dev mesh's every exchange crosses the host boundary, so
    # QUEST_EXCHANGE_SLICES_DCI alone governs the slicing — A/B finer
    # DCI slicing against the unsliced baseline above. On a single-host
    # chip pair this measures the knob's overhead floor; on a real
    # multi-host slice it measures the overlap win (docs/DISTRIBUTED.md
    # §topology).
    report["exchange_slices_dci"] = {
        v: run("sharded", ns,
               env={**env2, "QUEST_EXCHANGE_SLICES": "1",
                    "QUEST_EXCHANGE_SLICES_DCI": v,
                    "QUEST_COMM_TOPOLOGY": "hosts=2"},
               reps=reps, interpret=interpret)
        for v in ("0", "4")}

    # 7. the priced plan chooser (ISSUE 16 satellite): chooser pick vs
    # every forced engine, with the auto-route knob on and off — on
    # chip this validates that the CPU-side cost model RANKS engines
    # the way silicon does (docs/PLANNING.md §pricing)
    report["autotune"] = {
        v: run("autotune", n, env={"QUEST_APPLY_AUTOROUTE": v},
               reps=reps, interpret=interpret)
        for v in ("1", "0")}

    # 8. the adjoint differentiation engine (ISSUE 19 satellite):
    # forced-taped vs forced-adjoint vs capacity-auto on the VQE
    # training step — on chip this measures the steps/s ratio the CPU
    # host can only model (docs/AUTODIFF.md; the capacity gates live in
    # scripts/check_adjoint_golden.py). Sized down from the headline n:
    # the taped leg materializes (P+2) state registers
    ng = 10 if smoke else min(n, 26)
    report["grad"] = {
        v or "auto": run("grad", ng,
                         env={"QUEST_ADJOINT": v} if v else {},
                         reps=reps, interpret=interpret)
        for v in ("0", "1", None)}

    # 9. the circuit transpiler (ISSUE 20 satellite): the QASM gallery
    # corpus imported under QUEST_TRANSPILE auto vs 0 — on chip this
    # prices the rewriter's predicted-sweep wins against real per-class
    # requests/s (docs/TRANSPILE.md; the equivalence and never-worse
    # gates live in scripts/check_transpile_golden.py). Sized below the
    # serve tier's HBM headroom: B=8 batched states per request.
    nt = 9 if smoke else min(n, 24)
    report["transpile"] = {
        v: run("transpile", nt, env={"QUEST_TRANSPILE": v},
               reps=2 if smoke else 16, interpret=interpret)
        for v in ("auto", "0")}

    print("[ab-silicon] " + json.dumps(report), flush=True)
    print(json.dumps(report, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
