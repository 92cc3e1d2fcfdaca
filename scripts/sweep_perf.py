"""On-chip perf experiment matrix for the fused kernel (round 3).

Answers three questions the recorded stage timings raise:

  E1  pass-baseline vs block size: a single sc-butterfly segment moves
      state bytes and does ~no flops, yet measured 2.2x the HBM roofline
      at 29q. Sweep QUEST_ROWS_EFF_BITS (subprocess per value — the knob
      is read once at import, see pallas_band._rows_eff_override).
  E2  MXU cost vs dot dim: time scb segments at d=128/16/8. If cost is
      ~flat in d (tile padding), the current 7-qubit bands are optimal;
      if it scales with d, splitting bands into 4+3 saves ~5x MACs.
  E3  the bench step (16 rx @ 30q) at the winning block size, HIGHEST
      and HIGH tiers — the would-be new headline.

Each experiment runs in a subprocess so block-size/precision knobs are
honored and a single OOM/compile failure cannot kill the matrix.
Usage: python scripts/sweep_perf.py [n]   (default 30)
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

def out(**kw):
    print("[sweep-result] " + json.dumps(kw), flush=True)

if mode == "segment":
    from quest_tpu.ops import pallas_band as PB
    kind = %(kind)r
    d = %(d)d
    if kind == "sc":
        bit = n - 8   # a high scattered bit
        stages = [PB.MatStage(kind="sc", bit=bit, dim=2, real_only=False,
                              lane_preds=(), row_preds=())]
        g = np.zeros((2, 2, 2), np.float32); g[0] = np.eye(2)
        arrays = [jnp.asarray(g)]
    else:  # scb over the TOP w bits, like the real high band
        w = d.bit_length() - 1
        bit = n - 7 - w
        stages = [PB.MatStage(kind="scb", bit=bit, dim=d, real_only=False,
                              lane_preds=(), row_preds=())]
        g = np.zeros((2, d, d), np.float32); g[0] = np.eye(d)
        arrays = [jnp.asarray(g)]
    fn = PB.compile_segment(stages, n)
    jfn = jax.jit(lambda a: fn(a, arrays), donate_argnums=(0,))
    from quest_tpu.state import basis_planes, fused_state_shape
    # ONE fused device buffer: zeros().at.set() would briefly hold two
    # full states (16 GB at 30q -> guaranteed OOM on a 15.75 GiB v5e)
    amps = basis_planes(0, n=n, rdt=jnp.float32,
                        shape=fused_state_shape(n))
    amps = jfn(amps)
    _ = np.asarray(amps[0, 0, :4])
    t0 = time.perf_counter()
    for _ in range(reps):
        amps = jfn(amps)
    _ = np.asarray(amps[0, 0, :4])
    dt = (time.perf_counter() - t0) / reps
    gb = 2 * 2 * (1 << n) * 4 / 2**30
    out(mode=mode, kind=kind, d=d, n=n,
        rows_bits=os.environ.get("QUEST_ROWS_EFF_BITS", "default"),
        ms=round(dt * 1e3, 2), eff_gb_s=round(gb / dt, 1))
else:  # bench step
    from quest_tpu.circuit import Circuit
    from quest_tpu.state import basis_planes, fused_state_shape
    rng = np.random.default_rng(42)
    c = Circuit(n)
    for i in range(16):
        c.rx(1 + i %% (n - 1), float(rng.uniform(0, 2 * np.pi)))
    iters = 8
    step = c.compiled_fused(n, density=False, donate=True, iters=iters)
    shape = fused_state_shape(n)
    s = basis_planes(0, n=n, rdt=jnp.float32, shape=shape)
    s = step(s)
    jax.block_until_ready(s)
    t0 = time.perf_counter()
    for _ in range(reps):
        s = step(s)
    jax.block_until_ready(s)
    dt = (time.perf_counter() - t0) / reps
    gps = 16 * iters / dt
    out(mode=mode, n=n,
        rows_bits=os.environ.get("QUEST_ROWS_EFF_BITS", "default"),
        prec=os.environ.get("QUEST_MATMUL_PRECISION", "highest"),
        ms_per_application=round(dt / iters * 1e3, 2),
        gates_per_sec=round(gps, 1))
"""


def run(mode, n, env=None, **kw):
    params = dict(repo=REPO, mode=mode, n=n, reps=kw.pop("reps", 6),
                  kind=kw.pop("kind", ""), d=kw.pop("d", 0))
    code = WORKER % params
    e = dict(os.environ)
    e.update(env or {})
    try:
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=1200, env=e, cwd=REPO)
    except subprocess.TimeoutExpired:
        print(f"[sweep] TIMEOUT mode={mode} env={env}", flush=True)
        return None
    for line in r.stdout.splitlines():
        if line.startswith("[sweep-result]"):
            print(line, flush=True)
            return json.loads(line[len("[sweep-result]"):])
    print(f"[sweep] FAILED mode={mode} env={env}: "
          f"{r.stdout[-400:]} {r.stderr[-1500:]}", flush=True)
    return None


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 30
    results = []

    # E1: pass baseline vs block size (single butterfly, ~zero flops)
    for bits in ("10", "11", "12", "13"):
        results.append(run("segment", n, kind="sc", d=2,
                           env={"QUEST_ROWS_EFF_BITS": bits}))

    # E2: MXU cost vs dot dim at the default block size
    for d in (128, 16, 8):
        results.append(run("segment", n, kind="scb", d=d))

    # E3: the bench step at default and best block size, both tiers
    best = None
    e1 = [r for r in results[:4] if r]
    if e1:
        best = min(e1, key=lambda r: r["ms"])["rows_bits"]
    envs = [{}]
    if best and best != "12":
        envs.append({"QUEST_ROWS_EFF_BITS": best})
    envs.append({"QUEST_MATMUL_PRECISION": "high"})
    if best and best != "12":
        envs.append({"QUEST_MATMUL_PRECISION": "high",
                     "QUEST_ROWS_EFF_BITS": best})
    for e in envs:
        results.append(run("bench", n, env=e))

    print(json.dumps([r for r in results if r], indent=1))


if __name__ == "__main__":
    main()
