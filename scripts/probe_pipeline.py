"""Why don't stacked segments overlap MXU compute under the DMA stream?

The round-3 cost model (docs/KERNELS.md) measured multi-stage segments
at DMA + compute SERIAL (bench 3-stage: ~80 ms/pass at 30q vs the 34.7
pass baseline + ~45 ms summed stage cost), while single-stage segments
hide their compute almost entirely. Automatic Pallas pipelining should
give max(DMA, compute). Hypotheses, each one experiment (28q so a
non-aliased variant fits HBM):

  H1  input_output_aliases breaks the pipeliner's overlap (conservative
      buffer-level hazard between block i's store and block i+1's load).
      -> same segment with and without aliasing.
  H2  dimension semantics: grid marked arbitrary serializes. -> parallel.
  H3  neither: the compute genuinely saturates a shared resource.

Each case runs in a subprocess (one compile failure must not kill the
matrix). Usage: python scripts/probe_pipeline.py [n]   (default 28)
"""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import json, sys, time
sys.path.insert(0, %(repo)r)
from quest_tpu.precision import enable_compile_cache
enable_compile_cache()
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

mode = %(mode)r
n = %(n)d
reps = %(reps)d

from quest_tpu.ops import pallas_band as PB

if mode != "alias":
    # strip the in-place aliasing / force dimension semantics by
    # intercepting pallas_call (probe-only: the production path keeps
    # aliasing for the 30q memory story)
    real_call = pl.pallas_call
    def patched(kernel, **kw):
        if mode == "noalias":
            kw.pop("input_output_aliases", None)
        elif mode == "parallel":
            from jax.experimental.pallas import tpu as pltpu
            grid = kw.get("grid")
            kw["compiler_params"] = pltpu.CompilerParams(
                vmem_limit_bytes=PB.VMEM_LIMIT_BYTES,
                dimension_semantics=("parallel",) * len(grid))
        return real_call(kernel, **kw)
    pl.pallas_call = patched
    PB.pl.pallas_call = patched

# the bench-shaped 3-stage segment: b0 + b1 + scb8 (the measured
# "stacking exposes compute" case), identity values (perf only)
stages = []
arrays = []
g128 = np.zeros((2, 128, 128), np.float32); g128[0] = np.eye(128)
stages.append(PB.MatStage(kind="b0", dim=128, real_only=False,
                          lane_preds=(), row_preds=()))
arrays.append(jnp.asarray(g128))
stages.append(PB.MatStage(kind="b1", dim=128, real_only=False,
                          lane_preds=(), row_preds=()))
arrays.append(jnp.asarray(g128))
d = 8; w = 3
g8 = np.zeros((2, d, d), np.float32); g8[0] = np.eye(d)
stages.append(PB.MatStage(kind="scb", bit=n - 7 - w, dim=d,
                          real_only=False, lane_preds=(), row_preds=()))
arrays.append(jnp.asarray(g8))

fn = PB.compile_segment(stages, n)
donate = (0,) if mode == "alias" else ()
jfn = jax.jit(lambda a: fn(a, arrays), donate_argnums=donate)
from quest_tpu.state import basis_planes, fused_state_shape
amps = basis_planes(0, n=n, rdt=jnp.float32, shape=fused_state_shape(n))
out = jfn(amps)
_ = np.asarray(out[0, 0, :4])
if mode == "alias":
    amps = out
t0 = time.perf_counter()
for _ in range(reps):
    if mode == "alias":
        amps = jfn(amps)
    else:
        out = jfn(amps)
_ = np.asarray((amps if mode == "alias" else out)[0, 0, :4])
dt = (time.perf_counter() - t0) / reps
gb = 2 * 2 * (1 << n) * 4 / 2**30
print("[probe-result] " + json.dumps(dict(
    mode=mode, n=n, ms=round(dt * 1e3, 2),
    eff_gb_s=round(gb / dt, 1))), flush=True)
"""


def run(mode, n, reps=8):
    code = WORKER % dict(repo=REPO, mode=mode, n=n, reps=reps)
    try:
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=1200, cwd=REPO)
    except subprocess.TimeoutExpired:
        print(f"[probe] TIMEOUT mode={mode}", flush=True)
        return None
    for line in r.stdout.splitlines():
        if line.startswith("[probe-result]"):
            print(line, flush=True)
            return json.loads(line[len("[probe-result]"):])
    print(f"[probe] FAILED mode={mode}: {r.stdout[-400:]} "
          f"{r.stderr[-1500:]}", flush=True)
    return None


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 28
    for mode in ("alias", "noalias", "parallel"):
        run(mode, n)


if __name__ == "__main__":
    main()
