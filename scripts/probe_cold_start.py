"""Pin the first-execution cost: per-KERNEL or per-BYTE?

Round-3 measured a fresh process paying 51-266 s before its first step
completes even with a fully warm XLA cache, through the remote backend
of that time. VERDICT r3 item 5 asks whether shrinking the
distinct Mosaic-kernel count would cut it, or whether the cost tracks
program SIZE. The existing numbers already hint per-byte (QFT-30: only
8 distinct kernels, 266 s; bench: few kernels, small program, 8-14 s);
this probe separates the variables with two synthetic programs of the
SAME total size and very different kernel counts:

  one-kernel   ONE segment structure applied k times (operands differ,
               structure shared -> 1 Mosaic kernel, large program)
  k-kernels    k structurally DISTINCT segments (phase-predicate
               layouts force distinct geometries via scattered bits),
               same program length

Each runs in a FRESH subprocess twice: run 1 (cold process, warm XLA
disk cache after the first iteration) and run 2 (second fresh process)
— the difference between programs at matched size is the per-kernel
cost; the growth with k at matched kernel count is the per-byte cost.

Usage: python scripts/probe_cold_start.py [n] [k]   (default 26, 24)
"""
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import json, sys, time
sys.path.insert(0, %(repo)r)
t_import0 = time.perf_counter()
from quest_tpu.precision import enable_compile_cache
enable_compile_cache()
import jax
import jax.numpy as jnp
import numpy as np
from quest_tpu.ops import pallas_band as PB
from quest_tpu.state import basis_planes, fused_state_shape

mode = %(mode)r
n = %(n)d
k = %(k)d

stages_list = []
arrays_list = []
rng = np.random.default_rng(3)
for j in range(k):
    if mode == "one-kernel":
        bit = n - 10          # same structure every time
    else:
        bit = 3 + (j %% (n - 13))   # distinct scattered geometry per j
    g = rng.standard_normal((2, 2, 2)).astype(np.float32)
    stages_list.append([PB.MatStage("sc", 2, False, (), (), bit)])
    arrays_list.append([jnp.asarray(g)])

fns = [PB.compile_segment(st, n) for st in stages_list]

def program(amps):
    for fn, arrs in zip(fns, arrays_list):
        amps = fn(amps, arrs)
    return amps

jfn = jax.jit(program, donate_argnums=(0,))
amps = basis_planes(0, n=n, rdt=jnp.float32, shape=fused_state_shape(n))
t0 = time.perf_counter()
amps = jfn(amps)
_ = np.asarray(amps[0, 0, :4])
first = time.perf_counter() - t0
t0 = time.perf_counter()
amps = jfn(amps)
_ = np.asarray(amps[0, 0, :4])
steady = time.perf_counter() - t0
print("[probe-result] " + json.dumps(dict(
    mode=mode, n=n, k=k,
    first_s=round(first, 2), steady_s=round(steady, 3))), flush=True)
"""


def run(mode, n, k):
    code = WORKER % dict(repo=REPO, mode=mode, n=n, k=k)
    t0 = time.time()
    try:
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=2400, cwd=REPO)
    except subprocess.TimeoutExpired:
        print(f"[probe] TIMEOUT mode={mode} k={k}", flush=True)
        return None
    wall = time.time() - t0
    for line in r.stdout.splitlines():
        if line.startswith("[probe-result]"):
            rec = json.loads(line[len("[probe-result]"):])
            rec["process_wall_s"] = round(wall, 1)
            print("[probe-result] " + json.dumps(rec), flush=True)
            return rec
    print(f"[probe] FAILED mode={mode} k={k}: {r.stdout[-300:]} "
          f"{r.stderr[-1200:]}", flush=True)
    return None


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 26
    k = int(sys.argv[2]) if len(sys.argv) > 2 else 24
    for mode in ("one-kernel", "k-kernels"):
        # twice: first process populates the persistent XLA cache for
        # this structure set; the second isolates the relay cost
        run(mode, n, k)
        run(mode, n, k)
    # size scaling at fixed kernel count
    run("one-kernel", n, k * 2)


if __name__ == "__main__":
    main()
