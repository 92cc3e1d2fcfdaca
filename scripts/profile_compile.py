"""Split compile latency of the flagship workloads into its phases.

VERDICT r2 weak #2: RCS 30q d20 cost 71.4 s compile+first-run against
6.76 s of execution. This harness measures, per workload:

  plan    - circuit flatten + band planning + segmentation (host Python)
  trace   - jax tracing to jaxpr/StableHLO (jit(...).lower())
  compile - XLA + Mosaic compilation (lowered.compile()); Mosaic kernel
            count comes from the segment cache
  run1    - first execution (device upload + any deferred work)

Run on the chip:   python scripts/profile_compile.py [n] [depth]
Also meaningful on CPU for the plan/trace phases (compile there measures
XLA:CPU, not Mosaic). A warm persistent cache (the default; see
quest_tpu.precision.enable_compile_cache) makes `compile` ~disk-load —
run twice to see cold vs warm.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(n=30, depth=20):
    from quest_tpu.precision import enable_compile_cache
    enable_compile_cache()

    import jax
    import jax.numpy as jnp

    from quest_tpu.circuit import random_circuit
    from quest_tpu.ops import fusion as F
    from quest_tpu.ops import pallas_band as PB
    from quest_tpu.state import basis_planes, fused_state_shape

    rec = {"n": n, "depth": depth,
           "platform": jax.devices()[0].platform}

    t0 = time.perf_counter()
    c = random_circuit(n, depth=depth, seed=7, entangler="cz")
    items = F.plan(c._flat_ops(n, False), n, bands=PB.plan_bands(n))
    parts = PB.segment_plan(items, n)
    keys = {tuple(p[1]) for p in parts if p[0] == "segment"}
    rec["plan_s"] = round(time.perf_counter() - t0, 2)
    rec["segments"] = sum(1 for p in parts if p[0] == "segment")
    rec["distinct_kernels"] = len(keys)

    interp = rec["platform"] != "tpu"  # CPU: interpreter
    rec["interpret"] = interp

    t0 = time.perf_counter()
    step = c.compiled_fused(n, density=False, donate=True, interpret=interp)
    shape = fused_state_shape(n)
    s = basis_planes(0, n=n, rdt=jnp.float32, shape=shape)
    lowered = jax.jit(
        lambda a: step(a), donate_argnums=()).lower(
            jax.ShapeDtypeStruct(shape, jnp.float32))
    rec["trace_s"] = round(time.perf_counter() - t0, 2)

    t0 = time.perf_counter()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.perf_counter() - t0, 2)

    # sync without reading through .reshape(2, -1), which would force a
    # full relayout copy of the tiled state on device (8 GB at 30q ->
    # OOM next to the live state on a 16 GB v5e)
    t0 = time.perf_counter()
    out = step(s)
    jax.block_until_ready(out)
    rec["run1_s"] = round(time.perf_counter() - t0, 2)

    t0 = time.perf_counter()
    out = step(out)
    jax.block_until_ready(out)
    rec["steady_s"] = round(time.perf_counter() - t0, 3)
    del compiled
    print(json.dumps(rec))


if __name__ == "__main__":
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 30
    depth = int(sys.argv[2]) if len(sys.argv) > 2 else 20
    main(n, depth)
