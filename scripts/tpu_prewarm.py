"""Pre-warm the persistent compile cache with the flagship programs.

XLA+Mosaic compilation of the 30q fused RCS program costs ~70 s cold
(VERDICT r2); all quest_tpu entry points share one persistent cache
(quest_tpu.precision.enable_compile_cache), so compiling the common
programs ONCE here makes every later cold process — bench.py, the driver
entry points, a user's first circuit — a disk-cache load instead.

Run on the machine that holds the chip:
    python scripts/tpu_prewarm.py
Warms: the bench ladder shapes (30/28/26/24/22q fused+banded steps) and
RCS 30q depth-20. Safe to re-run; warm entries are no-ops.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    from quest_tpu.precision import enable_compile_cache
    enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("[prewarm] no TPU; nothing to warm for the chip")

    import jax.numpy as jnp

    from quest_tpu.circuit import random_circuit
    from quest_tpu.state import basis_planes, fused_state_shape

    import bench as B

    for n in (22, 24, 26, 28, 30):
        for engine in ("fused", "banded"):
            if engine == "banded" and not B.banded_fits(n):
                continue  # would OOM after ~20 min of compile (see bench)
            t0 = time.perf_counter()
            try:
                c = B._build_circuit(n)
                if engine == "fused":
                    step = c.compiled_fused(n, density=False, donate=True,
                                            iters=B.INNER_STEPS)
                    shape = fused_state_shape(n)
                else:
                    step = c.compiled_banded(n, density=False, donate=True,
                                             iters=B.INNER_STEPS)
                    shape = (2, 1 << n)
                s = step(basis_planes(0, n=n, rdt=jnp.float32, shape=shape))
                del s, step
                print(f"[prewarm] bench {engine} {n}q: "
                      f"{time.perf_counter()-t0:.1f}s", file=sys.stderr)
            except Exception as e:  # a failed size must not block the rest
                print(f"[prewarm] bench {engine} {n}q FAILED: {e!r}",
                      file=sys.stderr)

    t0 = time.perf_counter()
    n = 30
    c = random_circuit(n, depth=20, seed=7, entangler="cz")
    step = c.compiled_fused(n, density=False, donate=True)
    s = step(basis_planes(0, n=n, rdt=jnp.float32,
                          shape=fused_state_shape(n)))
    del s, step
    print(f"[prewarm] rcs 30q d20: {time.perf_counter()-t0:.1f}s",
          file=sys.stderr)

    # QFT 30q: the certification sweep's coldest program (290.9 s cold,
    # measured r3 — its all-to-all segment structure shares nothing with
    # the RCS/bench kernels)
    t0 = time.perf_counter()
    try:
        from quest_tpu.circuit import qft_circuit
        step = qft_circuit(n).compiled_fused(n, density=False, donate=True)
        s = step(basis_planes(0, n=n, rdt=jnp.float32,
                              shape=fused_state_shape(n)))
        del s, step
        print(f"[prewarm] qft 30q: {time.perf_counter()-t0:.1f}s",
              file=sys.stderr)
    except Exception as e:
        print(f"[prewarm] qft 30q FAILED: {e!r}", file=sys.stderr)

    # the driver's entry() compile-check program (28q depth-4 RCS on
    # the fused engine): not covered by any of the above — it is a
    # different circuit than the bench/RCS programs, and the driver
    # should pay a cache load, not a fresh compile
    t0 = time.perf_counter()
    try:
        import jax

        import __graft_entry__ as g
        fn, args = g.entry()
        jax.jit(fn).lower(*args).compile()
        print(f"[prewarm] graft entry: {time.perf_counter()-t0:.1f}s",
              file=sys.stderr)
    except Exception as e:
        print(f"[prewarm] graft entry FAILED: {e!r}", file=sys.stderr)


if __name__ == "__main__":
    main()
