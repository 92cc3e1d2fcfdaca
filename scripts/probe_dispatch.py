"""Isolate per-dispatch overhead from real kernel cost:
- empty jit on a tiny array
- identity jit on the full state (pure donate/alias)
- 1 pallas pass per dispatch vs 8 passes per dispatch
"""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "/root/repo")
from quest_tpu.precision import enable_compile_cache
enable_compile_cache()

from quest_tpu.ops import pallas_band as PB


def timeit(jfn, amps, reps, label, n, passes=1):
    amps = jfn(amps)
    _ = np.asarray(amps.ravel()[:4])
    t0 = time.perf_counter()
    for _ in range(reps):
        amps = jfn(amps)
    _ = np.asarray(amps.ravel()[:4])
    dt = (time.perf_counter() - t0) / reps
    bw = passes * 2 * 2 * (1 << n) * 4 / dt
    print(f"{label:22s}: {dt*1e3:8.3f} ms/call "
          f"({bw/1e9:7.1f} GB/s per-pass r+w x {passes})", flush=True)
    return amps


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 26
    print("devices:", jax.devices(), flush=True)

    tiny = jnp.zeros((8, 128), dtype=jnp.float32)
    jfn = jax.jit(lambda a: a + 1.0)
    timeit(jfn, tiny, 50, "tiny add", 10)

    amps = jnp.zeros((2, 1 << n), dtype=jnp.float32).at[0, 0].set(1.0)
    jfn = jax.jit(lambda a: a, donate_argnums=(0,))
    amps = timeit(jfn, amps, 20, "identity (donated)", n)

    jfn = jax.jit(lambda a: a * 1.0000001, donate_argnums=(0,))
    amps = timeit(jfn, amps, 20, "scale (1 pass)", n)

    rng = np.random.default_rng(0)
    q, _ = np.linalg.qr(rng.standard_normal((128, 128)))
    g = jnp.asarray(np.stack([q, q * 0.1]).astype(np.float32))
    seg = PB.compile_segment([PB.MatStage("b0", 128, False, (), ())], n)

    amps3 = amps.reshape(2, -1, 128)
    jfn = jax.jit(lambda a: seg(a, [g]), donate_argnums=(0,))
    amps3 = timeit(jfn, amps3, 20, "pallas b0 (1 pass)", n)

    def eight(a):
        for _ in range(8):
            a = seg(a, [g])
        return a
    jfn = jax.jit(eight, donate_argnums=(0,))
    amps3 = timeit(jfn, amps3, 20, "pallas b0 (8 passes)", n, passes=8)


if __name__ == "__main__":
    main()
