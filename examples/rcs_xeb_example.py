"""Random-circuit sampling with linear-XEB fidelity — the BASELINE.json
single-chip headline workload, end to end:

  1. build a depth-d random circuit (rotation layers + CZ brick),
  2. run it through the band-fusion Pallas engine (one HBM pass per
     segment; on a v5e chip a 30-qubit depth-20 instance takes ~7 s),
  3. draw measurement shots from the final state,
  4. score them with the linear cross-entropy benchmark
     F_XEB = 2^n <p(s)> - 1  (≈1 when sampling from the true output
     distribution, ≈0 for uniform noise).

The reference stops at measurement; XEB is this framework's addition
(calculations.calc_linear_xeb). Run: python examples/rcs_xeb_example.py [n] [depth]
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from quest_tpu.precision import enable_compile_cache

enable_compile_cache()

import quest_tpu as qt
from quest_tpu import calculations as calc
from quest_tpu import measurement as meas
from quest_tpu.circuit import random_circuit


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 20
    depth = int(sys.argv[2]) if len(sys.argv) > 2 else 12
    shots = 2000

    circ = random_circuit(n, depth, seed=42)
    print(f"RCS: {n} qubits, depth {depth}, {len(circ.ops)} gates")

    q = qt.create_qureg(n)
    t0 = time.perf_counter()
    q = circ.apply_fused(q)
    probe = calc.calc_total_prob(q)  # forces completion
    dt = time.perf_counter() - t0
    print(f"simulated in {dt:.2f}s (incl. compile); norm = {probe:.8f}")

    t0 = time.perf_counter()
    import jax
    samples = meas.sample(q, shots, jax.random.key(7))
    xeb = calc.calc_linear_xeb(q, samples)
    print(f"{shots} shots in {time.perf_counter()-t0:.2f}s; "
          f"sampled linear XEB = {xeb:.3f}")

    # the meaningful check: the sampled XEB estimates the state's exact
    # collision XEB (2^n sum p^2 - 1). It approaches 1 only as the
    # circuit family converges to Porter-Thomas (deep circuits); at any
    # depth, sampler and exact value must agree.
    amps = np.asarray(q.amps, dtype=np.float64)
    p = amps[0] ** 2 + amps[1] ** 2
    exact = (1 << n) * float(np.sum(p * p) / np.sum(p)) - 1.0
    print(f"exact collision XEB of the state: {exact:.3f} "
          f"(sampler should estimate this)")

    # uniform-noise control: XEB of random bitstrings should be ~0
    rng = np.random.default_rng(0)
    noise = rng.integers(0, 1 << n, size=shots)
    xeb_noise = calc.calc_linear_xeb(q, noise)
    print(f"uniform-noise control: XEB = {xeb_noise:.4f} (expect ~0.0)")


if __name__ == "__main__":
    main()
