"""Grover's search: find a marked basis state in sqrt(2^n) iterations.

Builds the whole search as ONE traced circuit (oracle = all-ones phase
flip conjugated by X on the 0-bits of the marked string; diffusion =
all-ones phase flip conjugated by H and X), runs it through the
band-fusion engine, and verifies the analytic success probability

    p(k) = sin^2((2k + 1) * asin(1/sqrt(N)))

at the optimal iteration count — a self-checking example with no
reference analogue (the reference ships tutorial/BV/damping examples
only; see docs/api_parity.md for the API surface this drives).

Run: python examples/grover_search.py
"""

import numpy as np

N_QUBITS = 12
MARKED = 0b101101110010 & ((1 << N_QUBITS) - 1)


def grover_circuit(n, marked, iters):
    from quest_tpu.circuit import Circuit

    c = Circuit(n)
    for q in range(n):
        c.h(q)
    all_q = tuple(range(n))
    zero_bits = [q for q in range(n) if not (marked >> q) & 1]
    for _ in range(iters):
        # oracle: flip the phase of |marked>
        for q in zero_bits:
            c.x(q)
        c.cphase(np.pi, *all_q)          # all-ones phase flip (-1)
        for q in zero_bits:
            c.x(q)
        # diffusion: 2|s><s| - 1
        for q in range(n):
            c.h(q)
        for q in range(n):
            c.x(q)
        c.cphase(np.pi, *all_q)
        for q in range(n):
            c.x(q)
        for q in range(n):
            c.h(q)
    return c


def main():
    import jax

    import quest_tpu as qt
    from quest_tpu import measurement as meas

    n = N_QUBITS
    dim = 1 << n
    theta = np.arcsin(1.0 / np.sqrt(dim))
    k_opt = int(np.round(np.pi / (4 * theta) - 0.5))
    p_want = np.sin((2 * k_opt + 1) * theta) ** 2

    q = qt.create_qureg(n)
    q = grover_circuit(n, MARKED, k_opt).apply_banded(q)

    amp_re = float(q.amps[0, MARKED])
    amp_im = float(q.amps[1, MARKED])
    p_got = amp_re ** 2 + amp_im ** 2
    print(f"n={n}, marked=|{MARKED:0{n}b}>, optimal iterations k={k_opt}")
    print(f"success probability: got {p_got:.6f}, analytic {p_want:.6f}")
    assert abs(p_got - p_want) < 1e-4, "Grover amplitude off the analytic value"

    shots = np.asarray(meas.sample(q, 32, jax.random.PRNGKey(7)))
    frac = float((shots == MARKED).mean())
    print(f"32 measurement shots hit the marked state {frac:.0%} of the time")
    assert frac > 0.9
    print("OK")


if __name__ == "__main__":
    main()
