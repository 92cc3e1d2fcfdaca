"""Shor's algorithm: factor 15 by quantum order finding.

The full textbook pipeline on the simulator: an 8-qubit counting
register drives controlled modular-multiplication permutations
U_b |x> = |b*x mod 15> on a 4-qubit work register (each a 16x16
permutation matrix applied through the general multi-qubit unitary
path, ref QuEST_cpu.c:1814-1898's op class), then the inverse QFT via
Circuit.inverse(), measurement, and the CLASSICAL half: continued
fractions on the measured phase to recover the order r, and
gcd(a^{r/2} +- 1, M) for the factors.

Self-checking: a=7 has order 4 mod 15, so the algorithm must recover
the factors {3, 5}; the counting distribution concentrates on
multiples of 2^t/r = 64 and the assertion requires >= 90% of shots
there (the ideal distribution puts ALL mass there since r | 2^t).

Run: python examples/shor_factoring.py
"""

import math
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

M = 15          # the number to factor
A = 7           # coprime base: order 4 mod 15
T_BITS = 8      # counting precision: 2 * ceil(log2 M)
W_BITS = 4      # work register: ceil(log2 M)


def mod_mult_matrix(b, m, w):
    """Permutation |x> -> |b*x mod m> on w qubits (identity above m).
    Matrix index bit j corresponds to targets[j], matching the
    framework's multi-qubit operand convention."""
    d = 1 << w
    u = np.zeros((d, d), dtype=np.complex128)
    for x in range(d):
        u[(b * x) % m if x < m else x, x] = 1.0
    return u


def order_finding_circuit(a, m, t, w):
    from quest_tpu.circuit import Circuit, qft_circuit

    c = Circuit(t + w)
    work = tuple(range(t, t + w))
    c.x(t)                               # work register starts in |1>
    for q in range(t):
        c.h(q)
    for k in range(t):
        b = pow(a, 1 << k, m)            # U^(2^k) is itself a mod-mult
        c.gate(mod_mult_matrix(b, m, w), work, controls=(k,))
    iqft = qft_circuit(t).inverse()
    for op in iqft.ops:
        c.ops.append(op)
    return c


def order_from_phase(y, t, m, a=A):
    """Continued-fraction convergents of y/2^t; the order is the first
    denominator r < m with a^r = 1 (mod m)."""
    frac = y / (1 << t)
    # expand y/2^t and test each convergent's denominator
    num, den = y, 1 << t
    coeffs = []
    while den:
        coeffs.append(num // den)
        num, den = den, num % den
    for upto in range(1, len(coeffs) + 1):
        # rebuild the convergent from the truncated expansion
        p, q = 1, 0
        for c in reversed(coeffs[:upto]):
            p, q = c * p + q, p
        if q < m and q > 0 and abs(frac - (p / q if q else 0)) <= 1 / (1 << (t // 2 + 1)):
            if pow(a, q, m) == 1:
                return q
    return None


def main():
    import jax

    import quest_tpu as qt
    from quest_tpu import measurement as meas

    circ = order_finding_circuit(A, M, T_BITS, W_BITS)
    q = qt.create_qureg(T_BITS + W_BITS)
    q = circ.apply_banded(q)

    shots = np.asarray(meas.sample(q, 128, jax.random.PRNGKey(15)))
    counting = shots & ((1 << T_BITS) - 1)

    # ideal distribution: r | 2^t, so ALL mass sits on multiples of 2^t/r
    step = (1 << T_BITS) // 4
    on_peak = np.mean(counting % step == 0)
    print(f"counting outcomes concentrate on multiples of {step}: "
          f"{on_peak:.0%} of shots")
    assert on_peak >= 0.9, f"phase distribution off the order-4 peaks: {on_peak}"

    orders = [order_from_phase(int(y), T_BITS, M) for y in counting if y]
    r = next(o for o in orders if o)
    print(f"recovered order r = {r} (a={A} mod {M})")
    assert pow(A, r, M) == 1 and r == 4

    f1 = math.gcd(pow(A, r // 2) - 1, M)
    f2 = math.gcd(pow(A, r // 2) + 1, M)
    print(f"factors: {M} = {f1} x {f2}")
    assert sorted((f1, f2)) == [3, 5], (f1, f2)
    print("OK")


if __name__ == "__main__":
    main()
