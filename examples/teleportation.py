"""Quantum teleportation — a fully-compiled dynamic circuit.

Teleports a random single-qubit state from qubit 0 to qubit 2 using a
Bell pair, two MID-CIRCUIT measurements, and CLASSICALLY-CONTROLLED
corrections (Circuit.measure / x_if / z_if). The entire protocol —
entangling gates, outcome draws, collapses, and feed-forward — is ONE
compiled XLA program taking a PRNG key; the reference must return to the
host after each measurement to branch.

Self-checking: for every key, qubit 2's post-protocol state equals the
input state exactly (fidelity 1 up to float rounding), regardless of
which of the four outcome branches was taken.

Run: python examples/teleportation.py
"""

import numpy as np

THETA, PHI = 1.0471975511965976, 0.6


def teleport_circuit():
    from quest_tpu.circuit import Circuit

    c = Circuit(3)
    # the state to teleport, on qubit 0: Ry(theta) then phase(phi)
    c.ry(0, THETA)
    c.phase(0, PHI)
    # Bell pair between 1 (Alice) and 2 (Bob)
    c.h(1)
    c.cnot(1, 2)
    # Bell-basis measurement of (0, 1)
    c.cnot(0, 1)
    c.h(0)
    c.measure(0)          # outcome index 0
    c.measure(1)          # outcome index 1
    # feed-forward corrections on Bob's qubit
    c.x_if(2, (1, 1))
    c.z_if(2, (0, 1))
    return c


def main():
    import jax
    jax.config.update("jax_enable_x64", True)   # 3 qubits: exactness over speed

    import quest_tpu as qt
    from quest_tpu.state import to_dense

    want = np.zeros(2, dtype=complex)
    want[0] = np.cos(THETA / 2)
    want[1] = np.sin(THETA / 2) * np.exp(1j * PHI)

    c = teleport_circuit()
    branches = set()
    for s in range(24):
        q, outs = c.apply_measured(qt.create_qureg(3, dtype=np.complex128),
                                   jax.random.PRNGKey(s))
        outs = tuple(int(x) for x in np.asarray(outs))
        branches.add(outs)
        v = to_dense(q).reshape(2, 2, 2)       # [q2, q1, q0] (little-endian)
        # qubits 0,1 are collapsed to |outs>; extract Bob's state
        bob = v[:, outs[1], outs[0]]
        fid = abs(np.vdot(want, bob)) ** 2
        assert fid > 1 - 1e-10, f"branch {outs}: fidelity {fid}"
    print(f"teleported across outcome branches {sorted(branches)}: "
          f"fidelity 1.0 on every key")
    assert len(branches) >= 3, "expected to see several outcome branches"
    print("OK")


if __name__ == "__main__":
    main()
