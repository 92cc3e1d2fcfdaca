"""Repetition-code QEC cycles ON A DEVICE MESH — the dynamic sharded engine.

The round-5 capability this demonstrates: a dynamic circuit (gates +
mid-circuit syndrome measurements + classical feedback corrections)
compiled as ONE shard_map program over a multi-device mesh, where the
measurement-free stretches get the full static-engine treatment —
band-fusion, and the layer-amortized relabel pass per stretch
(quest_tpu/parallel/sharded.py compile_circuit_sharded_measured,
engine='banded'). The reference must host-round-trip AND MPI-broadcast
per measurement, and its measurement path communicates per-gate and
fuses nothing (QuEST_cpu_distributed.c:1244-1319).

The program: a 3-qubit bit-flip code with two syndrome ancillas runs
TWO full noise->syndrome->correct cycles, with deterministic injected
X errors (a different single data qubit each cycle). Self-checking:
every trajectory must decode back to the exact encoded state, the
syndrome outcomes must match the injected error pattern, and the
8-device trajectory must equal the single-device dynamic engine's for
the same key.

Run: python examples/qec_on_mesh.py     (bootstraps an 8-virtual-device
CPU mesh when fewer real devices are attached, like __graft_entry__)
"""

import os
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

THETA = 1.1


def build_cycle_circuit():
    """Qubits 0-2 data, 3-4 ancillas; two QEC cycles with X(0) injected
    in cycle 1 and X(2) in cycle 2. Outcome indices: cycle k uses
    4 measurements (syndrome a3, a4, then ancilla resets via
    measure+x_if)."""
    import numpy as np
    from quest_tpu.circuit import Circuit
    from quest_tpu.ops.matrices import PAULI_X

    c = Circuit(5)
    c.ry(0, THETA)
    c.cnot(0, 1)
    c.cnot(0, 2)

    out = 0
    for cycle, bad in enumerate((0, 2)):
        c.gate(PAULI_X, (bad,))           # deterministic injected error
        c.cnot(0, 3)
        c.cnot(1, 3)                      # a3 = q0 XOR q1
        c.cnot(1, 4)
        c.cnot(2, 4)                      # a4 = q1 XOR q2
        c.measure(3)                      # outcome out+0
        c.measure(4)                      # outcome out+1
        # decode: (1,0)->X on q0, (1,1)->X on q1, (0,1)->X on q2
        c.gate_if(PAULI_X, (0,), [(out, 1), (out + 1, 0)])
        c.gate_if(PAULI_X, (1,), [(out, 1), (out + 1, 1)])
        c.gate_if(PAULI_X, (2,), [(out, 0), (out + 1, 1)])
        # reset ancillas for the next cycle (measure + conditional flip)
        c.reset(3)                        # outcome out+2
        c.reset(4)                        # outcome out+3
        out += 4
    return c


def main():
    import jax
    import numpy as np

    if len(jax.devices()) < 8:
        if os.environ.get("_QEC_MESH_BOOTSTRAPPED"):
            raise RuntimeError("virtual mesh bootstrap failed")
        env = dict(os.environ)
        env["_QEC_MESH_BOOTSTRAPPED"] = "1"
        env["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if not f.startswith(
                     "--xla_force_host_platform_device_count")]
        flags.append("--xla_force_host_platform_device_count=8")
        env["XLA_FLAGS"] = " ".join(flags)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        code = ("import jax; jax.config.update('jax_enable_x64', True); "
                "import examples.qec_on_mesh as m; m.main()")
        raise SystemExit(subprocess.run(
            [sys.executable, "-c", code], env=env, cwd=repo).returncode)

    jax.config.update("jax_enable_x64", True)   # 5 qubits: exactness over speed

    import quest_tpu as qt
    from quest_tpu.parallel import make_amp_mesh
    from quest_tpu.state import to_dense

    mesh = make_amp_mesh(8)
    c = build_cycle_circuit()

    # the exact encoded state the cycles must restore
    want = np.zeros(32, dtype=complex)
    want[0b00000] = np.cos(THETA / 2)
    want[0b00111] = np.sin(THETA / 2)

    print(c.explain_sharded(mesh, engine="banded"))

    for s in range(6):
        key = jax.random.PRNGKey(s)
        q = qt.create_qureg(5, dtype=np.complex128)
        r, outs = c.apply_sharded_measured(q, key, mesh, engine="banded")
        outs = np.asarray(outs)
        # syndromes must finger the injected errors: X(0) -> (1,0),
        # X(2) -> (0,1)
        assert (outs[0], outs[1]) == (1, 0), outs
        assert (outs[4], outs[5]) == (0, 1), outs
        v = to_dense(r)
        fidelity = abs(np.vdot(want, v)) ** 2
        assert fidelity > 1 - 1e-10, (s, fidelity)
        # the mesh trajectory equals the single-device dynamic engine's
        q1 = qt.create_qureg(5, dtype=np.complex128)
        r1, o1 = c.apply_measured(q1, key)
        assert np.array_equal(np.asarray(o1), outs)
        np.testing.assert_allclose(to_dense(r1), v, atol=1e-11, rtol=0)
    print("qec_on_mesh: 6/6 trajectories decoded exactly on the "
          "8-device mesh (and match the single-device engine per key)")


if __name__ == "__main__":
    main()
