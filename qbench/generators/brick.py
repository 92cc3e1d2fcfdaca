"""Brick circuits, the generator of a traffic file that names none.

A traffic file (qbench/traffic/<name>.json) fixes the circuit as an
architecture is fixed for a model; the register size comes from the
configuration. This generator makes brick circuits: in each layer a random
rotation on every qubit, then an entangler on the pairs that start at
layer mod 2, with optional one-qubit noise after each rotation, after
each entangler (on both its qubits) and at the end of the layer.

The random draws follow quest_tpu.circuit.random_circuit exactly (one
angle then one kind per qubit, from numpy's default_rng(circuit_seed)), so
`rcs_d20` on 30 qubits is the program PR 21 timed. Nothing here imports
the program.
"""

from __future__ import annotations

from typing import List

import numpy as np

from qbench.generators import Op


def brick_circuit(traffic: dict, num_qubits: int) -> List[Op]:
    rng = np.random.default_rng(traffic["circuit_seed"])
    rotations = traffic["rotations"]
    entangler = traffic["entangler"]
    noise = traffic.get("noise", {})

    def channel(where, q):
        if where in noise:
            name, p = noise[where]
            ops.append(Op(name, (q,), float(p)))

    ops: List[Op] = []
    for d in range(traffic["depth"]):
        for q in range(num_qubits):
            angle = float(rng.uniform(0, 2 * np.pi))
            kind = int(rng.integers(0, len(rotations)))
            ops.append(Op(rotations[kind], (q,), angle))
            channel("after_rotation", q)
        for q in range(d % 2, num_qubits - 1, 2):
            ops.append(Op(entangler, (q, q + 1)))
            channel("after_entangler", q)
            channel("after_entangler", q + 1)
        for q in range(num_qubits):
            channel("end_of_layer", q)
    return ops


ops = brick_circuit
