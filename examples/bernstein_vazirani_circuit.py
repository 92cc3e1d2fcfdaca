"""Port of the reference Bernstein--Vazirani circuit
(examples/bernstein_vazirani_circuit.c), 1:1 through the compatible API."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from quest_tpu.api import (
    createQuESTEnv, createQureg, destroyQureg, destroyQuESTEnv,
    initZeroState, pauliX, controlledNot, calcProbOfOutcome,
)


def main():
    # model parameters (ref bernstein_vazirani_circuit.c:20-22)
    num_qubits = 9
    secret_num = 2 ** 4 + 1

    env = createQuESTEnv()

    # create qureg; let zeroth qubit be ancilla
    qureg = createQureg(num_qubits, env)
    initZeroState(qureg)

    # NOT the ancilla
    pauliX(qureg, 0)

    # CNOT secretNum bits with ancilla
    bits = secret_num
    for qb in range(1, num_qubits):
        bit = bits % 2
        bits //= 2
        if bit:
            controlledNot(qureg, 0, qb)

    # calculate prob of solution state
    success_prob = 1.0
    bits = secret_num
    for qb in range(1, num_qubits):
        bit = bits % 2
        bits //= 2
        success_prob *= calcProbOfOutcome(qureg, qb, bit)

    print(f"solution reached with probability {success_prob:f}")

    destroyQureg(qureg, env)
    destroyQuESTEnv(env)


if __name__ == "__main__":
    main()
