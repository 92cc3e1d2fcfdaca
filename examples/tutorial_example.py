"""Port of the reference tutorial (examples/tutorial_example.c) using the
QuEST-compatible API — every call maps 1:1 onto the reference's.

Expected output (matches the reference binary):
  Probability amplitude of |111>: 0.112422
  Probability of qubit 2 being in state 1: 0.749178
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from quest_tpu.api import (
    createQuESTEnv, createQureg, destroyQureg, destroyQuESTEnv,
    reportQuregParams, reportQuESTEnv, startRecordingQASM, printRecordedQASM,
    hadamard, controlledNot, rotateY, multiControlledPhaseFlip, unitary,
    compactUnitary, rotateAroundAxis, controlledCompactUnitary,
    multiControlledUnitary, multiQubitUnitary, createComplexMatrixN,
    getProbAmp, calcProbOfOutcome, measure, measureWithStats,
)


def main():
    # prepare our environment and register (ref tutorial_example.c:19-37)
    env = createQuESTEnv()
    qubits = createQureg(3, env)

    print("\nThis is our environment:")
    reportQuregParams(qubits)
    reportQuESTEnv(env)

    startRecordingQASM(qubits)

    # apply circuit (ref tutorial_example.c:50-82)
    hadamard(qubits, 0)
    controlledNot(qubits, 0, 1)
    rotateY(qubits, 2, 0.1)

    multiControlledPhaseFlip(qubits, [0, 1, 2])

    u = np.array([[0.5 + 0.5j, 0.5 - 0.5j],
                  [0.5 - 0.5j, 0.5 + 0.5j]])
    unitary(qubits, 0, u)

    a = 0.5 + 0.5j
    b = 0.5 - 0.5j
    compactUnitary(qubits, 1, a, b)

    v = (1.0, 0.0, 0.0)
    rotateAroundAxis(qubits, 2, 3.14 / 2, v)

    controlledCompactUnitary(qubits, 0, 1, a, b)

    multiControlledUnitary(qubits, [0, 1], 2, u)

    toff = createComplexMatrixN(3)
    toff[6, 7] = 1
    toff[7, 6] = 1
    for i in range(6):
        toff[i, i] = 1
    multiQubitUnitary(qubits, [0, 1, 2], toff)

    # study the quantum state (ref tutorial_example.c:89-105)
    print("\nCircuit output:")

    prob = getProbAmp(qubits, 7)
    print(f"Probability amplitude of |111>: {prob:g}")

    prob = calcProbOfOutcome(qubits, 2, 1)
    print(f"Probability of qubit 2 being in state 1: {prob:g}")

    outcome = measure(qubits, 0)
    print(f"Qubit 0 was measured in state {outcome}")

    outcome, prob = measureWithStats(qubits, 2)
    print(f"Qubit 2 collapsed to {outcome} with probability {prob:g}")

    print("\nRecorded QASM:")
    printRecordedQASM(qubits)

    destroyQureg(qubits, env)
    destroyQuESTEnv(env)


if __name__ == "__main__":
    main()
