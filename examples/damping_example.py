"""Port of the reference damping demo (examples/damping_example.c), 1:1
through the compatible API: repeated amplitude damping of a |+> qubit held
as a density matrix."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from quest_tpu.api import (
    createQuESTEnv, createDensityQureg, destroyQureg, destroyQuESTEnv,
    initPlusState, mixDamping, reportStateToScreen,
)


def main():
    env = createQuESTEnv()

    print("-------------------------------------------------------")
    print("Running quest_tpu damping example:\n\t Basic circuit involving "
          "damping of a qubit.")
    print("-------------------------------------------------------")

    qubits = createDensityQureg(1, env)
    initPlusState(qubits)

    print("\n Reporting the qubit state to screen:")
    reportStateToScreen(qubits, env, 0)

    print("\n Applying damping 10 times with probability 0.1 ")
    for counter in range(10):
        mixDamping(qubits, 0, 0.1)
        print(f"\n Qubit state after applying damping {counter + 1} times:")
        reportStateToScreen(qubits, env, 0)

    destroyQureg(qubits, env)
    destroyQuESTEnv(env)


if __name__ == "__main__":
    main()
