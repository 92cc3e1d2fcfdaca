"""The textbook quantum Fourier transform on the whole register.

From the definition (Nielsen and Chuang, section 5.1), in the gate order
of quest_tpu.circuit.qft_circuit: for each qubit q from the top, `h` on q,
then a controlled phase pi / 2^(q - j) on (j, q) for every j below q;
then `swap(q, n - 1 - q)` for the lower half. With amplitude index bit q
for qubit q, the output is the normalised inverse DFT of the input,
numpy's ifft(x, norm="ortho"). The traffic file holds no parameter of
the circuit. Nothing here imports the program.
"""

from __future__ import annotations

from typing import List

import numpy as np

from qbench.generators import Op


def ops(traffic: dict, num_qubits: int) -> List[Op]:
    out: List[Op] = []
    for q in reversed(range(num_qubits)):
        out.append(Op("h", (q,)))
        out.extend(Op("cphase", (j, q), float(np.pi / (1 << (q - j))))
                   for j in range(q))
    out.extend(Op("swap", (q, num_qubits - 1 - q))
               for q in range(num_qubits // 2))
    return out
