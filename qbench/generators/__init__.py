"""Circuit generators, one file each, found by a traffic file's
`generator` (`brick` where the file names none).

Each module gives `ops(traffic, num_qubits) -> list[Op]`: the traffic
file's parameters to a gate list, in circuit order. The register size
comes from the configuration. Nothing here imports the program."""

from __future__ import annotations

import importlib
from typing import List, NamedTuple, Tuple


class Op(NamedTuple):
    name: str                 # a gate file of qbench/gates
    qubits: Tuple[int, ...]
    param: float | None = None


def ops(traffic: dict, num_qubits: int) -> List[Op]:
    name = traffic.get("generator", "brick")
    return importlib.import_module(f"qbench.generators.{name}").ops(
        traffic, num_qubits)
