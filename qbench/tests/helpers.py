"""Small cells for the CPU tests: the real traffic files, cut to the
register size its kind gives for tests (SMALL_QUBITS of
qbench/registers/<kind>.py) and to a depth of 2 where the traffic has
one."""

import importlib
import json
import os

from qbench import run as RUN

ROOT = RUN.ROOT
# the CPU has no row in peaks.json; the tests give it the v5e's
PEAKS = RUN._load("peaks.json")
PEAKS["devices"]["cpu"] = PEAKS["devices"]["TPU v5 lite"]


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


CELLS = sorted(w["name"] for w in bench()["workloads"])


def small_cell(cell, depth=2):
    """(cell, config, traffic) of a cell, given as its BENCHMARK.json
    entry or its name there, at the CPU's size."""
    if isinstance(cell, str):
        cell = {w["name"]: w for w in bench()["workloads"]}[cell]
    config = RUN._load("configs", cell["config"] + ".json")
    reg = importlib.import_module(f"qbench.registers.{config['register']}")
    config["qubits"] = reg.SMALL_QUBITS
    traffic = RUN._load("traffic", cell["traffic"] + ".json")
    if "depth" in traffic:
        traffic["depth"] = min(traffic["depth"], depth)
    return cell, config, traffic


def run_small(cell, *, trace=False, seconds=0.2, seed=2**31 + 7,
              program_hook=None, limits=None):
    """One small run of a cell (an entry or a name); its limits are
    qbench/cells/<name>.json's unless given."""
    cell, config, traffic = small_cell(cell)
    if limits is None:
        limits = RUN._load("cells", cell["name"] + ".json")["limits"]
    return RUN.run_cell(
        cell, config, traffic, RUN.cell_metrics(bench(), cell["name"], trace),
        limits, PEAKS, seed=seed, seconds=seconds, trace=trace,
        require_tpu=False, interpret=True, program_hook=program_hook)
