"""Small cells for the CPU tests: the real traffic files, cut to a register
of 10 state bits and a depth of 2."""

import json
import os

from qbench import run as RUN

ROOT = RUN.ROOT
SMALL = {"sv30_f32.rcs_d20": 10, "dm15_f32.noisy_d2": 5}
# the CPU has no row in peaks.json; the tests give it the v5e's
PEAKS = RUN._load("peaks.json")
PEAKS["devices"]["cpu"] = PEAKS["devices"]["TPU v5 lite"]


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def small_cell(name, depth=2):
    cell = {w["name"]: w for w in bench()["workloads"]}[name]
    config = RUN._load("configs", cell["config"] + ".json")
    config["qubits"] = SMALL[name]
    traffic = RUN._load("traffic", cell["traffic"] + ".json")
    traffic["depth"] = min(traffic["depth"], depth)
    return cell, config, traffic


def run_small(name, *, trace=False, seconds=0.2, seed=2**31 + 7,
              program_hook=None):
    cell, config, traffic = small_cell(name)
    limits = RUN._load("cells", name + ".json")["limits"]
    return RUN.run_cell(
        cell, config, traffic, RUN.cell_metrics(bench(), name, trace),
        limits, PEAKS, seed=seed, seconds=seconds, trace=trace,
        require_tpu=False, interpret=True, program_hook=program_hook)
