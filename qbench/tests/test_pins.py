"""The two cells' work, pinned at full size: the op list each traffic
file makes and the reference's passes over it, hashed as they were
before generators and gates became files of their own. A change to the
harness that alters either makes an existing cell read different work."""

import hashlib
import importlib

import numpy as np
import pytest

from qbench import generators, reference as R
from qbench import run as RUN
from qbench.tests.helpers import bench

PINS = {
    "sv30_f32.rcs_d20": (
        890, "0c836b5e834ca155c7274d554445f8f2ca287fcc21e3c5088c49d11357159a69",
        40, "03a953f30f9bc2f86af59877d337db8165607a814b86b0f10ad96ec78b97d3ea"),
    "dm15_f32.noisy_d2": (
        132, "a315494eb9a775150ceae063807696c1e7c2555ad7c963abcb0edadef24b532e",
        9, "c1c1270d8295b58e886bad19dd2db2d4a773c4942ef998dab68511747e1f3299"),
}


def ops_digest(ops):
    h = hashlib.sha256()
    for op in ops:
        h.update(repr((op.name, tuple(int(q) for q in op.qubits),
                       op.param)).encode())
    return h.hexdigest()


def passes_digest(passes):
    h = hashlib.sha256()
    for kind, body in passes:
        h.update(kind.encode())
        for key, m in (body.items() if kind == "window" else body):
            h.update(repr(tuple(int(k) for k in key)).encode())
            h.update(np.ascontiguousarray(m, np.complex128).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(PINS))
def test_full_size_work_is_pinned(name):
    cell = {w["name"]: w for w in bench()["workloads"]}[name]
    config = RUN._load("configs", cell["config"] + ".json")
    traffic = RUN._load("traffic", cell["traffic"] + ".json")
    reg = importlib.import_module(f"qbench.registers.{config['register']}")
    nq = config["qubits"]
    ops = generators.ops(traffic, nq)
    passes = R.plan_passes(reg.lower(ops, nq), reg.windows(nq))
    assert (len(ops), ops_digest(ops), len(passes),
            passes_digest(passes)) == PINS[name]
