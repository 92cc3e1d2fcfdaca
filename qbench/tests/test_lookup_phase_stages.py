"""The QFT cell's reader of the block-local lookup counter
(`lookup_phase_stages`), beside the trig path's."""

import pytest

from quest_tpu import profiling

from qbench import run as RUN
from qbench.metrics import lookup_phase_stages
from qbench.tests import helpers


def _run_qft(qubits):
    """A traced run of the QFT cell's traffic at `qubits` on the CPU."""
    cell, config, traffic = helpers.small_cell("qft30_f32.qft")
    config["qubits"] = qubits
    limits = RUN._load("cells", cell["name"] + ".json")["limits"]
    return RUN.run_cell(
        cell, config, traffic,
        RUN.cell_metrics(helpers.bench(), cell["name"], True), limits,
        helpers.PEAKS, seed=2**31 + 7, seconds=0.1, trace=True,
        require_tpu=False, interpret=True)


# at 10 qubits one block of 8 rows holds all 3 row bits and both groups (9
# and 12 terms) read all three: a table would be as tall as the block, so
# they stay on trig; at 14 the block has 2^7 rows, and the two groups that
# read 3 and 2 of its row bits take the lookup
@pytest.mark.parametrize("qubits,lookup,trig", [(10, None, 2), (14, 2, 5)])
def test_traced_qft_run_reads_the_lookup_counter(qubits, lookup, trig):
    res = _run_qft(qubits)
    got = res["metrics"]
    assert got.get("lookup_phase_stages", {}).get("value") == lookup
    assert got["trig_phase_stages"]["value"] == trig
    assert res["correct"]


def test_lookup_reader_is_silent_without_the_counter():
    assert lookup_phase_stages.read({}) is None
    with profiling.recording() as rec:
        pass
    # a program without the lookup path records no lookup counter
    assert lookup_phase_stages.read({"program": rec}) is None
