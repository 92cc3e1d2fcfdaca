"""The QFT cell's per-layer metrics of the planner and the kernels, read
from the program's recording."""

from quest_tpu import profiling

from qbench.metrics import trig_phase_stages, vmem_cuts
from qbench.tests.helpers import run_small


def test_traced_qft_run_reads_the_program_counters():
    res = run_small("qft30_f32.qft", trace=True, seconds=0.1)
    got = res["metrics"]
    # at 10 qubits QFT is one sweep: groups of 9 and 12 terms, no cut
    assert got["trig_phase_stages"]["value"] == 2
    assert got["vmem_cuts"]["value"] == 0
    assert res["correct"]


def test_readers_are_silent_without_the_counters():
    assert vmem_cuts.read({}) is None
    assert trig_phase_stages.read({}) is None
    with profiling.recording() as rec:
        pass
    # a program without the VMEM price records no cut counter
    assert vmem_cuts.read({"program": rec}) is None
    assert trig_phase_stages.read({"program": rec}) == 0
