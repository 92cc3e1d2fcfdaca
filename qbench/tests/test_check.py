"""The comparison that decides `correct`, driven through a whole run on
the CPU at a small size: sound runs pass; the control and each fault the
cells can have fail. (Neither cell runs across chips, so the fault of a
skipped exchange has no place here.)"""

import jax.numpy as jnp
import pytest

from qbench.tests.helpers import CELLS, run_small

# a QFT cell that BENCHMARK.json does not hold: a cell made of files alone
QFT = {"name": "sv30_f32.qft", "config": "sv30_f32", "traffic": "qft",
       "chips": 1}


def _unchanged(compiled):
    """A step that returns its state unchanged."""
    return lambda s: s


def _half(compiled):
    """Half of the register left out: the upper half of the rows keeps
    its input."""
    def run(s):
        keep = jnp.copy(s)
        out = compiled(s)
        half = out.shape[1] // 2
        return out.at[:, half:].set(keep[:, half:])
    return run


def _altered(compiled):
    """One amplitude altered where it is produced: an ordinary one, the
    amplitude nearest the median magnitude, negated. (It is picked from
    the output, since the last input follows the window's application
    count; a negated amplitude a reads proj_gap 2|a| over the norm.)"""
    def run(s):
        out = compiled(s)
        mag = (out[0] ** 2 + out[1] ** 2).reshape(-1)
        row, lane = jnp.divmod(jnp.argmin(jnp.abs(mag - jnp.median(mag))),
                               out.shape[2])
        return out.at[:, row, lane].multiply(-1.0)
    return run


def _bf16(compiled):
    """Stand-in for the control on the CPU, where a DEFAULT-precision dot
    is exact: the output rounded once to bfloat16 (the control on the chip,
    QUEST_MATMUL_PRECISION=default, rounds at every MXU product)."""
    return lambda s: compiled(s).astype(jnp.bfloat16).astype(jnp.float32)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = run_small(cell)
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "check"
    assert set(res["metrics"]) == {"apply_s", "setup_s"}


@pytest.mark.parametrize("fault", [_unchanged, _half, _altered, _bf16])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_caught(cell, fault):
    res = run_small(cell, program_hook=fault)
    assert not res["correct"], res["check"]
    assert res["failed"] == 1


def test_seed_changes_inputs_not_program():
    a = run_small("sv30_f32.rcs_d20", seed=1)
    b = run_small("sv30_f32.rcs_d20", seed=2**40 + 3)
    assert a["correct"] and b["correct"]
    assert a["check"]["proj_gap"]["value"] != b["check"]["proj_gap"]["value"]


@pytest.mark.parametrize("fault", [None, _unchanged, _altered, _bf16])
def test_cell_from_files_alone(fault):
    res = run_small(QFT, program_hook=fault, limits={"proj_gap": 1e-3})
    assert res["correct"] == (fault is None), res["check"]
