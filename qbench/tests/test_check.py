"""The comparison that decides `correct`, driven through a whole run on
the CPU at a small size: sound runs pass; the control and each fault the
cells can have fail. (Neither cell runs across chips, so the fault of a
skipped exchange has no place here.)"""

import jax.numpy as jnp
import pytest

from qbench.tests.helpers import SMALL, run_small

CELLS = sorted(SMALL)


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
    """One amplitude altered where it is produced."""
    return lambda s: (lambda out: out.at[:, 3, 5].multiply(-1.0))(
        compiled(s))


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
