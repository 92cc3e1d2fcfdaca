"""Entry points and chip-session scripts that run without a TPU: the
driver's entry() on the suite's CPU platform and scripts/ab_silicon.py's
generated worker code.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(rel):
    with open(os.path.join(REPO, rel)) as f:
        return f.read()


def test_graft_entry_on_the_suite_cpu():
    """entry() on the CPU platform the suite pins: returns (fn, args)
    whose jitted application preserves the norm — the driver's
    compile-check surface. entry() takes its size from the platform JAX
    already runs on and never switches it (its TPU branch runs on the
    chip)."""
    import jax
    import numpy as np

    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    norm = float(np.sum(np.asarray(out, dtype=np.float64) ** 2))
    assert abs(norm - 1.0) < 1e-5


def _load_ab_silicon():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "ab_silicon", os.path.join(REPO, "scripts", "ab_silicon.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_ab_silicon_worker_code_compiles():
    """The one-session silicon A/B bundle (scripts/ab_silicon.py,
    ISSUE 11): every worker mode's generated subprocess code must be
    valid Python for both chip and smoke parameterizations — a
    template typo otherwise only surfaces ON the chip session it was
    supposed to serve."""
    ab = _load_ab_silicon()
    for mode in ("bench", "batch", "sharded"):
        for interpret in (0, 1):
            code = ab.WORKER % dict(repo=ab.REPO, mode=mode, n=10,
                                    reps=1, batch=2, interpret=interpret)
            compile(code, f"<ab-worker:{mode}>", "exec")


def test_ab_silicon_covers_the_flagged_debts():
    """The A/B matrix must sweep every knob shipped with a 'validate
    on first chip run' note: the pipeline knob (this round), the
    legacy slot count, sweep fusion (PR 3), the batch grid (PR 4) and
    exchange slicing (PR 8) — dropping one silently reopens its debt."""
    src = _read("scripts/ab_silicon.py")
    for knob in ("QUEST_FUSED_PIPELINE", "QUEST_FUSED_NBUF",
                 "QUEST_SWEEP_FUSION", "QUEST_EXCHANGE_SLICES",
                 "QUEST_EXCHANGE_SLICES_DCI", "QUEST_COMM_TOPOLOGY"):
        assert knob in src, knob
    assert "compiled_batched" in src and "lax.map" in src


@pytest.mark.slow
def test_ab_silicon_smoke_runs():
    """Full CPU smoke of the A/B matrix: every experiment runs in its
    subprocess (interpret-mode kernels) and the report carries a
    result or an explicit skip for each — the structure a chip session
    will emit. Slow: ~2-4 min of subprocess compiles."""
    import json
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "ab_silicon.py"),
         "--smoke"],
        cwd=REPO, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-800:]
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("[ab-silicon] {")][-1]
    rec = json.loads(line[len("[ab-silicon] "):])
    assert set(rec) >= {"pipeline", "nbuf", "sweep_fusion",
                        "batch_grid", "exchange_slices",
                        "exchange_slices_dci"}
    for v in ("1", "0"):
        assert "error" not in rec["pipeline"][v], rec["pipeline"][v]
    assert "error" not in rec["batch_grid"], rec["batch_grid"]

