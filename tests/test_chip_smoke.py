"""chip_smoke.py's phases at tiny sizes on the CPU (Pallas kernels in
interpret mode, the sharded phase on four of the suite's eight virtual
devices), and its refusal to run without a TPU."""

import jax
import numpy as np
import pytest

import chip_smoke as S


def test_main_fails_without_a_tpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(S.SmokeFailure, match="not a TPU"):
        S.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_phase_eager():
    S.phase_eager()


def test_phase_flagship_tiny():
    S.phase_flagship(n=11, depth=3, reps=1, interpret=True)


def test_phase_agreement_tiny():
    S.phase_agreement(n=11, depth=3, interpret=True)


def test_phase_serve_tiny():
    S.phase_serve(n_state=10, n_noisy=10, requests=3, shots=2,
                  shot_requests=2, interpret=True)


def test_phase_sharded_tiny():
    S.phase_sharded(n_known=13, n_cmp=12, depth=3, interpret=True)


def test_norm_f64_sums_in_groups():
    x = np.full((2, 4, 128), 1 / np.sqrt(1024), np.float32)
    assert S.norm_f64(jax.numpy.asarray(x)) == pytest.approx(1.0, abs=1e-6)
