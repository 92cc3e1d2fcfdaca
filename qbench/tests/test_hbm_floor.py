"""hbm_floor_share reads the register's bytes and peaks.json only."""

import json
import os

import pytest

from qbench import run as RUN
from qbench import trace as TR
from qbench.metrics import hbm_floor_share as H
from qbench.tests.helpers import run_small, small_cell


def _summary(busy_s, apps):
    return TR.Summary(window_s=busy_s, busy_s=busy_s, apps=apps,
                      kernel_s=0.0, other_s=busy_s, op_totals=[],
                      idle_gaps=[])


def test_peaks_table_has_the_v5e_with_its_source():
    with open(os.path.join(RUN.HERE, "peaks.json")) as f:
        peaks = json.load(f)
    row = peaks["devices"]["TPU v5 lite"]
    assert row["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in peaks["source"]


def test_floor_share_from_bytes_and_peak_alone():
    # 8 GiB read and written once at 819 GB/s is 20.97 ms
    ctx = {"trace": _summary(busy_s=2.544 * 4, apps=4),
           "state_bytes": 8 << 30, "peak": {"hbm_bytes_per_s": 819e9}}
    share = H.read(ctx)
    assert share == pytest.approx(100 * 2 * (8 << 30) / 819e9 / 2.544)
    # no plan and no kernel list in ctx: nothing else is read
    ctx["trace"] = ctx["trace"]._replace(kernel_s=1.0, op_totals=[("k", 1)])
    assert H.read(ctx) == share


def test_floor_share_is_silent_without_device_time():
    ctx = {"trace": _summary(busy_s=0.0, apps=0), "state_bytes": 8,
           "peak": {"hbm_bytes_per_s": 1.0}}
    assert H.read(ctx) is None
    assert H.read({"trace": None}) is None


def test_unknown_device_kind_is_an_error():
    cell, config, traffic = small_cell("sv30_f32.rcs_d20")
    with pytest.raises(KeyError, match="no row for device kind"):
        RUN.run_cell(cell, config, traffic, [], {"proj_gap": 1}, {"devices": {}},
                     seed=1, seconds=0.1, trace=False, require_tpu=False,
                     interpret=True)


def test_traced_run_reports_every_per_layer_metric():
    res = run_small("dm15_f32.noisy_d2", trace=True, seconds=0.1)
    got = set(res["metrics"])
    # the CPU trace has no Pallas kernels, so kernel_ms stays out
    assert got == {"planned_sweeps", "compile_s", "mlir_lower_s",
                   "hbm_floor_share", "idle_share"}
    assert res["metrics"]["mlir_lower_s"]["value"] > 0
    assert 0 < res["metrics"]["hbm_floor_share"]["value"] < 100
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert res["breakdown"]["device_ops"] and res["correct"]
