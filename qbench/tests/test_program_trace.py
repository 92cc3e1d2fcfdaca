"""qbench/program_trace.py: its reductions on a made-up device trace, and
a whole small run on the CPU with the program's recording open."""

from types import SimpleNamespace as NS

from qbench import program_trace as PT
from qbench.tests import helpers as H


def _ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


KERNEL = ('%quest_seg_mat2_r13s0_0a1b2c3d{i} = f32[2,8,128] '
          'custom-call(%p), custom_call_target="tpu_custom_call"')


def _profile():
    ops = [
        _ev(KERNEL.format(i=""), 0, 2_000_000),
        _ev(KERNEL.format(i=".1"), 2_000_000, 3_000_000),
        _ev("%copy.3 = f32[2,8,128] copy(%x)", 5_000_000, 100_000),
    ]
    marks = [_ev("quest:dma_in_wait", 0, 100_000),
             _ev("quest:stages", 100_000, 1_500_000),
             _ev("quest:dma_out_wait", 1_600_000, 50_000),
             _ev("quest:stages", 2_000_000, 2_500_000)]
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=ops), NS(name="XLA TraceMe",
                                           events=marks)])
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[_ev("quest:stages", 0, 9)])])
    return NS(planes=[device, host])


HLO = """
  %quest_seg_mat2_r13s0_0a1b2c3d.1 = f32[2,8,128]{2,1,0} custom-call(%a), \
custom_call_target="tpu_custom_call", metadata={op_name="jit(run)/quest.sweep01/quest_seg_mat2_r13s0_0a1b2c3d/pallas_call"}
  ROOT %quest_seg_mat2_r13s0_0a1b2c3d = f32[2,8,128]{2,1,0} custom-call(%b), \
custom_call_target="tpu_custom_call", metadata={op_name="jit(run)/quest.sweep00/quest_seg_mat2_r13s0_0a1b2c3d/pallas_call"}
  %copy.3 = f32[2,8,128]{2,1,0} copy(%x), metadata={op_name="jit(run)/quest.sweep02/copy"}
"""


def test_sweep_map_reads_the_scope_from_the_hlo():
    assert PT.sweep_map(HLO) == {"quest_seg_mat2_r13s0_0a1b2c3d.1": 1,
                                 "quest_seg_mat2_r13s0_0a1b2c3d": 0}


def test_kernel_rows_by_sweep_with_their_regions():
    rows = PT.kernel_rows(_profile(), PT.sweep_map(HLO))
    assert [(r["sweep"], r["kernel"]) for r in rows] == [
        (0, "quest_seg_mat2_r13s0_0a1b2c3d"),
        (1, "quest_seg_mat2_r13s0_0a1b2c3d")]
    assert [r["ms"] for r in rows] == [2.0, 3.0]
    assert [r["stages_ms"] for r in rows] == [1.5, 2.5]
    assert abs(rows[0]["dma_wait_ms"] - 0.15) < 1e-12
    assert rows[1]["dma_wait_ms"] == 0
    # without the map a kernel has no sweep, and still its name
    assert {r["sweep"] for r in PT.kernel_rows(_profile(), {})} == {None}


def test_regions_on_device_planes_only():
    got = PT.regions(_profile())
    assert got["quest:stages"]["count"] == 2
    assert got["quest:stages"]["ms"] == 4.0
    assert got["quest:stages"]["lines"] == ["XLA TraceMe"]
    assert abs(got["quest:dma_in_wait"]["ms"] - 0.1) < 1e-12
    assert got["quest:dma_out_wait"]["count"] == 1


def test_expected_regions_from_planned_steps():
    got = PT.expected_regions([(2, 4096), (3, 1), (1, 8)], out_slots=2)
    assert got == {"quest:dma_in_wait": 4105, "quest:stages": 4105,
                   "quest:dma_out_wait": 4094 + 6}


def test_small_run_records_the_setup_split():
    cell, config, traffic = H.small_cell("sv30_f32.rcs_d20")
    out = PT.run(cell, config, traffic, seed=2**31 + 3, record=True,
                 trace=True, require_tpu=False, interpret=True)
    split = out["setup"]
    assert split["plan_s"] > 0
    assert split["jaxpr_trace_s"] > 0 and split["mlir_lower_s"] > 0
    assert split["traces"] >= 1
    assert out["setup_s"] > out["compile_s"] > 0
    # the traced application compiles nothing
    assert out["window"] == {"jaxpr_trace": 0, "backend_compile": 0}
    off = PT.run(cell, config, traffic, seed=2**31 + 3, record=False,
                 trace=False, require_tpu=False, interpret=True)
    assert "setup" not in off and "sweeps" not in off
