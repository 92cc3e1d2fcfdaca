"""qbench/trace.py on a small trace recorded here on the CPU, and its
interval arithmetic on intervals made by hand."""

import jax
import jax.numpy as jnp
import pytest

from qbench import trace as TR


def test_union_gaps_and_labels():
    busy = TR.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert busy == [(0, 3), (5, 8)]
    assert TR.gaps(busy, -1, 10) == [(-1, 0), (3, 5), (8, 10)]
    assert TR.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
    spans = [("window", 0, 10), ("wait", 2.5, 4.2), ("input", 4.2, 5)]
    assert TR.label((3, 5), spans) == "wait"
    assert TR.label((8, 10), spans) == "other"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Three applications of a small program in the benchmark's spans,
    with a host sleep inside each wait so the device idles."""
    import time
    d = str(tmp_path_factory.mktemp("trace"))
    f = jax.jit(lambda x: jnp.tanh(x @ x) + 1.0)
    x = jnp.ones((256, 256))
    f(x + 0).block_until_ready()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("qbench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("qbench.input"):
                y = x + 0
            with jax.profiler.TraceAnnotation("qbench.dispatch"):
                y = f(y)
            with jax.profiler.TraceAnnotation("qbench.wait"):
                y.block_until_ready()
                time.sleep(0.02)
    jax.profiler.stop_trace()
    return TR.load(d)


def test_summary_of_a_recorded_trace(recorded):
    ops = TR.device_ops(recorded)
    assert ops, "no device ops found in the CPU trace"
    names = {n for v in ops.values() for n, _, _ in v}
    dots = {n for n in names if n.startswith("dot")}
    assert dots, names
    s = TR.summarize(recorded, kernel_names=dots)
    assert s.apps == 3                       # from the dispatch spans
    assert 0 < s.busy_s < s.window_s
    # kernel vs other split the op time; the union never exceeds the sum
    assert s.kernel_s > 0 and s.other_s > 0
    assert s.busy_s <= s.kernel_s + s.other_s + 1e-12
    # the three host sleeps are idle, and labelled with the wait span
    assert sum(t for name, t in s.idle_gaps if name == "wait") >= 0.06
    assert s.idle_gaps[0][0] == "wait"
    # busy is the merged union of the clipped device intervals
    (lo, hi), = [(a, b) for n, a, b in TR.host_spans(recorded)
                 if n == "window"]
    ivs = [iv for v in ops.values() for _, a, b in v
           for iv in TR.clip([(a, b)], lo, hi)]
    assert s.busy_s == pytest.approx(
        sum(b - a for a, b in TR.union(ivs)) * 1e-9)


def test_device_op_names_from_hlo_text():
    text = ('%run.72 = f32[2,8,128]{2,1,0:T(8,128)} custom-call(f32[2,8,128]'
            ' %p), custom_call_target="tpu_custom_call", operand_layout_'
            'constraints={f32[2,8,128]{2,1,0}}')
    assert TR.op_name(text) == "run.72:kernel"
    assert TR.op_name("%fusion.1 = f32[8]{0} fusion(%y), kind=kLoop") \
        == "fusion.1"
