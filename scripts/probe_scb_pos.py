"""On-chip probe: scattered-contraction (scb) cost vs width and position.

The round-4 decision record behind "do NOT Kron-split a factorizable
band operator" (docs/KERNELS.md round-4 findings, segment_plan comment):
a narrow scb's MXU time is ~flat in d — a small-M dot idles most of the
systolic array — so splitting one wide dot into factors multiplies
cost. Measured 30q, v5e: whole d=128 42.6 ms; the d4+d4+d8 split of the
same band 161.4 ms; lone d=8 at top/mid/bottom scat positions
40.3/40.3/42.5 ms; seven stacked sc butterflies 160.3 ms.

Usage: python scripts/probe_scb_pos.py   (on the machine that holds the chip)
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from quest_tpu.precision import enable_compile_cache
enable_compile_cache()
import jax, jax.numpy as jnp, numpy as np
from quest_tpu.ops import pallas_band as PB
from quest_tpu.state import basis_planes, fused_state_shape

n = 30

def run(tag, stages, arrays):
    fn = PB.compile_segment(stages, n)
    arrays = [jnp.asarray(a) for a in arrays]
    jfn = jax.jit(lambda a: fn(a, arrays), donate_argnums=(0,))
    amps = basis_planes(0, n=n, rdt=jnp.float32, shape=fused_state_shape(n))
    amps = jfn(amps); _ = np.asarray(amps[0,0,:4])
    t0 = time.perf_counter()
    for _ in range(5): amps = jfn(amps)
    _ = np.asarray(amps[0,0,:4])
    print(tag, round((time.perf_counter()-t0)/5*1e3, 2), 'ms', flush=True)

def mat(kind, d, bit):
    g = np.zeros((2, d, d), np.float32); g[0] = np.eye(d)
    if kind == 'scb' and d == 128:
        pass  # identity symmetric; transpose moot
    return PB.MatStage(kind, d, False, (), (), bit), g

# the high band qubits 14-20 = row bits 7..13
# A: whole-band d=128 (two-step mirror path)
st, g = mat('scb', 128, 7)
run('whole-d128', [st], [g])
# B: the real split shape: d4(bits 7-8) + d4(9-10) + d8(11-13)
sts, gs = [], []
for kind, d, bit in (('scb',4,7), ('scb',4,9), ('scb',8,11)):
    s, g = mat(kind, d, bit); sts.append(s); gs.append(g)
run('split-4/4/8', sts, gs)
# C: single narrow at TOP position (pre=1): d8 at bits 20-22
st, g = mat('scb', 8, 20)
run('top-d8', [st], [g])
# D: single narrow MID position: d8 at bits 11-13 alone
st, g = mat('scb', 8, 11)
run('mid-d8', [st], [g])
# E: single narrow BOTTOM: d8 at bits 7-9 alone
st, g = mat('scb', 8, 7)
run('bot-d8', [st], [g])
# F: 7 sc butterflies (bits 7..13)
sts, gs = [], []
for b in range(7, 14):
    s, g = mat('sc', 2, b); sts.append(s); gs.append(g)
run('sc-x7', sts, gs)
