"""Compile main-path programs for a described TPU v5e, without a chip.

Each case lowers and compiles one program of the main path against a
`v5e:2x2` topology that JAX describes but does not attach, and checks
what only the chip's compiler can show: the program holds the Pallas
kernels (`tpu_custom_call`), and its arguments and temporaries fit the
chip's 15.75 GiB. Mosaic refuses here what interpret mode accepts (the
round-5 i64 loop bound under x64, unaligned slices, VMEM over-use), so
these run under the suite's `jax_enable_x64=True`.

The topology is described inside a module fixture, never at import: only
one process may load libtpu, and the suite's workers each import every
test file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

HBM_LIMIT = int(15.75 * 2 ** 30)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prior)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    """(compiled, kernel count) after asserting the TPU program holds a
    Pallas kernel and fits the chip."""
    lowered = fn.lower(*args)
    kernels = lowered.as_text().count("tpu_custom_call")
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert kernels > 0, "no Pallas kernel in the TPU program"
    assert used <= HBM_LIMIT, f"{used / 2 ** 30:.2f} GiB > 15.75 GiB"
    return compiled, kernels


def test_fused_engine_30q(one_chip):
    from quest_tpu.circuit import random_circuit
    from quest_tpu.state import fused_state_shape
    c = random_circuit(30, 2, seed=7, entangler="cz")
    fn = c.compiled_fused(30, False, donate=True)
    state = jax.ShapeDtypeStruct(fused_state_shape(30), jnp.float32,
                                 sharding=one_chip)
    compiled, _ = _compile(fn, state)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 8 * 2 ** 30   # donated in place


def test_fused_kernels_carry_names_and_sweep_scopes(one_chip):
    """Each kernel's custom call is named by its structure
    (pallas_band.kernel_name) and sits under its sweep's position in the
    plan (`quest.sweepNN` in op_name), so a device trace can name every
    kernel event and place it in the plan."""
    import re

    from quest_tpu.circuit import random_circuit
    from quest_tpu.state import fused_state_shape
    n = 22
    c = random_circuit(n, 2, seed=7, entangler="cz")
    sweeps = c.plan_stats()["fused"]["hbm_sweeps"]
    assert sweeps > 1
    fn = c.compiled_fused(n, False, donate=True)
    state = jax.ShapeDtypeStruct(fused_state_shape(n), jnp.float32,
                                 sharding=one_chip)
    compiled, _ = _compile(fn, state)
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == sweeps
    seen = set()
    for line in calls:
        name = re.match(r"\s*%(\S+) = ", line).group(1)
        assert re.match(r"quest_seg_\w+_r\d+s\d+_[0-9a-f]{8}(\.\d+)?$",
                        name), name
        scope = re.search(r'op_name="[^"]*quest\.sweep(\d\d)/'
                          r'(quest_seg_\w+)/', line)
        assert scope, line[:300]
        assert name.startswith(scope.group(2))
        seen.add(int(scope.group(1)))
    assert seen == set(range(sweeps))


def test_batched_trajectory_kernel_20q(one_chip):
    from quest_tpu import trajectories as T
    from quest_tpu.circuit import Circuit
    c = Circuit(20)
    c.h(0).h(9).h(19)
    c.damping(2, 0.3).depolarising(8, 0.2).dephasing(15, 0.25)
    fn = T._compiled_traj(c, 20, 8, "fused", False)
    keys = jax.random.split(jax.random.key(0), 8)
    spec = jax.ShapeDtypeStruct(keys.shape, keys.dtype, sharding=one_chip)
    compiled, _ = _compile(fn, spec)
    bucket_bytes = 8 * 2 * 4 * (1 << 20)
    # the damping draw's reduced density once padded the batch axis 16x
    # (2.1 GiB of temporaries for this 64 MiB bucket)
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * bucket_bytes


def test_qft30_former_sweep2_kernels_fit_their_vmem_price(one_chip,
                                                         monkeypatch):
    """QFT-30's sweep 2 as planned before the VMEM price, [scb128, 21
    phase groups, phase] at 2^12 rows, asked Mosaic for 125.58M of scoped
    VMEM against the 100M limit. Each kernel the planner now makes that
    holds a stage of that chain compiles on its own, with its scoped
    limit set to the price the planner gave it, so an allocation above
    the price fails. The compiles run side by side."""
    from concurrent.futures import ThreadPoolExecutor

    from quest_tpu.circuit import qft_circuit
    from quest_tpu.ops import fusion as F
    from quest_tpu.ops import pallas_band as PB
    from quest_tpu.state import fused_state_shape
    n = 30
    items = F.plan(qft_circuit(n)._planned_flat(n, False), n,
                   bands=PB.plan_bands(n))
    free = PB.sweep_plan(PB.segment_plan(items, n, vmem_budget=np.inf), n,
                         vmem_budget=np.inf)
    lo = len(free[0][1]) + len(free[1][1])
    hi = lo + len(free[2][1])
    at, fns = 0, []
    for _, stages, arrays in PB.sweep_plan(PB.segment_plan(items, n), n):
        if at < hi and at + len(stages) > lo:
            price = PB.sweep_vmem_bytes(stages, arrays, n)["total_bytes"]
            assert price <= PB.VMEM_LIMIT_BYTES
            monkeypatch.setattr(PB, "VMEM_LIMIT_BYTES", price)
            seg = PB.compile_segment(stages, n)
            monkeypatch.undo()
            fns.append(jax.jit(lambda a, seg=seg, arrays=arrays:
                               seg(a, arrays), donate_argnums=0))
        at += len(stages)
    assert len(fns) == 3
    state = jax.ShapeDtypeStruct(fused_state_shape(n), jnp.float32,
                                 sharding=one_chip)
    with ThreadPoolExecutor(len(fns)) as pool:
        list(pool.map(lambda fn: _compile(fn, state), fns))


def test_density_15q(one_chip):
    from quest_tpu.circuit import Circuit
    from quest_tpu.state import fused_state_shape
    c = Circuit(15)
    c.damping(1, 0.1).dephasing(7, 0.2).cz(3, 12)
    fn = c.compiled_fused(30, True, donate=True)
    state = jax.ShapeDtypeStruct(fused_state_shape(30), jnp.float32,
                                 sharding=one_chip)
    _compile(fn, state)


def test_sharded_fused_four_chips(topo):
    """A random circuit crossing the global qubits on a 4-chip mesh:
    kernels per shard, the relabel all-to-alls between them, and no
    temporaries from padded tiny-minor-dim views (the compile that
    exhausted a 62 GB host before the relabel repair)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from quest_tpu.circuit import random_circuit
    from quest_tpu.env import AMP_AXIS
    from quest_tpu.parallel.sharded import compile_circuit_sharded_fused
    n = 24
    mesh = Mesh(np.array(topo.devices[:4]), (AMP_AXIS,))
    c = random_circuit(n, 2, seed=11, entangler="cz")
    fn = compile_circuit_sharded_fused(tuple(c.ops), n, False, mesh,
                                       donate=True)
    state = jax.ShapeDtypeStruct((2, 1 << n), jnp.float32,
                                 sharding=NamedSharding(mesh,
                                                        P(None, AMP_AXIS)))
    compiled, _ = _compile(fn, state)
    hlo = compiled.as_text()
    assert "all-to-all" in hlo or "collective-permute" in hlo
    shard_bytes = 2 * 4 * (1 << n) // 4
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * shard_bytes
