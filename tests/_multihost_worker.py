"""Worker for the multi-HOST distributed test (tests/test_multihost.py).

Runs as one of `num_processes` OS processes; each holds 4 virtual CPU
devices of a global 8-device mesh wired through jax.distributed (gloo
over TCP on this host — the stand-in for DCN on a real pod; ICI/DCN
routing is XLA's job either way, which is precisely the design claim:
the engine code is identical from 1 chip to a multi-host pod).

Applies a circuit touching every distribution mechanism through
compile_circuit_sharded, then checks THIS process's addressable shards
against the dense single-device oracle computed locally.
"""

import sys

import jax

jax.config.update("jax_platforms", "cpu")


PROC = int(sys.argv[1])
NPROC = int(sys.argv[2])
PORT = sys.argv[3]

jax.distributed.initialize(coordinator_address=f"127.0.0.1:{PORT}",
                           num_processes=NPROC, process_id=PROC)

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from quest_tpu.circuit import random_circuit  # noqa: E402
from quest_tpu.env import AMP_AXIS  # noqa: E402
from quest_tpu.parallel.sharded import compile_circuit_sharded  # noqa: E402

assert len(jax.devices()) == 8, jax.devices()
assert jax.process_count() == NPROC

n = 10
c = random_circuit(n, depth=4, seed=21)
mesh = Mesh(np.array(jax.devices()), (AMP_AXIS,))
sharding = NamedSharding(mesh, P(None, AMP_AXIS))

base = np.zeros((2, 1 << n), dtype=np.float32)
base[0, 0] = 1.0
amps = jax.make_array_from_callback((2, 1 << n), sharding,
                                    lambda idx: base[idx])

step = compile_circuit_sharded(c.ops, n, density=False, mesh=mesh,
                               donate=False)
out = step(amps)

# every process computes the dense oracle locally (single-CPU path) and
# checks the shards IT holds — no cross-process gather needed
want = np.asarray(c.compiled(n, density=False, donate=False)(
    jnp.asarray(base)))
for shard in out.addressable_shards:
    got = np.asarray(shard.data)
    ref = want[shard.index]
    err = float(np.max(np.abs(got - ref)))
    assert err < 5e-6, f"proc {PROC} shard {shard.index}: err {err}"

# and one cross-process reduction: total probability via psum (the
# MPI_Allreduce analogue riding gloo/DCN)
def _norm(chunk):
    return lax.psum(jnp.sum(chunk * chunk), AMP_AXIS)

from quest_tpu import compat
total = jax.jit(compat.shard_map(_norm, mesh,
                                 P(None, AMP_AXIS), P()))(out)
total = float(jax.device_get(total))
assert abs(total - 1.0) < 1e-5, total

print(f"proc {PROC}: shards ok, psum norm {total:.8f}", flush=True)

# dynamic circuit across processes: mid-circuit measurement draws the
# SAME outcome on every host (psum'd probability, shared key) and the
# feedback correction applies consistently
from quest_tpu.circuit import Circuit  # noqa: E402
from quest_tpu.parallel.sharded import (  # noqa: E402
    compile_circuit_sharded_measured)

dc = Circuit(n).h(0).cnot(0, n - 1).measure(n - 1).x_if(0, (0, 1))
dc.measure(0)
step_d = compile_circuit_sharded_measured(dc.ops, n, False, mesh,
                                          donate=False)
amps_d = jax.make_array_from_callback((2, 1 << n), sharding,
                                      lambda idx: base[idx])
out_d, outcomes = step_d(amps_d, jax.random.PRNGKey(7))
outcomes = np.asarray(jax.device_get(outcomes))
# Bell pair: after X-correction on the 1-branch, qubit 0 is |0> -> the
# second measurement must read 0 on EVERY host, deterministically
assert outcomes[1] == 0, outcomes
print(f"proc {PROC}: dynamic circuit outcomes {outcomes.tolist()}",
      flush=True)

# layer-amortized relabeling cross-process: the fused sharded engine's
# all_to_all relabel events must route over gloo/DCN exactly like they
# will over ICI on a pod. nr=13 so local_n=10 clears the Pallas
# kernel's minimum — at n=10 the fused compiler silently falls back to
# banded and NOTHING relabel-related runs (a false positive caught in
# review); the fused_shard_bands assertion pins the real path.
from quest_tpu.parallel.sharded import (  # noqa: E402
    compile_circuit_sharded_fused, fused_shard_bands)

nr = 13            # 8 devices -> local_n = 10
g_bits = 3
assert fused_shard_bands(nr, nr - g_bits) is not None, \
    "fused engine would silently fall back to banded"
rng_r = np.random.default_rng(5)
cr = Circuit(nr)
for _ in range(3):
    for q in range(nr):
        cr.rx(q, float(rng_r.uniform(0, 2 * np.pi)))
    for q in range(0, nr - 1, 2):
        cr.cz(q, q + 1)
from quest_tpu.circuit import flatten_ops  # noqa: E402
from quest_tpu.parallel.relabel import plan_full_relabels  # noqa: E402
n_events = sum(1 for op in plan_full_relabels(
    flatten_ops(cr.ops, nr, False), nr, nr - g_bits)
    if op.kind == "relabel")
assert n_events > 0, "deep-global circuit fired no relabel events"
step_r = compile_circuit_sharded_fused(cr.ops, nr, False, mesh,
                                       donate=False, interpret=True)
base_r = np.zeros((2, 1 << nr), dtype=np.float32)
base_r[0, 0] = 1.0
sharding_r = NamedSharding(mesh, P(None, AMP_AXIS))
amps_r = jax.make_array_from_callback((2, 1 << nr), sharding_r,
                                      lambda idx: base_r[idx])
out_r = step_r(amps_r)
want_r = np.asarray(cr.compiled_banded(nr, density=False, donate=False)(
    jnp.asarray(base_r)))
for shard in out_r.addressable_shards:
    got = np.asarray(shard.data)
    ref = want_r[shard.index]
    err = float(np.max(np.abs(got - ref)))
    assert err < 5e-5, f"proc {PROC} relabel shard {shard.index}: err {err}"
print(f"proc {PROC}: relabel all_to_all ok ({n_events} events)", flush=True)
