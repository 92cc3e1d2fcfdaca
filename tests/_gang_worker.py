"""Worker for the gang-consistent durable test (tests/test_gang.py).

Runs as one of `num_processes` OS processes holding 4 virtual CPU
devices each of a global 8-device mesh wired through jax.distributed
(gloo over TCP — the localhost stand-in for DCI on a real pod). Four
scenarios, each printing a marker line the parent asserts:

  1. topology-aware planner parity PER HOST: sharded_schedule over the
     global mesh under QUEST_COMM_TOPOLOGY=hosts=2 — predicted ==
     lowered StableHLO on every host, hierarchical strategy chosen;
  2. uninterrupted multi-host run_durable (the bit-identity baseline);
  3. gang preempt + resume: both hosts killed at a seeded step
     boundary, rerun resumes from the gang checkpoint, final shards
     bit-identical to the uninterrupted run;
  4. MID-SAVE HOST KILL: checkpoint.save fires on host 1 only, inside
     the second gang save (payload written, stamp withheld), host 0
     preempted at the next boundary — the half-stamped step must never
     commit (all hosts stamp or none do), both hosts resume from the
     PREVIOUS committed cut, and the finish is still bit-identical.
"""

import hashlib
import os
import sys

import jax

jax.config.update("jax_platforms", "cpu")


PROC = int(sys.argv[1])
NPROC = int(sys.argv[2])
PORT = sys.argv[3]
ROOT = sys.argv[4]

# the topology knob must be in place before any planning happens
os.environ["QUEST_COMM_TOPOLOGY"] = f"hosts={NPROC}"

jax.distributed.initialize(coordinator_address=f"127.0.0.1:{PORT}",
                           num_processes=NPROC, process_id=PROC)

import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from quest_tpu import checkpoint as ckpt  # noqa: E402
from quest_tpu.circuit import Circuit  # noqa: E402
from quest_tpu.env import AMP_AXIS  # noqa: E402
from quest_tpu.resilience import faults  # noqa: E402
from quest_tpu.resilience.durable import run_durable  # noqa: E402
from quest_tpu.state import Qureg  # noqa: E402

assert len(jax.devices()) == 8, jax.devices()
assert jax.process_count() == NPROC

N = 8
mesh = Mesh(np.array(jax.devices()), (AMP_AXIS,))
sharding = NamedSharding(mesh, P(None, AMP_AXIS))

rng = np.random.default_rng(11)
c = Circuit(N)
for _ in range(3):
    for q in range(N):
        c.rx(q, float(rng.uniform(0, 2 * np.pi)))
        c.ry(q, float(rng.uniform(0, 2 * np.pi)))
    for q in range(0, N - 1, 2):
        c.cz(q, q + 1)


def fresh_state() -> Qureg:
    base = np.zeros((2, 1 << N), dtype=np.float32)
    base[0, 0] = 1.0
    amps = jax.make_array_from_callback((2, 1 << N), sharding,
                                        lambda idx: base[idx])
    return Qureg(amps=amps, num_qubits=N, is_density=False)


def shard_hash(q: Qureg) -> str:
    h = hashlib.sha256()
    for s in sorted(q.amps.addressable_shards,
                    key=lambda s: s.index[-1].start or 0):
        h.update(np.ascontiguousarray(
            np.asarray(jax.device_get(s.data))).tobytes())
    return h.hexdigest()[:16]


# -- 1. planner parity per host under the hierarchical topology --------------
from quest_tpu.parallel.introspect import sharded_schedule  # noqa: E402

rec = sharded_schedule(c.ops, N, False, mesh, engine="banded")
assert rec["comm_matches_hlo"], rec
assert rec["comm_topology"]["hosts"] == NPROC, rec["comm_topology"]
assert rec["comm_dci_bytes"] > 0, rec
assert rec["comm_ici_bytes"] + rec["comm_dci_bytes"] == rec["comm_bytes"]
print(f"proc {PROC}: gang parity ok strategy={rec['comm_strategy']} "
      f"dci={rec['comm_dci_bytes']}", flush=True)

# -- 2. uninterrupted baseline -----------------------------------------------
dir_a = os.path.join(ROOT, "a")
out_a = run_durable(c, fresh_state(), dir_a, every=2, mesh=mesh)
hash_a = shard_hash(out_a)
assert ckpt.step_dirs(dir_a) == [], "completed run must consume its chain"
print(f"proc {PROC}: gang uninterrupted ok {hash_a}", flush=True)

# -- 3. gang preempt + resume ------------------------------------------------
dir_b = os.path.join(ROOT, "b")
plan = faults.FaultPlan()
plan.inject("durable.preempt", after_n=5, times=1)
faults.install(plan)
try:
    run_durable(c, fresh_state(), dir_b, every=2, mesh=mesh)
    raise AssertionError("seeded preempt did not fire")
except faults.InjectedFault:
    pass
faults.clear()
assert ckpt.step_dirs(dir_b), "no gang checkpoint committed before kill"
out_b = run_durable(c, fresh_state(), dir_b, every=2, mesh=mesh)
assert shard_hash(out_b) == hash_a, "gang resume diverged"
print(f"proc {PROC}: gang resume ok", flush=True)

# -- 4. mid-save host kill ---------------------------------------------------
dir_c = os.path.join(ROOT, "c")
plan = faults.FaultPlan()
if PROC == 1:
    # fire INSIDE the second gang save: shard written, stamp withheld
    plan.inject("checkpoint.save", after_n=1, times=1)
else:
    # host 0 is preempted at the boundary right after that save — it
    # never enters a collective the dead host cannot join
    plan.inject("durable.preempt", after_n=4, times=1)
faults.install(plan)
try:
    run_durable(c, fresh_state(), dir_c, every=2, mesh=mesh)
    raise AssertionError("seeded mid-save kill did not fire")
except faults.InjectedFault:
    pass
faults.clear()
# the half-stamped step must NOT have committed: only ckpt-2 exists,
# and the gang tmp of the killed save holds host 0's stamp alone
steps = [s for s, _ in ckpt.step_dirs(dir_c)]
assert steps == [2], f"mid-save kill leaked a commit: {steps}"
tmp4 = os.path.join(dir_c, "ckpt-00000004.tmp-gang")
assert os.path.isdir(tmp4), "killed save left no gang tmp"
if PROC == 0:
    # only host 0 can assert its OWN stamp: the protocol is
    # collective-free, so host 1 has no ordering against host 0's
    # prepare — checking cross-host here would race
    assert os.path.exists(os.path.join(tmp4, "prepared-0"))
assert not os.path.exists(os.path.join(tmp4, "prepared-1")), \
    "the killed host stamped anyway"
out_c = run_durable(c, fresh_state(), dir_c, every=2, mesh=mesh)
assert shard_hash(out_c) == hash_a, "mid-save-kill resume diverged"
assert ckpt.step_dirs(dir_c) == [], "completed run must consume chain"
assert not os.path.isdir(tmp4), "completed run must sweep the gang tmp"
print(f"proc {PROC}: gang midsave ok", flush=True)
