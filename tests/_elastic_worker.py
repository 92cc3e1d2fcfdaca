"""Worker for the elastic gang chaos soak (tests/test_elastic.py).

Three phases over ONE shared checkpoint root (argv: proc_id|'solo',
num_processes, port, root, phase), exercising the ISSUE-15 contract —
"any hosts can pick it up" — across three mesh generations:

  baseline-and-kill   2 gloo processes x 2 virtual devices (D=4):
                      uninterrupted gang run_durable (per-host shard
                      hashes written for the later phases), then a
                      MID-SAVE HOST KILL: checkpoint.save fires on
                      host 1 inside the second gang save (shard
                      written, stamp withheld) and host 0 is preempted
                      at the next boundary — the half-stamped step must
                      never commit; the chain ends at the FIRST gang
                      checkpoint.
  solo-resume-and-kill one ordinary process, D'=2 sharded mesh
                      (fewer devices, no jax.distributed): elastic
                      resume of the gang chain, runs past further save
                      points (PLAIN-format checkpoints now top the
                      gang-format one), preempted again mid-run. The
                      phase asserts the resume consumed a real stamp
                      (not a hollow op-0 restart) and that the torn
                      gang tmp survives (sweeps only run at
                      completion).
  final-resume        2 gloo processes again: elastic resume of the
                      now mixed-format chain BACK onto the gang mesh,
                      completing bit-identical to the uninterrupted
                      baseline (per-host shard hashes equal), chain
                      and gang tmps consumed.

The circuit is bench._build_elastic_circuit under QUEST_SCHEDULE=0 (the
parent sets it): mesh-portable arithmetic, so bit-identity holds across
all three generations (docs/RESILIENCE.md §elastic).
"""

import hashlib
import json
import os
import sys

import jax

jax.config.update("jax_platforms", "cpu")

PROC = sys.argv[1]
NPROC = int(sys.argv[2])
PORT = sys.argv[3]
ROOT = sys.argv[4]
PHASE = sys.argv[5]

GANG = PROC != "solo"

if GANG:
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{PORT}",
        num_processes=NPROC, process_id=int(PROC))

import numpy as np  # noqa: E402

import bench  # noqa: E402
from quest_tpu import checkpoint as ckpt  # noqa: E402
from quest_tpu.parallel.mesh import make_amp_mesh  # noqa: E402
from quest_tpu.parallel.mesh import amp_sharding  # noqa: E402
from quest_tpu.resilience import faults  # noqa: E402
from quest_tpu.resilience.durable import run_durable  # noqa: E402
from quest_tpu.serve import metrics  # noqa: E402
from quest_tpu.state import Qureg  # noqa: E402

N = 10
EVERY = 10
CHAIN = os.path.join(ROOT, "chain")

c = bench._build_elastic_circuit(N, layers=3, seed=7)


def fresh(mesh) -> Qureg:
    base = np.zeros((2, 1 << N), dtype=np.float32)
    base[0, 0] = 1.0
    amps = jax.make_array_from_callback(
        (2, 1 << N), amp_sharding(mesh), lambda idx: base[idx])
    return Qureg(amps=amps, num_qubits=N, is_density=False)


def shard_hashes(q: Qureg) -> dict:
    """sha256 per contiguous half of the column space — comparable
    between the gang phases (each host hashes its half) and the solo
    phase (which holds everything)."""
    full = None
    if q.amps.is_fully_addressable:
        full = np.asarray(jax.device_get(q.amps))
    out = {}
    half = (1 << N) // 2
    for h in range(2):
        if full is not None:
            block = full[:, h * half:(h + 1) * half]
        else:
            shards = [s for s in q.amps.addressable_shards
                      if (s.index[-1].start or 0) // half == h]
            if not shards:
                continue
            shards.sort(key=lambda s: s.index[-1].start or 0)
            block = np.concatenate(
                [np.asarray(jax.device_get(s.data)) for s in shards],
                axis=-1)
        out[str(h)] = hashlib.sha256(
            np.ascontiguousarray(block).tobytes()).hexdigest()[:16]
    return out


def merge_hash_file(hashes: dict) -> None:
    path = os.path.join(ROOT, f"ref-hashes-{PROC}.json")
    with open(path, "w") as f:
        json.dump(hashes, f)


def load_ref_hashes() -> dict:
    out = {}
    for name in os.listdir(ROOT):
        if name.startswith("ref-hashes-"):
            with open(os.path.join(ROOT, name)) as f:
                out.update(json.load(f))
    return out


if PHASE == "baseline-and-kill":
    mesh = make_amp_mesh(len(jax.devices()))
    # -- uninterrupted baseline ------------------------------------------
    out = run_durable(c, fresh(mesh), os.path.join(ROOT, "ref"),
                      every=EVERY, mesh=mesh)
    merge_hash_file(shard_hashes(out))
    print(f"proc {PROC}: elastic baseline ok", flush=True)

    # -- mid-save host kill on the real chain ----------------------------
    plan = faults.FaultPlan()
    if PROC == "1":
        # fire INSIDE the second gang save: shard written, stamp withheld
        plan.inject("checkpoint.save", after_n=1, times=1)
    else:
        # host 0 preempted at the boundary right after that save point
        plan.inject("durable.preempt", after_n=2 * EVERY + 1, times=1)
    faults.install(plan)
    try:
        run_durable(c, fresh(mesh), CHAIN, every=EVERY, mesh=mesh)
        raise AssertionError("seeded mid-save kill did not fire")
    except faults.InjectedFault:
        pass
    faults.clear()
    steps = [s for s, _ in ckpt.step_dirs(CHAIN)]
    assert steps == [EVERY], f"half-stamped step leaked a commit: {steps}"
    tmp = ckpt.step_path(CHAIN, 2 * EVERY) + ".tmp-gang"
    assert os.path.isdir(tmp), "killed save left no gang tmp"
    assert not os.path.exists(os.path.join(tmp, "prepared-1")), \
        "the killed host stamped anyway"
    print(f"proc {PROC}: elastic midsave-kill ok", flush=True)

elif PHASE == "solo-resume-and-kill":
    mesh = make_amp_mesh(2)            # D' = 2 < the gang's D = 4
    reg = metrics.Registry()
    plan = faults.FaultPlan()
    plan.inject("durable.preempt", after_n=3 * EVERY + 5, times=1)
    faults.install(plan)
    try:
        run_durable(c, fresh(mesh), CHAIN, every=EVERY, mesh=mesh,
                    elastic=True, registry=reg)
        raise AssertionError("seeded solo preempt did not fire")
    except faults.InjectedFault:
        pass
    faults.clear()
    # the resume consumed the gang stamp — not a hollow op-0 restart
    assert reg.counter("durable_resumes").value == 1, "no resume"
    assert reg.counter("durable_elastic_resumes").value == 1
    steps = [s for s, _ in ckpt.step_dirs(CHAIN)]
    assert steps and max(steps) > EVERY, \
        f"solo leg stamped nothing new: {steps}"
    # the newest checkpoint is PLAIN-format now (written by this host)
    assert not ckpt.is_gang_step(ckpt.step_dirs(CHAIN)[-1][1])
    # the single-writer plain save path reclaimed the torn gang tmp
    # (prune_steps' stale sweep — once a new generation owns the chain,
    # the killed gang's leftovers are payload-sized garbage)
    assert not os.path.isdir(ckpt.step_path(CHAIN, 2 * EVERY)
                             + ".tmp-gang")
    print("elastic solo-resume ok", flush=True)

elif PHASE == "final-resume":
    mesh = make_amp_mesh(len(jax.devices()))
    reg = metrics.Registry()
    out = run_durable(c, fresh(mesh), CHAIN, every=EVERY, mesh=mesh,
                      elastic=True, registry=reg)
    assert reg.counter("durable_resumes").value == 1
    ref = load_ref_hashes()
    got = shard_hashes(out)
    for h, digest in got.items():
        assert ref.get(h) == digest, \
            f"half {h}: {digest} != baseline {ref.get(h)}"
    assert ckpt.step_dirs(CHAIN) == [], "completed run must consume chain"
    assert not any(name.endswith(".tmp-gang")
                   for name in os.listdir(CHAIN)), \
        "completed run left a gang tmp behind"
    print(f"proc {PROC}: elastic final ok", flush=True)

else:
    raise SystemExit(f"unknown phase {PHASE!r}")
