"""Multi-HOST distribution: the sharded engine over jax.distributed.

The reference scales across nodes with MPI (QuEST_cpu_distributed.c);
quest_tpu's equivalent is a global mesh wired by jax.distributed — XLA
routes collectives over ICI within a host and DCN between hosts. This
test actually RUNS that configuration: two OS processes, four virtual
CPU devices each, one 8-device global mesh, cross-process collectives
over gloo/TCP (the localhost stand-in for DCN). The engine code under
test is byte-identical to the single-process path — which is the design
claim (same code from 1 chip to a pod).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_two_process_global_mesh():
    # slow-marked (~23 s: spawns two subprocesses each paying a full
    # jax import — the same multihost discipline as the slow-marked
    # test_distributed suite) so tier-1 fits its 870 s budget; CI's
    # unfiltered `pytest tests/` and `-m slow` runs keep it covered
    # bounded by the communicate(timeout=240) below — no plugin needed
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    worker = os.path.join(REPO, "tests", "_multihost_worker.py")
    port = "19734"
    procs = [subprocess.Popen(
        [sys.executable, worker, str(i), "2", port], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for i in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out[-3000:]}"
        assert "shards ok" in out, out[-1000:]
        assert "dynamic circuit outcomes" in out, out[-1000:]
        assert "relabel all_to_all ok" in out, out[-1000:]
    # both processes drew the SAME outcome sequence
    import re
    seqs = {re.search(r"dynamic circuit outcomes (\[.*?\])", o).group(1)
            for o in outs}
    assert len(seqs) == 1, seqs
