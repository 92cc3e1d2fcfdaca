#!/usr/bin/env python3
"""Smoke run of the circuit engine's main path on a directly attached TPU.

    python chip_smoke.py              # phases a-e on one chip
    python chip_smoke.py --chips 4    # the sharded phase only, on four chips

Every phase drives the public entry points (`quest_tpu.api`, `Circuit`,
`ServeEngine`, `compile_circuit_sharded_fused`) and checks what comes out
against the repo's own references: the reference binary's tutorial
numbers, a second engine, the un-batched engine, the mirror circuit. Each
phase prints one line; the last line of stdout is the device record
`{"ok": true, "device": {...}}`. Any failed check raises, so the exit code
is non-zero and no device record is printed. Without a TPU the script
fails in phase a; it never falls back to the CPU.

The phase functions take their sizes and `interpret=` as arguments so
tests/test_chip_smoke.py can run them on the CPU at tiny sizes. The times
printed here are a bring-up record, not a benchmark.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def report(phase: str, **fields) -> None:
    print(f"phase {phase}: {json.dumps(fields)}", flush=True)


def _timed(fn, *args):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def norm_f64(planes) -> float:
    """sum |a|^2 of (2, ...) f32 planes: f32 partial sums over groups of
    at most 1024 amplitudes on the device, the total in f64 on the host
    (no full-state transfer, no x64 needed)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    size = int(np.prod(planes.shape[1:]))
    group = min(size, 1024)

    @jax.jit
    def partial_sums(x):
        sq = jnp.square(x).reshape(2, size // group, group)
        return jnp.sum(sq, axis=(0, 2))

    return float(np.sum(np.asarray(partial_sums(planes), dtype=np.float64)))


def max_abs_diff(a, b) -> float:
    import jax
    import jax.numpy as jnp
    d = jax.jit(lambda x, y: jnp.max(jnp.abs(x.reshape(2, -1)
                                             - y.reshape(2, -1))))(a, b)
    return float(d)


def phase_device(chips: int = 1) -> dict:
    """a. A TPU is JAX's default device; print what it reports."""
    import jax
    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu",
          f"JAX's default device is {d.platform!r}, not a TPU")
    check(len(devs) >= chips, f"{chips} chips asked for, {len(devs)} found")
    limit = (d.memory_stats() or {}).get("bytes_limit")
    check(limit is not None, "the device reports no bytes_limit")
    report("a", platform=d.platform, kind=d.device_kind, count=len(devs),
           jax=jax.__version__, bytes_limit=int(limit))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def phase_eager() -> None:
    """b. The reference tutorial through the eager QuEST API, plus the
    complex64 host<->device round trip that cplx.py's float planes were
    once said to need."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from quest_tpu import api as Q

    env = Q.createQuESTEnv()
    qubits = Q.createQureg(3, env)
    Q.hadamard(qubits, 0)
    Q.controlledNot(qubits, 0, 1)
    Q.rotateY(qubits, 2, 0.1)
    Q.multiControlledPhaseFlip(qubits, [0, 1, 2])
    u = np.array([[0.5 + 0.5j, 0.5 - 0.5j], [0.5 - 0.5j, 0.5 + 0.5j]])
    Q.unitary(qubits, 0, u)
    a, b = 0.5 + 0.5j, 0.5 - 0.5j
    Q.compactUnitary(qubits, 1, a, b)
    Q.rotateAroundAxis(qubits, 2, 3.14 / 2, (1.0, 0.0, 0.0))
    Q.controlledCompactUnitary(qubits, 0, 1, a, b)
    Q.multiControlledUnitary(qubits, [0, 1], 2, u)
    toff = Q.createComplexMatrixN(3)
    toff[6, 7] = 1
    toff[7, 6] = 1
    for i in range(6):
        toff[i, i] = 1
    Q.multiQubitUnitary(qubits, [0, 1, 2], toff)

    p111 = Q.getProbAmp(qubits, 7)
    p2 = Q.calcProbOfOutcome(qubits, 2, 1)
    total = Q.calcTotalProb(qubits)
    err111, err2, errt = abs(p111 - 0.112422), abs(p2 - 0.749178), \
        abs(total - 1.0)
    check(err111 < 1e-5, f"prob |111> = {p111}, want 0.112422 +- 1e-5")
    check(err2 < 1e-5, f"prob(q2=1) = {p2}, want 0.749178 +- 1e-5")
    check(errt < 1e-5, f"total prob = {total}, want 1 +- 1e-5")

    z = (np.arange(8) + 1j * np.arange(8)[::-1]).astype(np.complex64)
    back = np.asarray(jax.jit(lambda x: x * jnp.complex64(1 - 2j))(
        jax.device_put(z)))
    err_c = float(np.max(np.abs(back - z * np.complex64(1 - 2j))))
    check(err_c < 1e-5, f"complex64 round trip off by {err_c}")
    report("b", prob_111=p111, err_111=err111, prob_q2=p2, err_q2=err2,
           total=total, limit=1e-5, complex64_roundtrip_err=err_c)


def phase_flagship(n: int = 30, depth: int = 20, reps: int = 3,
                   interpret: bool = False) -> None:
    """c. The flagship RCS program at full width through compiled_fused:
    lowered to Pallas kernels, donated, norm kept."""
    import jax

    from quest_tpu.circuit import random_circuit
    from quest_tpu.state import basis_planes, fused_state_shape

    c = random_circuit(n, depth, seed=7, entangler="cz")
    fn = c.compiled_fused(n, False, donate=True, interpret=interpret)
    state = basis_planes(0, n=n, shape=fused_state_shape(n))
    t0 = time.perf_counter()
    lowered = fn.lower(state)
    kernels = lowered.as_text().count("tpu_custom_call")
    check(interpret or kernels > 0,
          "the fused program holds no tpu_custom_call: compiled_fused "
          "took the XLA route")
    compiled = lowered.compile()
    state = jax.block_until_ready(compiled(state))
    first_s = time.perf_counter() - t0
    steady = []
    for _ in range(reps):
        state, dt = _timed(compiled, state)
        steady.append(dt)
    norm = norm_f64(state)
    check(abs(norm - 1.0) < 1e-4, f"norm after {reps + 1} applications "
          f"is {norm}, want 1 +- 1e-4")
    report("c", n=n, depth=depth, gates=len(c.ops),
           state_bytes=int(state.nbytes), tpu_custom_calls=kernels,
           compile_and_first_run_s=first_s,
           steady_median_s=statistics.median(steady), steady_s=steady,
           norm=norm, norm_err=abs(norm - 1.0), limit=1e-4)


def phase_agreement(n: int = 26, depth: int = 8,
                    interpret: bool = False) -> None:
    """d. The fused (Pallas) and banded (XLA) engines agree on the chip."""
    from quest_tpu.circuit import random_circuit
    from quest_tpu.state import basis_planes, fused_state_shape

    c = random_circuit(n, depth, seed=3, entangler="cz")
    fused_in = basis_planes(5, n=n, shape=fused_state_shape(n))
    banded_in = basis_planes(5, n=n)
    out_f, fused_s = _timed(c.compiled_fused(n, False, donate=True,
                                             interpret=interpret), fused_in)
    out_b, banded_s = _timed(c.compiled_banded(n, False, donate=True),
                             banded_in)
    diff = max_abs_diff(out_f, out_b)
    check(diff < 1e-5, f"fused vs banded max|diff| = {diff} >= 1e-5")
    report("d", n=n, depth=depth, max_abs_diff=diff, limit=1e-5,
           fused_compile_and_run_s=fused_s,
           banded_compile_and_run_s=banded_s)


def phase_serve(n_state: int = 24, n_noisy: int = 12, requests: int = 16,
                shots: int = 8, shot_requests: int = 2,
                interpret: bool = False) -> None:
    """e. The served path: state= requests through the batched fused
    engine, shots= requests through the batched-trajectory kernel, no
    dispatch degraded to a lower engine rung."""
    import jax
    import numpy as np

    from quest_tpu import trajectories as T
    from quest_tpu.circuit import Circuit, random_circuit
    from quest_tpu.serve import metrics as M
    from quest_tpu.serve.engine import ServeEngine
    from quest_tpu.state import fused_state_shape

    c = random_circuit(n_state, 8, seed=5, entangler="cz")
    noisy = Circuit(n_noisy)
    for q in range(n_noisy):
        noisy.h(q)
    noisy.cz(0, n_noisy - 1).damping(2, 0.3).ry(8, 0.3)
    noisy.depolarising(8, 0.2).dephasing(0, 0.25).rz(n_noisy - 1, 0.4)
    noisy.depolarising(n_noisy - 1, 0.1)

    dim = 1 << n_state
    states = []
    for i in range(requests):
        s = np.zeros((2, dim), np.float32)
        s[i % 2, (i * 2654435761) % dim] = 1.0
        states.append(s)
    keys = [jax.random.key(100 + i) for i in range(shot_requests)]

    registry = M.Registry()
    engine = ServeEngine(ladder=("fused",), max_batch=8, registry=registry,
                         interpret=interpret)
    t0 = time.perf_counter()
    futs = [engine.submit(c, state=s) for s in states]
    traj = [engine.submit(noisy, shots=shots, key=k) for k in keys]
    outs = [f.result() for f in futs]
    traj_outs = [f.result() for f in traj]
    serve_s = time.perf_counter() - t0
    degraded = registry.counter("serve_degraded_dispatches").value
    engine.close()
    check(degraded == 0, f"{degraded} serve dispatches degraded")

    direct = c.compiled_fused(n_state, False, donate=False,
                              interpret=interpret)
    shape = fused_state_shape(n_state)
    state_diff = 0.0
    for s, out in zip(states, outs):
        ref = np.asarray(direct(s.reshape(shape))).reshape(2, dim)
        state_diff = max(state_diff, float(np.max(np.abs(out - ref))))
    check(state_diff < 1e-5,
          f"served vs direct compiled_fused max|diff| = {state_diff}")

    traj_diff = 0.0
    for k, (planes, draws) in zip(keys, traj_outs):
        ref_p, ref_d = T.run_batched(noisy, k, shots, engine="banded")
        check(np.array_equal(np.asarray(draws), np.asarray(ref_d)),
              "served trajectory draws differ from the banded engine's")
        traj_diff = max(traj_diff, float(np.max(np.abs(
            np.asarray(planes) - np.asarray(ref_p)))))
    check(traj_diff < 1e-5,
          f"served trajectories vs banded max|diff| = {traj_diff}")
    report("e", n_state=n_state, requests=requests, n_noisy=n_noisy,
           shot_requests=shot_requests, shots=shots,
           served_s=serve_s, state_max_abs_diff=state_diff,
           traj_max_abs_diff=traj_diff, limit=1e-5,
           serve_degraded_dispatches=degraded)


def phase_sharded(n_known: int = 24, n_cmp: int = 24, depth: int = 4,
                  devices=None, interpret: bool = False) -> None:
    """--chips 4. A random circuit that crosses the global qubits, on a
    state built already sharded over four devices: norm after c, |0>
    back after c.inverse() (a separate program, so nothing cancels);
    then the sharded and one-chip engines agree at n_cmp. The sizes are
    the ones run on four chips in PR 21; 32q (8 GiB a chip) waits on
    ROADMAP R2: its compile does not fit a bounded host memory yet."""
    import jax
    import numpy as np

    from quest_tpu.circuit import random_circuit
    from quest_tpu.env import QuESTEnv
    from quest_tpu.parallel.mesh import make_amp_mesh
    from quest_tpu.parallel.sharded import compile_circuit_sharded_fused
    from quest_tpu.state import (basis_planes, create_qureg,
                                 fused_state_shape)

    devices = list(devices if devices is not None else jax.devices())[:4]
    mesh = make_amp_mesh(4, devices)

    def sharded_program(circ, n):
        return compile_circuit_sharded_fused(tuple(circ.ops), n, False,
                                             mesh, donate=True,
                                             interpret=interpret)

    c = random_circuit(n_known, depth, seed=11, entangler="cz")
    q = create_qureg(n_known, env=QuESTEnv(devices=devices),
                     dtype=np.complex64)
    amps, fwd_s = _timed(sharded_program(c, n_known), q.amps)
    where = {s.device for s in amps.addressable_shards}
    check(len(where) == 4, f"state sits on {len(where)} devices, want 4")
    norm = norm_f64(amps)
    check(abs(norm - 1.0) < 1e-4, f"norm after c is {norm}")
    amps, inv_s = _timed(sharded_program(c.inverse(), n_known), amps)
    p0 = float(np.sum(np.square(np.asarray(amps[:, 0], np.float64))))
    check(p0 > 1 - 1e-4, f"|amp(0)|^2 after c.inverse() is {p0}")
    del amps

    c2 = random_circuit(n_cmp, depth, seed=13, entangler="cz")
    q2 = create_qureg(n_cmp, env=QuESTEnv(devices=devices),
                      dtype=np.complex64)
    out_s, cmp_s = _timed(sharded_program(c2, n_cmp), q2.amps)
    one = basis_planes(0, n=n_cmp, shape=fused_state_shape(n_cmp))
    one = jax.device_put(one, devices[0])
    out_1, one_s = _timed(c2.compiled_fused(n_cmp, False, donate=True,
                                            interpret=interpret), one)
    diff = float(np.max(np.abs(np.asarray(out_s)
                               - np.asarray(out_1).reshape(2, -1))))
    check(diff < 1e-5, f"sharded vs one-chip max|diff| = {diff}")
    report("sharded", n_known=n_known, depth=depth, gates=len(c.ops),
           devices=len(where), forward_compile_and_run_s=fwd_s,
           inverse_compile_and_run_s=inv_s, norm=norm,
           norm_err=abs(norm - 1.0), prob_zero=p0, limit=1e-4,
           n_cmp=n_cmp, sharded_compile_and_run_s=cmp_s,
           one_chip_compile_and_run_s=one_s, max_abs_diff=diff,
           diff_limit=1e-5)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase, on four chips")
    args = ap.parse_args(argv)
    device = phase_device(args.chips)
    if args.chips == 4:
        phase_sharded()
    else:
        phase_eager()
        phase_flagship()
        phase_agreement()
        phase_serve()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
