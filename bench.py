"""Headline benchmark: single-qubit gates/sec on a dense statevector.

Prints ONE JSON line on stdout:
  {"metric": ..., "value": N, "unit": "gates/sec", "vs_baseline": N}
All diagnostics (engine choice, per-size failures, effective bandwidth) go
to stderr so the driver's JSON parse never breaks.

The metric matches BASELINE.json's north star ("single-qubit gates/sec at
30q statevec") and is measured THROUGH THE FRAMEWORK's public circuit
engine: a block of single-qubit rotations applied to a 2^N-amplitude
statevector (split re/im f32 planes, see quest_tpu/state.py), timed over
repeated executions with buffer donation. The default engine is the
band-fusion engine (quest_tpu/ops/fusion): commuting gate runs compose
into one operator per 7-qubit band, each applied as a single MXU
contraction; if it fails to compile, the XLA per-gate path runs instead
and the fallback is REPORTED on stderr, never silent (ladder overridable
via QUEST_BENCH_ENGINES). A size ladder (30 -> 22) degrades
gracefully: any size that fails logs its error and the next one runs, so a
JSON line is emitted whenever ANY size succeeds.

A fusion-resistant CHAIN variant (dependent H/CNOT chain where no two
gates compose — _build_chain_circuit) rides along as chain_metric /
chain_value / chain_unit in the same JSON line, bounding the per-stage
floor so the headline cannot be read as fusion-gamed (VERDICT r5 weak
#7).

vs_baseline: measured from the reference's own CPU build when
benchmarks/reference_baseline.json exists (see benchmarks/measure_reference.py,
VERDICT round-1 item 6); otherwise falls back to an in-process NumPy port
of the reference butterfly (QuEST_cpu.c:1656-1713, vectorized), scaled
per-amplitude to the benchmark size.
"""

import json
import os
import sys
import time
import traceback
from typing import Optional

import jax
import numpy as np

from quest_tpu.precision import enable_compile_cache
enable_compile_cache()

REPO = os.path.dirname(os.path.abspath(__file__))
REF_BASELINE = os.path.join(REPO, "benchmarks", "reference_baseline.json")

GATES_PER_STEP = 16
INNER_STEPS = 16   # circuit applications per dispatch (lax.fori_loop):
                   # the measured program carries enough work to
                   # amortize the per-dispatch host cost


def _log(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _sync(state):
    jax.block_until_ready(state)


def _build_circuit(n: int):
    """GATES_PER_STEP single-qubit rotations round-robin over qubits
    [1, n-1] through the public Circuit builder."""
    from quest_tpu.circuit import Circuit

    rng = np.random.default_rng(42)
    c = Circuit(n)
    for i in range(GATES_PER_STEP):
        q = 1 + i % (n - 1)
        c.rx(q, float(rng.uniform(0, 2 * np.pi)))
    return c


def _build_chain_circuit(n: int):
    """FUSION-RESISTANT variant (VERDICT r5 weak #7): a dependent chain
    alternating Hadamards with CNOTs between two far-apart qubits, so no
    two gates compose — every gate is its own band operator / kernel
    stage (each H shares its qubit with the neighbouring CNOT's mixing
    side, which blocks both run composition and the scheduler's
    reordering; verified by tests/test_scheduler.py's plan assertion).
    The headline block of independent rotations fuses ~5:1 into band
    contractions; this chain bounds the engine's PER-STAGE floor, so
    the headline can't be read as fusion-gamed."""
    from quest_tpu.circuit import Circuit

    c = Circuit(n)
    a, b = 1, n - 2
    for i in range(GATES_PER_STEP):
        k = i % 4
        if k == 0:
            c.h(a)
        elif k == 1:
            c.cnot(a, b)
        elif k == 2:
            c.h(b)
        else:
            c.cnot(b, a)
    return c


def _build_deep_global_circuit(n: int, depth: int):
    """The deep-global testbed (docs/DISTRIBUTED.md): every layer
    rotates EVERY qubit — including the device-index ones — and
    entangles with CZs; the worst case for per-gate swap-dancing and
    the comm planner's headline workload. One home, shared by the
    multichip scenario, scripts/check_comm_golden.py and
    tests/test_comm.py so the goldens gate the same circuit the bench
    measures."""
    from quest_tpu.circuit import Circuit

    rng = np.random.default_rng(5)
    c = Circuit(n)
    for _ in range(depth):
        for q in range(n):
            c.rx(q, float(rng.uniform(0, 2 * np.pi)))
            c.ry(q, float(rng.uniform(0, 2 * np.pi)))
        for q in range(0, n - 1, 2):
            c.cz(q, q + 1)
    return c


def _basis_state(shape, rdt=None):
    """|0...0> planes built in ONE fused device buffer DIRECTLY in the
    engine's view shape (zeros().at.set() would briefly hold two
    full-state buffers; an out-of-jit reshape would relayout-copy —
    either one is 16 GB at 30q). rdt defaults to float32; the f64
    scenario passes float64."""
    import jax.numpy as jnp
    from quest_tpu.state import basis_planes

    n = int(np.prod(shape)).bit_length() - 2  # shape holds 2 * 2^n reals
    return basis_planes(0, n=n, rdt=rdt or jnp.float32, shape=shape)


def _hbm_limit():
    """Per-device HBM byte limit: the QUEST_HBM_BYTES override, else the
    device's own stats — None when the backend reports none (the CPU).
    The ONE discovery path shared by the banded OOM gate and the f64
    capacity gate (apply.f64_capacity_stats takes the result), so the
    two cannot disagree about the chip."""
    from quest_tpu.env import knob_value
    lim = knob_value("QUEST_HBM_BYTES")
    if lim is None:
        lim = (jax.local_devices()[0].memory_stats() or {}).get(
            "bytes_limit")
    return lim


def banded_fits(n: int, bytes_per_real: int = 4) -> bool:
    """Whether the banded engine's XLA band-dot footprint fits this
    device. The band dots need ~3x the state in HLO temps even under
    remat (measured: 24 GB at 30q, six 4 GB dot_general buffers), so on a
    16 GB v5e the 30q banded compile is a guaranteed OOM that still costs
    ~20 min of XLA time before failing — skip it up front. Shared by the
    bench ladder and scripts/tpu_prewarm.py so the measured 4x-state
    constant lives in one place. NOTE: this is the f32 XLA-dot model;
    the f64 limb path is chunk-bounded and gates through
    apply.f64_capacity_stats instead (_measure_f64_inner)."""
    lim = _hbm_limit()
    # state (2 planes) + ~3x in temps; f64 planes double every term
    need = 4 * 2 * bytes_per_real * (1 << n)
    if lim is None:
        _log(f"device reports no HBM limit; banded OOM gate is a no-op "
             f"at n={n} (a too-big size will pay its full compile "
             f"before failing)")
        return True
    if need > lim:
        _log(f"engine banded skipped at n={n}: ~4x state "
             f"({need / 2**30:.0f} GiB) exceeds device HBM "
             f"({lim / 2**30:.1f} GiB)")
        return False
    return True



def _engine_step(circ, n: int, engine: str, iters: int, density: bool):
    """(compiled step, boundary state shape) for an engine name — the
    ONE home of the engine -> (builder, shape) mapping, shared by the
    statevector ladder and the density scenario (the fused engine's
    boundary shape differs from the flat XLA ones; keeping the pairing
    in one place stops the copies drifting)."""
    from quest_tpu.state import fused_state_shape

    if engine == "fused":
        return (circ.compiled_fused(n, density=density, donate=True,
                                    iters=iters), fused_state_shape(n))
    if engine == "banded":
        return (circ.compiled_banded(n, density=density, donate=True,
                                     iters=iters), (2, 1 << n))
    if engine == "host":
        return (circ.compiled_host(n, density=density, iters=iters),
                (2, 1 << n))
    return (circ.compiled(n, density=density, donate=True, iters=iters),
            (2, 1 << n))


def _warm_step(n: int, build=_build_circuit):
    """Compile + warm the benchmark step through the fastest engine that
    works on this platform (jit errors only surface at first call, so the
    warmup runs inside the ladder). Returns (step, warmed_state, engine,
    compile_s) — compile_s is the winning engine's compile+warmup wall
    seconds, reported in the JSON line so the trajectory sees what the
    first run paid (the f64-26q warmup alone is ~297 s on chip).
    Fallbacks are loud, not silent; override via QUEST_BENCH_ENGINES."""
    import jax.numpy as jnp

    on_tpu = jax.devices()[0].platform == "tpu"
    # CPU fallback leads with the NATIVE host engine (quest_tpu/host.py):
    # cache-blocked C++ kernels, measured 140 gates/s @ 24q vs the
    # reference CPU build's 8.98 (the XLA-CPU banded path loses to the
    # reference at 7.3 — VERDICT r4 weak item 1)
    from quest_tpu.env import knob_value
    try:
        ladder = knob_value("QUEST_BENCH_ENGINES")
    except ValueError as e:
        raise SystemExit(str(e))
    if ladder is None:
        ladder = ("fused,banded,xla" if on_tpu else "host,banded,xla"
                  ).split(",")
    last = None
    for name in ladder:
        if name == "banded" and on_tpu and not banded_fits(n):
            continue
        circ = build(n)
        t0 = time.perf_counter()
        try:
            step, shape = _engine_step(circ, n, name, INNER_STEPS,
                                       density=False)
            state = _basis_state(shape)
            state = step(state)  # warmup/compile
            _sync(state)
            compile_s = time.perf_counter() - t0
            _log(f"n={n} engine={name} compile+warmup {compile_s:.1f}s")
            return step, state, name, compile_s
        except Exception as e:
            last = e
            _log(f"engine {name} failed at n={n}:\n{traceback.format_exc()}")
    raise RuntimeError(f"no engine available at n={n}") from last


def _measure_jax(n: int, reps: int):
    step, state, engine, compile_s = _warm_step(n)
    t0 = time.perf_counter()
    for _ in range(reps):
        state = step(state)
    _sync(state)
    dt = time.perf_counter() - t0
    gps = GATES_PER_STEP * INNER_STEPS * reps / dt
    eff_bw = gps * 2 * (1 << n) * 4 * 2  # r+w of both f32 planes per gate
    _log(f"n={n} engine={engine}: {gps:.1f} gates/s "
         f"({eff_bw/1e9:.1f} GB/s effective per-gate traffic)")
    return gps, engine, compile_s


def _measure_chain(n: int, reps: int):
    """gates/sec on the fusion-resistant dependent chain at the headline
    size — the engine's per-stage floor. Returns None on any failure so
    the headline JSON never breaks."""
    try:
        step, state, engine, compile_s = _warm_step(
            n, build=_build_chain_circuit)
        t0 = time.perf_counter()
        for _ in range(reps):
            state = step(state)
        _sync(state)
        dt = time.perf_counter() - t0
        gps = GATES_PER_STEP * INNER_STEPS * reps / dt
        _log(f"chain n={n} engine={engine}: {gps:.1f} gates/s "
             f"(dependent chain, no fusion)")
        return gps, compile_s
    except Exception:
        _log(f"chain variant failed (headline unaffected):\n"
             f"{traceback.format_exc()}")
        return None, None


def _measure_numpy_amps_per_sec(n: int, num_gates: int = 8) -> float:
    """Amplitudes-processed/sec for the dense host butterfly kernel."""
    re = np.zeros(1 << n, dtype=np.float32)
    re[0] = 1.0
    im = np.zeros(1 << n, dtype=np.float32)
    c, s = np.float32(0.6), np.float32(0.8)
    t0 = time.perf_counter()
    for i in range(num_gates):
        q = 1 + i % (n - 1)
        pre, post = 1 << (n - 1 - q), 1 << q
        tr = re.reshape(pre, 2, post)
        ti = im.reshape(pre, 2, post)
        r0, r1 = tr[:, 0].copy(), tr[:, 1].copy()
        i0, i1 = ti[:, 0].copy(), ti[:, 1].copy()
        tr[:, 0] = c * r0 + s * i1
        ti[:, 0] = c * i0 - s * r1
        tr[:, 1] = s * i0 + c * r1
        ti[:, 1] = -s * r0 + c * i1
    dt = time.perf_counter() - t0
    return num_gates * (1 << n) / dt


def _build_density_circuit(nd: int):
    """BASELINE.json config-4 shaped channel scenario on an nd-qubit
    density register: a rotation gate layer, amplitude damping, a
    two-qubit depolarising channel (16-op Kraus) and a 4-op Pauli
    Kraus map — the doubled-register channel kernels the reference
    implements in QuEST_cpu.c:48-383, here compiled as fused
    superoperator stages (ops/channels.py, ops/pallas_band.py
    PairStage)."""
    from quest_tpu.circuit import Circuit
    from quest_tpu.ops import matrices as M

    rng = np.random.default_rng(7)
    c = Circuit(nd)
    for q in range(nd):
        c.rx(q, float(rng.uniform(0, 2 * np.pi)))
    c.damping(1, 0.1)
    # two-qubit depolarising as its 16-op Kraus map (ref
    # mixTwoQubitDepolarising semantics)
    p = 0.15
    paulis = [np.eye(2), M.PAULI_X, M.PAULI_Y, M.PAULI_Z]
    ops2 = []
    for i, a in enumerate(paulis):
        for j, b in enumerate(paulis):
            w = np.sqrt(1 - 15 * p / 16) if i == j == 0 else np.sqrt(p / 16)
            ops2.append(w * np.kron(b, a))
    c.kraus((0, nd - 1), ops2)
    c.kraus(2, M.pauli_kraus(0.05, 0.05, 0.05))   # 4-op Kraus
    return c


def _measure_density(reps: int):
    """(ops/sec, nd, compile_s) through the fused engine on a density
    register, or (None, None, None) — the density figure must never
    break the headline JSON. Ladder over register sizes like the
    statevector bench."""
    on_tpu = jax.devices()[0].platform == "tpu"
    sizes = (15, 14, 13) if on_tpu else (12, 10)
    # Pallas kernels need the chip; CPU degradation leads with the native
    # host engine, then the XLA banded path if the native lib is missing
    engines = ("fused",) if on_tpu else ("host", "banded")
    iters = 4
    for nd in sizes:
        n = 2 * nd                      # doubled register
        for engine in engines:
            try:
                circ = _build_density_circuit(nd)
                num_ops = len(circ.ops)
                t0 = time.perf_counter()
                step, shape = _engine_step(circ, n, engine, iters,
                                           density=True)
                state = _basis_state(shape)     # |0><0| flat
                state = step(state)
                _sync(state)
                compile_s = time.perf_counter() - t0
                _log(f"density nd={nd} engine={engine} compile+warmup "
                     f"{compile_s:.1f}s")
                t0 = time.perf_counter()
                for _ in range(reps):
                    state = step(state)
                _sync(state)
                dt = time.perf_counter() - t0
                ops_per_sec = num_ops * iters * reps / dt
                _log(f"density nd={nd} engine={engine} ({n} state qubits): "
                     f"{ops_per_sec:.1f} ops/s "
                     f"({num_ops} ops: {nd} rotations + damping + 2q-depol "
                     f"+ 4-op Kraus)")
                return ops_per_sec, nd, compile_s
            except Exception:
                _log(f"density nd={nd} engine={engine} failed; trying "
                     f"next:\n{traceback.format_exc()}")
    return None, None, None


def _build_traj_circuit(n: int, depth: int = 3):
    """Noisy RCS-shaped trajectory workload (ISSUE 4 scenario): depth
    layers of random single-qubit rotations + a CZ brick, each followed
    by the standard NISQ noise model — a depolarising channel on EVERY
    qubit plus one amplitude-damping channel per layer (the per-qubit
    per-layer channel density of examples/noisy_rcs_trajectories.py) —
    the B-shot statevector unraveling of an open-system circuit
    (quest_tpu/trajectories.py run_batched; the density engine would
    need 2n state qubits for the same physics)."""
    from quest_tpu.circuit import Circuit

    rng = np.random.default_rng(11)
    c = Circuit(n)
    for d in range(depth):
        for q in range(n):
            kind = rng.integers(0, 3)
            ang = float(rng.uniform(0, 2 * np.pi))
            (c.rx if kind == 0 else c.ry if kind == 1 else c.rz)(q, ang)
        for q in range(d % 2, n - 1, 2):
            c.cz(q, q + 1)
        for q in range(n):
            c.depolarising(q, 0.02)
        c.damping(int(rng.integers(0, n)), 0.05)
    return c


def _measure_trajectories(shots: int = 256, chunk: int = 8):
    """Batched-trajectory scenario: `shots` noisy shots through
    trajectories.run_batched (the batched sweep engine; launches
    independent of B) vs the vmap-of-eager-workers BASELINE (the
    module-docstring pattern this PR obsoletes: one per-gate pass per
    op per shot). Returns a record dict or None — the scenario must
    never break the headline JSON. The baseline is timed on a SUBSET
    of shots (one chunk, logged) and reported as a rate: shots are
    i.i.d., so shots/s is size-invariant; timing 256 eager shots at
    ~1 shot/s would add minutes of bench wall for the same number."""
    import jax.numpy as jnp
    from quest_tpu import trajectories as T
    from quest_tpu.circuit import _apply_one
    from quest_tpu.env import batch_bucket
    from quest_tpu.state import basis_planes

    on_tpu = jax.devices()[0].platform == "tpu"
    # off-chip the ladder starts where a host-engine CPU can actually
    # afford the full B (n=24 costs minutes of warmup before the pilot
    # gate can even fire); the pilot still degrades loudly within each
    # ladder
    sizes = (24, 20) if on_tpu else (20, 16)
    if on_tpu:
        chunk = min(shots, 64)   # HBM holds the whole chunk batch
    for n in sizes:
        try:
            circ = _build_traj_circuit(n)
            stats = T.plan_stats(circ, shots)
            key = jax.random.key(0)

            # per-shot <Z_top> reduced PER CHUNK: a serving workload
            # averages observables, it does not materialize B full
            # statevectors (32 GiB at B=256, n=24)
            @jax.jit
            def z0(planes):
                planes = jnp.asarray(planes)
                v = (planes[:, 0] ** 2 + planes[:, 1] ** 2).reshape(
                    planes.shape[0], 2, -1)
                return jnp.sum(v[:, 0] - v[:, 1], axis=1)

            t0 = time.perf_counter()
            T.run_batched(circ, key, chunk, chunk=chunk,
                          observable=z0)               # warm/compile
            compile_s = time.perf_counter() - t0
            _log(f"traj n={n} batched compile+warmup {compile_s:.1f}s "
                 f"(chunk {chunk}, bucket shares one compiled program)")
            # pilot gate (the size-ladder analogue of banded_fits): a
            # 2-chunk pilot projects the full-B wall time; a host that
            # cannot afford the full run at this size degrades to the
            # next size LOUDLY and measures the full B there — a
            # subset-extrapolated headline rate would be easy to game
            pilot = chunk
            t0 = time.perf_counter()
            vals, _ = T.run_batched(circ, key, pilot, chunk=chunk,
                                    observable=z0)
            jax.block_until_ready(vals)
            pilot_dt = time.perf_counter() - t0
            projected = pilot_dt * shots / pilot
            if projected > 300 and n != sizes[-1]:
                _log(f"traj n={n}: projected {projected:.0f}s for "
                     f"B={shots} exceeds the 300s bench budget on this "
                     f"host ({pilot / pilot_dt:.2f} shots/s pilot); "
                     f"degrading to the next size")
                continue
            t0 = time.perf_counter()
            vals, draws = T.run_batched(circ, key, shots, chunk=chunk,
                                        observable=z0)
            jax.block_until_ready(vals)
            dt = time.perf_counter() - t0
            shots_per_s = shots / dt
            _log(f"traj n={n}: {shots} shots in {dt:.1f}s -> "
                 f"{shots_per_s:.2f} shots/s (batched; "
                 f"{stats['hbm_sweeps']} sweeps/app independent of B)")

            # baseline: jax.vmap over the eager per-gate workers — the
            # strongest PRE-batched-engine shape (one jitted program,
            # but per-gate pass structure and per-shot channel math)
            def shot(k):
                amps = basis_planes(0, n=n, rdt=jnp.float32)
                for op in circ.ops:
                    if op.kind == "superop":
                        amps, k, _ = T.kraus(amps, k, n, op.targets,
                                             op.meta[1])
                    else:
                        amps = _apply_one(amps, n, op)
                return amps
            base = jax.jit(lambda ks: z0(jax.vmap(shot)(ks)))
            bshots = min(shots, chunk)
            keys = jax.random.split(key, bshots)
            t0 = time.perf_counter()
            out = base(keys)                      # warm/compile
            jax.block_until_ready(out)
            base_compile_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            out = base(keys)
            jax.block_until_ready(out)
            base_dt = time.perf_counter() - t0
            base_rate = bshots / base_dt
            _log(f"traj n={n} baseline (vmap-of-eager, {bshots}-shot "
                 f"subset, compile {base_compile_s:.1f}s): "
                 f"{base_rate:.2f} shots/s -> speedup "
                 f"{shots_per_s / base_rate:.1f}x")
            return {
                "traj_metric": (f"noisy-trajectory shots/sec @ {n}q, "
                                f"B={shots} (batched engine)"),
                "traj_value": round(shots_per_s, 2),
                "traj_unit": "shots/sec",
                "traj_compile_s": round(compile_s, 1),
                "batch": shots,
                # the EXECUTED bucket: chunking bounds live memory, so
                # each launch streams bucket_of(chunk) states
                "states_per_sweep": batch_bucket(min(chunk, shots)),
                "traj_hbm_sweeps": stats["hbm_sweeps"],
                "traj_channels": stats["channels"],
                "traj_baseline_value": round(base_rate, 2),
                "traj_baseline_note": (f"jax.vmap of eager per-gate "
                                       f"workers, {bshots}-shot subset"),
                "traj_speedup": round(shots_per_s / base_rate, 2),
            }
        except Exception:
            _log(f"trajectories n={n} failed; trying next size down:\n"
                 f"{traceback.format_exc()}")
    return None


def _measure_f64(reps: int):
    """(gates/sec, n) for the f64 (reference-default precision) banded
    path — on TPU this rides the MXU limb scheme (ops/apply.py
    _limb_band_contract, r5); returns (None, None) on any failure so
    the headline JSON never breaks. TPU-only: the CPU fallback's f64
    story is the host engine's, already covered by the headline."""
    if jax.devices()[0].platform != "tpu":
        return None, None, None
    prior_x64 = bool(jax.config.jax_enable_x64)
    if not prior_x64:
        try:
            jax.config.update("jax_enable_x64", True)
        except Exception:
            return None, None, None
    try:
        return _measure_f64_inner(reps)
    finally:
        if not prior_x64:
            # restore the process-global flag: anything running after
            # this helper (tpu_prewarm imports bench) must not silently
            # promote f32 work to f64
            jax.config.update("jax_enable_x64", prior_x64)


def _measure_f64_inner(reps: int):
    import jax.numpy as jnp
    from quest_tpu.ops import apply as A

    lim = _hbm_limit()
    for n in (28, 26, 24):
        # gate through the chunk-bounded limb capacity model, not the
        # f32 XLA-dot constant: the chunked limb path's working set is
        # 2x state + ~4x one chunk, which is what routes 28q f64 — the
        # reference's DEFAULT precision at the chip's capacity point —
        # onto a 15.75 GiB v5e at all (docs/PRECISION.md; the old
        # banded_fits(28, 8) gate refused it while the un-chunked form
        # OOMed, so the question sat unanswerable)
        cap = A.f64_capacity_stats(n, hbm_bytes=lim)
        if lim is not None and not cap["fits_hbm"]:
            _log(f"f64 n={n} skipped: limb peak "
                 f"{cap['peak_bytes'] / 2**30:.1f} GiB exceeds device "
                 f"HBM ({lim / 2**30:.1f} GiB)")
            continue
        try:
            circ = _build_circuit(n)
            iters = 4
            t0 = time.perf_counter()
            step, shape = _engine_step(circ, n, "banded", iters,
                                       density=False)
            state = _basis_state(shape, rdt=jnp.float64)
            state = step(state)
            _sync(state)
            compile_s = time.perf_counter() - t0
            _log(f"f64 n={n} compile+warmup {compile_s:.1f}s")
            t0 = time.perf_counter()
            for _ in range(reps):
                state = step(state)
            _sync(state)
            dt = time.perf_counter() - t0
            gps = GATES_PER_STEP * iters * reps / dt
            _log(f"f64 banded n={n}: {gps:.1f} gates/s (MXU limb dots)")
            return gps, n, compile_s
        except Exception:
            _log(f"f64 n={n} failed; trying next size down:\n"
                 f"{traceback.format_exc()}")
    return None, None, None


def _sweep_metrics(build, n: int):
    """(hbm_sweeps, per-sweep stage counts, pipeline_* keys) of a bench
    circuit through ONE Circuit.plan_stats pass — pure host planning
    (no compile, no chip), the CPU-assertable metrics behind the
    sweep-fusion layer and the decoupled pipeline (docs/SWEEPS.md).
    The pipeline dict is None when the legacy driver is active
    (QUEST_FUSED_PIPELINE=0), so the JSON stays bit-for-bit the old
    line for the silicon A/B. Returns (None, None, None) on any
    failure so the headline JSON never breaks."""
    try:
        rec = build(n).plan_stats()["fused"]
        pipe = None
        if "pipeline_in_slots" in rec:
            pipe = {k: rec[k] for k in ("pipeline_in_slots",
                                        "pipeline_out_slots",
                                        "pipeline_overlap_steps")}
        return rec["hbm_sweeps"], rec["sweep_stages"], pipe
    except Exception:
        _log(f"sweep metrics failed at n={n}:\n{traceback.format_exc()}")
        return None, None, None


def _measure_rcs(depth: int = 20, reps: int = 3):
    """Wall seconds per run of the depth-20 30q RCS circuit through the
    fused engine — the whole-circuit latency target of ROADMAP item 1
    (2.21 s measured r5 on the in-place slot driver; the decoupled
    pipeline targets <= 1.5 s). TPU-only (the CPU host cannot hold a
    30q state); returns (seconds, gate count, compile_s) or Nones so
    the headline JSON never breaks. The same circuit
    benchmarks/run.py rcs measures, now emitted as rcs_* keys in the
    headline line so the BENCH_r*.json trajectory captures the delta
    without a separate run."""
    if jax.devices()[0].platform != "tpu":
        return None, None, None
    import jax.numpy as jnp

    from quest_tpu.circuit import random_circuit
    from quest_tpu.state import basis_planes, fused_state_shape

    n = 30
    try:
        circ = random_circuit(n, depth, seed=1)
        t0 = time.perf_counter()
        fn = circ.compiled_fused(n, density=False, donate=True)
        amps = basis_planes(0, n=n, rdt=jnp.float32,
                            shape=fused_state_shape(n))
        amps = fn(amps)
        _sync(amps)
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(reps):
            amps = fn(amps)
        _sync(amps)
        dt = (time.perf_counter() - t0) / reps
        _log(f"rcs 30q d{depth}: {dt:.2f} s/run "
             f"({len(circ.ops) / dt:.1f} gates/s)")
        return dt, len(circ.ops), compile_s
    except Exception:
        _log(f"rcs scenario failed (headline unaffected):\n"
             f"{traceback.format_exc()}")
        return None, None, None


# Every key the headline JSON line may carry — the schema the trajectory
# files (BENCH_r*.json) are parsed against. main() asserts the emitted
# line stays inside it and scripts/check_sweep_golden.py asserts the
# round's NEW keys (pipeline_*, f64_28q_*, rcs_*) are registered here,
# so the next chip run lands in the trajectory without hand-editing.
HEADLINE_JSON_KEYS = frozenset({
    "metric", "value", "unit", "vs_baseline", "baseline_note", "engine",
    "compile_s", "hbm_sweeps", "sweep_stages",
    "pipeline_in_slots", "pipeline_out_slots", "pipeline_overlap_steps",
    "density_metric", "density_value", "density_unit", "density_compile_s",
    "f64_metric", "f64_value", "f64_unit", "f64_compile_s",
    "f64_28q_peak_bytes", "f64_28q_fits_hbm", "f64_28q_chunk_elems",
    "f64_28q_value", "f64_28q_unit",
    "chain_metric", "chain_value", "chain_unit", "chain_compile_s",
    "chain_hbm_sweeps", "chain_sweep_stages",
    "rcs_metric", "rcs_value", "rcs_unit", "rcs_gates_per_sec",
    "rcs_compile_s",
    "traj_metric", "traj_value", "traj_unit", "traj_compile_s", "batch",
    "states_per_sweep", "traj_hbm_sweeps", "traj_channels",
    "traj_baseline_value", "traj_baseline_note", "traj_speedup",
    "plan_metric", "plan_value", "plan_unit", "plan_engine",
    "plan_incumbent", "plan_candidates", "plan_search_ms",
    "plan_warm_ms", "plan_cache_cold", "plan_cache_warm",
    "plan_chosen_ms", "plan_forced_pergate_ms", "plan_forced_banded_ms",
    "plan_forced_fused_ms",
    "fleet_proc_metric", "fleet_proc_unit", "fleet_proc_requests",
    "fleet_proc_cores", "fleet_proc_host_parallelism",
    "fleet_proc_rps_1", "fleet_proc_rps_2",
    "fleet_proc_rps_4", "fleet_proc_speedup_4", "fleet_proc_efficiency",
    "fleet_proc_p50_ms", "fleet_proc_p99_ms", "fleet_proc_kill_p99_ms",
    "fleet_proc_kill_p99_delta_ms", "fleet_proc_kill_lost",
    "grad_metric", "grad_value", "grad_unit", "grad_compile_s",
    "grad_n", "grad_params", "grad_depth",
    "grad_steps_per_s_adjoint", "grad_steps_per_s_taped", "grad_speedup",
    "grad_qaoa_params", "grad_qaoa_steps_per_s_adjoint",
    "grad_qaoa_steps_per_s_taped", "grad_qaoa_speedup",
    "grad_engine_auto", "grad_adjoint_peak_bytes",
    "grad_taped_residual_bytes", "grad_residual_ratio",
    "grad_widest_trainable_n_adjoint", "grad_widest_trainable_n_taped",
    "grad_parity",
    "gallery_metric", "gallery_value", "gallery_unit", "gallery_n",
}) | frozenset(
    # the workload-gallery table (`bench.py gallery`): per circuit
    # class, raw-vs-transpiled op counts, predicted HBM sweeps and
    # measured serve throughput (docs/TRANSPILE.md)
    f"gallery_{cls}_{col}"
    for cls in ("qft", "qaoa", "rcs", "adder", "ghz")
    for col in ("ops_raw", "ops_auto", "sweeps_raw", "sweeps_auto",
                "sweep_ratio", "rps_raw", "rps_auto", "speedup"))


def _baseline_gates_per_sec(n: int) -> tuple[float, str]:
    """Reference gates/sec at size n. Prefers the measured reference-build
    numbers (amps/sec scale-invariantly per the reference's O(2^n) kernels);
    falls back to the in-process NumPy butterfly."""
    if os.path.exists(REF_BASELINE):
        try:
            with open(REF_BASELINE) as f:
                data = json.load(f)
            entry = data.get("single_qubit_gates", {})
            amps_per_sec = float(entry["amps_per_sec"])
            src = f"reference build ({entry.get('config', 'cpu')})"
            return amps_per_sec / (1 << n), src
        except Exception as e:
            _log(f"could not use {REF_BASELINE}: {e!r}")
    base_n = min(n, 22)
    return _measure_numpy_amps_per_sec(base_n) / (1 << n), "numpy butterfly"


def _run_serve_load(circuit, states, arrivals, *, wait_ms, max_batch):
    """One pass of the closed-loop serving client: submit each state at
    its arrival offset (seconds from pass start; an all-zeros schedule
    is the saturation pass — submit as fast as the engine admits),
    drain, and report (achieved_rps, registry snapshot). Each pass uses
    a FRESH metrics registry so latency percentiles and occupancy are
    per-load, not cumulative."""
    from quest_tpu.serve import ServeEngine, metrics

    reg = metrics.Registry()
    with ServeEngine(max_wait_ms=wait_ms, max_batch=max_batch,
                     max_queue=max(4096, 2 * len(states)),
                     registry=reg) as eng:
        # warm every bucket this pass can resolve to (and the demux
        # path), so the measurement is steady-state serving, not compile
        from quest_tpu.serve import warmup
        warmup(eng, [circuit])
        eng.submit(circuit, state=states[0]).result(timeout=600)
        reg2 = metrics.Registry()
        eng.registry = reg2
        t0 = time.perf_counter()
        futs = []
        for s, at in zip(states, arrivals):
            delay = t0 + at - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            futs.append(eng.submit(circuit, state=s))
        for f in futs:
            f.result(timeout=600)
        elapsed = time.perf_counter() - t0
    return len(states) / elapsed, reg2.snapshot()


def _measure_serve(max_batch: int = 64, wait_ms: float = 5.0):
    """The `bench.py serve` scenario (docs/SERVING.md): a closed-loop
    Poisson client against ServeEngine at several offered loads, vs the
    documented no-coalescing baseline (QUEST_SERVE_MAX_WAIT_MS=0 — one
    launch per request) at the same loads. Emits serve_* JSON keys:
    saturation throughput + speedup, mean batch occupancy at high load,
    p50/p95/p99 end-to-end latency per load with the baseline column.

    Off-chip the workload register stays sub-kernel-tier (CPU Pallas
    needs interpret mode); on TPU it rides the real kernels."""
    platform = jax.devices()[0].platform
    n = 20 if platform == "tpu" else 9
    circ = _build_circuit(n)
    rng = np.random.default_rng(7)
    n_sat = 512
    states = rng.standard_normal((n_sat, 2, 1 << n)).astype(np.float32)
    states /= np.sqrt((states ** 2).sum(axis=(1, 2), keepdims=True))
    zeros = np.zeros(n_sat)

    t_compile = time.perf_counter()
    # saturation: every request already queued — the throughput ceiling
    sat_rps, sat_snap = _run_serve_load(
        circ, states, zeros, wait_ms=wait_ms, max_batch=max_batch)
    compile_s = time.perf_counter() - t_compile   # first pass pays it
    base_n = min(n_sat, 256)                      # baseline is slow
    base_rps, base_snap = _run_serve_load(
        circ, states[:base_n], zeros[:base_n], wait_ms=0,
        max_batch=max_batch)
    _log(f"serve saturation: {sat_rps:.0f} req/s coalescing vs "
         f"{base_rps:.0f} req/s no-batching = {sat_rps / base_rps:.1f}x "
         f"(occupancy "
         f"{sat_snap['histograms']['serve_batch_occupancy']['mean']:.2f})")

    def _lat(snap):
        h = snap["histograms"]["serve_e2e_latency_s"]
        return {k: round(h[k] * 1e3, 3) for k in ("p50", "p95", "p99")}

    loads = []
    for frac in (0.5, 3.0):
        # offered load relative to the BASELINE's capacity: 0.5x = both
        # modes keep up (latency column), 3x = beyond what one-launch-
        # per-request can serve but within the coalescing ceiling — the
        # regime the subsystem exists for
        offered = frac * base_rps
        k = int(max(64, min(n_sat, offered * 2.0)))
        arrivals = np.cumsum(rng.exponential(1.0 / offered, size=k))
        rps, snap = _run_serve_load(circ, states[:k], arrivals,
                                    wait_ms=wait_ms, max_batch=max_batch)
        b_rps, b_snap = _run_serve_load(circ, states[:k], arrivals,
                                        wait_ms=0, max_batch=max_batch)
        lat, b_lat = _lat(snap), _lat(b_snap)
        occ = snap["histograms"]["serve_batch_occupancy"]["mean"]
        loads.append({
            "offered_rps": round(offered, 1),
            "achieved_rps": round(rps, 1),
            "occupancy": round(occ, 3),
            "p50_ms": lat["p50"], "p95_ms": lat["p95"],
            "p99_ms": lat["p99"],
            "base_achieved_rps": round(b_rps, 1),
            "base_p50_ms": b_lat["p50"], "base_p95_ms": b_lat["p95"],
            "base_p99_ms": b_lat["p99"],
        })
        _log(f"serve load {offered:.0f} req/s offered: achieved "
             f"{rps:.0f} (occ {occ:.2f}, p95 {lat['p95']:.1f} ms) vs "
             f"baseline {b_rps:.0f} (p95 {b_lat['p95']:.1f} ms)")

    sat_lat = _lat(sat_snap)
    return {
        "serve_metric": (f"served requests/sec at saturation @ {n}q "
                         f"statevec, continuous batching ({platform})"),
        "serve_value": round(sat_rps, 1),
        "serve_unit": "req/s",
        "serve_baseline_value": round(base_rps, 1),
        "serve_baseline_note": ("QUEST_SERVE_MAX_WAIT_MS=0: no "
                                "coalescing, one launch per request"),
        "serve_speedup": round(sat_rps / base_rps, 2),
        "serve_occupancy_mean": round(
            sat_snap["histograms"]["serve_batch_occupancy"]["mean"], 3),
        "serve_p50_ms": sat_lat["p50"],
        "serve_p95_ms": sat_lat["p95"],
        "serve_p99_ms": sat_lat["p99"],
        "serve_compile_s": round(compile_s, 1),
        "serve_max_batch": max_batch,
        "serve_wait_ms": wait_ms,
        "serve_loads": loads,
        # resilience health of the bench run itself (docs/RESILIENCE.md):
        # a nonzero restart/split/degrade count means the measured
        # throughput rode a recovery path, not the steady state — the
        # bench should be rerun and the cause investigated
        "serve_worker_restarts": sat_snap["counters"].get(
            "serve_worker_restarts", 0),
        "serve_batches_split": sat_snap["counters"].get(
            "serve_batches_split", 0),
        "serve_degraded_dispatches": sat_snap["counters"].get(
            "serve_degraded_dispatches", 0),
    }


def _measure_fleet(replicas: int = 2, max_batch: int = 32,
                   n_requests: int = 192):
    """The `bench.py fleet` scenario (docs/SERVING.md §fleet): four legs
    over a ServeFleet, each emitting fleet_* JSON keys and each the
    subject of a scripts/check_fleet_golden.py gate:

      * THROUGHPUT — a closed-loop multi-tenant stream through the
        fleet vs the SAME stream through one ServeEngine (fleet_value /
        fleet_single_value / fleet_speedup; on a GIL-bound CPU host two
        worker threads can price BELOW one — the number is reported,
        not gated).
      * FAILOVER — the same stream with a seeded plan killing one
        replica past its restart budget mid-stream: every future must
        resolve and the undispatched requests must be served by the
        survivor (fleet_failover_unresolved == 0 is the gate).
      * SHED — overload with two priority classes past the shed
        threshold: 100% of sheds land on class 0
        (fleet_shed_lowest_only), with the high class's p95 under shed
        reported (fleet_shed_p95_ms).
      * DURABLE — one long job through submit(durable_dir=), preempted
        mid-checkpoint-chain by a seeded durable.preempt kill: it must
        RESUME (durable_resumes >= 1) and finish bit-identical to an
        uninterrupted run_durable (fleet_durable_resume_bitexact)."""
    import hashlib
    import shutil
    import tempfile

    import quest_tpu as qt
    from quest_tpu.resilience import FaultPlan, faults, run_durable
    from quest_tpu.serve import ServeFleet, ServeEngine, ShedError
    from quest_tpu.serve import metrics, warmup

    platform = jax.devices()[0].platform
    n = 20 if platform == "tpu" else 9
    circ = _build_circuit(n)
    rng = np.random.default_rng(11)
    states = rng.standard_normal((n_requests, 2, 1 << n)).astype(np.float32)
    states /= np.sqrt((states ** 2).sum(axis=(1, 2), keepdims=True))
    tenants = ["alice", "bob", "carol"]

    def stream(target):
        t0 = time.perf_counter()
        futs = [target.submit(circ, state=states[i],
                              **({"tenant": tenants[i % 3]}
                                 if isinstance(target, ServeFleet) else {}))
                for i in range(n_requests)]
        for f in futs:
            f.result(timeout=600)
        return n_requests / (time.perf_counter() - t0)

    # leg 1: throughput, fleet vs single engine
    reg = metrics.Registry()
    with ServeFleet(replicas=replicas, max_wait_ms=2,
                    max_batch=max_batch, registry=reg) as fleet:
        warmup(fleet, [circ])
        stream(fleet)                        # warm pass pays compiles
        fleet_rps = stream(fleet)
    with ServeEngine(max_wait_ms=2, max_batch=max_batch,
                     registry=metrics.Registry()) as single:
        stream(single)
        single_rps = stream(single)
    _log(f"fleet throughput: {fleet_rps:.0f} req/s x{replicas} replicas "
         f"vs {single_rps:.0f} single-engine")

    # leg 2: failover — kill one replica past its budget mid-stream
    plan = FaultPlan().inject(
        "serve.worker_loop", error=RuntimeError("replica lost"),
        match=lambda ctx: (ctx.get("replica") == "r0"
                           and ctx["phase"] == "popped"))
    reg_f = metrics.Registry()
    unresolved = 0
    with faults.active(plan):
        with ServeFleet(replicas=replicas, max_wait_ms=2,
                        max_batch=max_batch, restart_max=1,
                        backoff_base_s=0.0, registry=reg_f) as fleet:
            futs = [fleet.submit(circ, state=states[i])
                    for i in range(n_requests // 2)]
            fleet.drain(timeout_s=600)
            unresolved = sum(1 for f in futs if not f.done())
    snap_f = reg_f.snapshot()["counters"]
    _log(f"fleet failover: {snap_f.get('fleet_failovers', 0)} failovers, "
         f"{snap_f.get('serve_requests_served', 0)} served, "
         f"{unresolved} unresolved")

    # leg 3: shed — overload with two priority classes. max_batch above
    # the per-replica queue bound keeps the backlog QUEUED (nothing
    # dispatches until drain), so pressure provably crosses the
    # threshold while the victims are still evictable. The free class
    # floods first and the paying burst stays SMALLER than the queued
    # free backlog, so class 0 never exhausts — the acceptance contract
    # ("100% of sheds on the lower class until it is exhausted") is
    # pinned in its never-exhausted regime here; the exhaustion edge is
    # pinned in tests/test_fleet.py.
    reg_s = metrics.Registry()
    shed_stream = min(n_requests, 96)
    queue_bound = max(8, shed_stream // 8)
    with ServeFleet(replicas=replicas, max_wait_ms=600_000,
                    max_queue=queue_bound,
                    max_batch=4 * shed_stream,
                    shed_threshold=0.5, priorities=2,
                    registry=reg_s) as fleet:
        for i in range(shed_stream):
            try:
                fleet.submit(circ, state=states[i], tenant="free",
                             priority=0)
            except ShedError:
                pass
        n_high = (replicas * queue_bound) // 4
        futs_hi = []
        for i in range(n_high):
            futs_hi.append((time.perf_counter(), fleet.submit(
                circ, state=states[i], tenant="paying", priority=1)))
        fleet.drain(timeout_s=600)
        # the high class's OWN e2e latencies: the shared histogram also
        # carries the surviving free-class waits, which dominate it in
        # this build-a-backlog scenario — the key promises the PAYING
        # class's experience under shed
        lat_hi = []
        for t0, f in futs_hi:
            f.result(timeout=600)
            lat_hi.append(time.perf_counter() - t0)
    snap_s = reg_s.snapshot()
    shed_total = snap_s["counters"].get("shed_requests", 0)
    shed_p0 = snap_s["counters"].get("shed_requests_p0", 0)
    shed_p1 = snap_s["counters"].get("shed_requests_p1", 0)
    lat_hi.sort()
    p95_hi = 1e3 * lat_hi[min(len(lat_hi) - 1,
                              int(round(0.95 * (len(lat_hi) - 1))))]
    _log(f"fleet shed: {shed_total} shed ({shed_p0} class-0, "
         f"{shed_p1} class-1), paying-class p95 under shed "
         f"{p95_hi:.1f} ms")

    # leg 4: durable through serve, preempted mid-chain
    nd = 16 if platform == "tpu" else 8
    circ_d = _build_durable_circuit(nd, layers=6)
    q0 = qt.init_debug_state(qt.create_qureg(nd))
    s0 = np.asarray(jax.device_get(q0.amps))
    td = tempfile.mkdtemp(prefix="quest-fleet-bench-")
    try:
        # engine auto-resolves exactly like the serve worker's
        # run_durable call does — the bit-identity comparison must ride
        # the same engine on every platform
        ref = run_durable(circ_d, q0, os.path.join(td, "ref"), every=2)
        ref_hash = hashlib.sha256(
            np.asarray(jax.device_get(ref.amps)).tobytes()).hexdigest()
        reg_d = metrics.Registry()
        plan_d = FaultPlan().inject("durable.preempt", after_n=5,
                                    times=1)
        with faults.active(plan_d):
            with ServeFleet(replicas=replicas, max_wait_ms=2,
                            registry=reg_d) as fleet:
                out = fleet.submit(
                    circ_d, state=s0,
                    durable_dir=os.path.join(td, "job"),
                    durable_every=2).result(timeout=600)
        got_hash = hashlib.sha256(np.asarray(out).tobytes()).hexdigest()
        resumed = reg_d.counter("durable_resumes").value
        preempted = plan_d.fired("durable.preempt")
    finally:
        shutil.rmtree(td, ignore_errors=True)
    _log(f"fleet durable: preempt fired {preempted}x, {resumed} "
         f"resume(s), bitexact={got_hash == ref_hash}")

    return {
        "fleet_metric": (f"fleet req/s @ {n}q x{replicas} replicas "
                         f"({platform})"),
        "fleet_value": round(fleet_rps, 1),
        "fleet_unit": "req/s",
        "fleet_single_value": round(single_rps, 1),
        "fleet_speedup": round(fleet_rps / single_rps, 2),
        "fleet_replicas": replicas,
        "fleet_requests": n_requests,
        "fleet_failovers": snap_f.get("fleet_failovers", 0),
        "fleet_failover_unresolved": unresolved,
        "fleet_failover_served": snap_f.get("serve_requests_served", 0),
        "fleet_shed_requests": shed_total,
        "fleet_shed_p0": shed_p0,
        "fleet_shed_p1": shed_p1,
        "fleet_shed_lowest_only": bool(shed_total > 0 and shed_p1 == 0),
        "fleet_shed_evictions": snap_s["counters"].get(
            "shed_evictions", 0),
        "fleet_shed_p95_ms": round(p95_hi, 3),
        "fleet_durable_preempted": bool(preempted),
        "fleet_durable_resumed": int(resumed),
        "fleet_durable_resume_bitexact": got_hash == ref_hash,
    }


def _parallelism_spin(q, iters: int = 20_000_000) -> None:
    """Child body for `_measure_host_parallelism` — module-level so the
    spawn start method can pickle it (fork under a live multithreaded
    JAX runtime is deadlock-prone)."""
    t0 = time.perf_counter()
    x = 0
    for i in range(iters):
        x += i
    q.put(time.perf_counter() - t0)


def _measure_host_parallelism(nproc: int = 2) -> float:
    """The host's REAL parallel capacity for `nproc` busy processes:
    wall-clock speedup of `nproc` concurrent pure-CPU spin loops over
    one. On dedicated hardware this is ~min(nproc, cores); on the
    shared/quota'd VMs CI runs on it is routinely far below nproc even
    when `os.cpu_count()` claims enough cores (this box reports 2 cores
    but delivers ~1.35x) — so the fleet sweep normalizes its scaling
    efficiency against THIS measured ceiling, not the advertised core
    count. Same honesty contract as the PR-11 thread-fleet numbers:
    report what the host can do, never gate on what it can't."""
    import multiprocessing as mp
    ctx = mp.get_context("spawn")

    q = ctx.Queue()
    p = ctx.Process(target=_parallelism_spin, args=(q,))
    p.start()
    p.join()
    solo = q.get()
    ps = [ctx.Process(target=_parallelism_spin, args=(q,))
          for _ in range(nproc)]
    t0 = time.perf_counter()
    for p in ps:
        p.start()
    for p in ps:
        p.join()
    duo_wall = time.perf_counter() - t0
    for _ in range(nproc):
        q.get()
    return max(1.0, nproc * solo / duo_wall)


def _measure_proc_fleet(max_batch: int = 8,
                        n_requests: Optional[int] = None):
    """The PR-18 process-fleet sweep (docs/SERVING.md §process-fleet):
    a closed-loop trajectory-sampling stream through
    `ServeFleet(process=True)` — every replica its own interpreter
    behind the serve/ipc.py boundary — swept over replicas ∈ {1, 2, 4}.
    Shots-mode requests are the fair probe for the boundary: per
    request the worker burns real compute while only a key and a small
    sample block cross the pipe, so the sweep measures process-parallel
    serving, not pickle bandwidth (a state-plane stream at this size is
    IPC-dominated and would misprice ANY multi-process design).

      * SCALING — req/s per replica count plus the 4-vs-1 speedup and
        the efficiency normalized to the MEASURED host-parallelism
        ceiling (`_measure_host_parallelism`), not os.cpu_count():
        thread replicas priced BELOW 1x on this path (the PR-11
        measurement that motivated the process boundary), and a
        quota'd CI host prices multi-process scaling below its
        advertised cores — both denominators are reported so the
        trajectory file carries the honest context.
      * LATENCY — per-request e2e p50/p99 at the widest sweep point,
        stamped via done-callbacks so result-collection order can't
        skew the sample.
      * KILL RECOVERY — the 2-replica stream re-run with one worker
        SIGKILLed (kill -9, no goodbye frame) after ~1/3 of results
        have landed: the proxy's heartbeat watchdog must respawn and
        resubmit so ZERO requests are lost (fleet_proc_kill_lost == 0
        is the scripts/check_fleet_golden.py gate) and the only damage
        is a p99 spike (fleet_proc_kill_p99_delta_ms reports it)."""
    import signal as _signal

    from quest_tpu.serve import ServeFleet, metrics, warmup

    platform = jax.devices()[0].platform
    n = 20 if platform == "tpu" else 9
    shots = 256
    if n_requests is None:
        n_requests = 192 if platform == "tpu" else 48
    cores = os.cpu_count() or 1
    host_par = _measure_host_parallelism(2)
    _log(f"host parallelism: {host_par:.2f}x over 2 processes "
         f"({cores} advertised cores)")
    circ = _build_circuit(n)
    circ.depolarising(0, 0.01)     # a channel: trajectories must branch

    def stream(fleet, kill_at: Optional[int] = None):
        """One closed-loop pass; returns (req/s, sorted latencies_s,
        lost). `kill_at` SIGKILLs the first replica's worker once that
        many results have landed."""
        done_t = [None] * n_requests
        t0 = time.perf_counter()
        futs = []
        for i in range(n_requests):
            f = fleet.submit(circ, shots=shots, key=jax.random.key(i))
            f.add_done_callback(
                lambda f, i=i: done_t.__setitem__(
                    i, time.perf_counter()))
            futs.append((time.perf_counter(), f))
        if kill_at is not None:
            while sum(t is not None for t in done_t) < kill_at:
                time.sleep(0.005)
            os.kill(fleet._engines[0].worker_pid(), _signal.SIGKILL)
        lost = 0
        for _, f in futs:
            try:
                f.result(timeout=600)
            except Exception:
                lost += 1
        rps = n_requests / (time.perf_counter() - t0)
        lats = sorted(done_t[i] - futs[i][0]
                      for i in range(n_requests) if done_t[i] is not None)
        return rps, lats, lost

    def pctl(lats, q):
        return 1e3 * lats[min(len(lats) - 1,
                              int(round(q * (len(lats) - 1))))]

    rps_by_r = {}
    p50 = p99 = base2_p99 = 0.0
    for r in (1, 2, 4):
        with ServeFleet(replicas=r, process=True, max_wait_ms=2,
                        max_batch=max_batch,
                        registry=metrics.Registry()) as fleet:
            warmup(fleet, [circ])
            stream(fleet)                    # warm pass pays compiles
            rps, lats, _ = stream(fleet)
        rps_by_r[r] = rps
        if r == 2:
            base2_p99 = pctl(lats, 0.99)
        if r == 4:
            p50, p99 = pctl(lats, 0.50), pctl(lats, 0.99)
        _log(f"proc fleet x{r}: {rps:.1f} req/s")

    with ServeFleet(replicas=2, process=True, max_wait_ms=2,
                    max_batch=max_batch,
                    registry=metrics.Registry()) as fleet:
        warmup(fleet, [circ])
        stream(fleet)
        _, kill_lats, kill_lost = stream(fleet, kill_at=n_requests // 3)
    kill_p99 = pctl(kill_lats, 0.99)
    _log(f"proc fleet kill: p99 {kill_p99:.1f} ms vs {base2_p99:.1f} ms "
         f"baseline, {kill_lost} lost")

    speedup = rps_by_r[4] / rps_by_r[1]
    return {
        "fleet_proc_metric": (f"process fleet req/s @ {n}q "
                              f"{shots}-shot x{{1,2,4}} replicas "
                              f"({platform}, {cores} cores)"),
        "fleet_proc_unit": "req/s",
        "fleet_proc_requests": n_requests,
        "fleet_proc_cores": cores,
        "fleet_proc_host_parallelism": round(host_par, 2),
        "fleet_proc_rps_1": round(rps_by_r[1], 1),
        "fleet_proc_rps_2": round(rps_by_r[2], 1),
        "fleet_proc_rps_4": round(rps_by_r[4], 1),
        "fleet_proc_speedup_4": round(speedup, 2),
        "fleet_proc_efficiency": round(
            speedup / min(4.0, max(host_par, 1.0)), 2),
        "fleet_proc_p50_ms": round(p50, 3),
        "fleet_proc_p99_ms": round(p99, 3),
        "fleet_proc_kill_p99_ms": round(kill_p99, 3),
        "fleet_proc_kill_p99_delta_ms": round(kill_p99 - base2_p99, 3),
        "fleet_proc_kill_lost": kill_lost,
    }


def fleet_main():
    """`python bench.py fleet` — the multi-replica fleet scenario alone,
    one JSON line of fleet_* keys (docs/SERVING.md §fleet), plus the
    PR-18 process-fleet replica sweep (§process-fleet)."""
    rec = _measure_fleet()
    if jax.default_backend() != "tpu":
        # process replicas cannot share the chip this process holds
        # (ServeFleet refuses them on a TPU backend; ROADMAP R1)
        rec.update(_measure_proc_fleet())
    print(json.dumps(rec))
    if not (rec["fleet_failover_unresolved"] == 0
            and rec["fleet_shed_lowest_only"]
            and rec["fleet_durable_resume_bitexact"]
            and rec.get("fleet_proc_kill_lost", 0) == 0):
        raise SystemExit(1)


def _build_tfim_sum(n: int):
    """30q-class TFIM Hamiltonian: n ring ZZ couplings + n transverse X
    fields (~2n terms) — the canonical variational/annealing energy
    shape. The grouped plan is 2 sweeps: ZZ is all-diagonal (one
    |amp|^2 pass), the n single-bit X masks co-ride one off-diagonal
    pass (docs/EXPECTATION.md)."""
    rows = []
    for i in range(n):
        r = [0] * n
        r[i] = 3
        r[(i + 1) % n] = 3
        rows.append(r)
    for i in range(n):
        r = [0] * n
        r[i] = 1
        rows.append(r)
    coeffs = np.concatenate([np.full(n, -1.0), np.full(n, -0.7)])
    return np.asarray(rows), coeffs


def _build_random_support_sum(n: int, terms: int = 100, families: int = 8,
                              seed: int = 42):
    """~100-term random-support sum in the shape of a tapered molecular
    Hamiltonian: a diagonal block (random Z supports — 40% of terms)
    plus off-diagonal terms whose X/Y content falls into `families`
    random interaction supports, each dressed with random Z factors
    elsewhere (Z dressing never changes the flip mask). Commuting-
    family structure like this is what real electronic-structure sums
    look like after qubit tapering — and it is exactly what the
    grouped planner exploits: ~1 + families mask groups instead of
    `terms` per-term passes."""
    rng = np.random.default_rng(seed)
    n_diag = int(terms * 0.4)
    rows = []
    for _ in range(n_diag):
        r = np.zeros(n, dtype=np.int32)
        sup = rng.choice(n, size=rng.integers(1, 4), replace=False)
        r[sup] = 3
        rows.append(r)
    fams = [rng.choice(n, size=rng.integers(1, 4), replace=False)
            for _ in range(families)]
    for i in range(terms - n_diag):
        r = np.zeros(n, dtype=np.int32)
        fam = fams[i % families]
        r[fam] = rng.integers(1, 3, size=len(fam))      # X or Y
        rest = [q for q in range(n) if q not in fam]
        r[rng.choice(rest, size=2, replace=False)] = 3  # Z dressing
        rows.append(r)
    return np.stack(rows), rng.standard_normal(terms)


def _time_expec(q, codes, coeffs, reps: int):
    """(seconds/call, compile_s) of calc_expec_pauli_sum, warmed."""
    from quest_tpu import calculations as C
    t0 = time.perf_counter()
    C.calc_expec_pauli_sum(q, codes, coeffs)
    compile_s = time.perf_counter() - t0
    jax.block_until_ready(q.amps)
    t0 = time.perf_counter()
    for _ in range(reps):
        C.calc_expec_pauli_sum(q, codes, coeffs)
    return (time.perf_counter() - t0) / reps, compile_s


def _measure_expec(reps: int = 10):
    """The `bench.py expec` scenario (docs/EXPECTATION.md): terms/s of
    the grouped sweep-fused Pauli-sum engine vs the per-term baseline
    (QUEST_EXPEC_FUSION=0 — the reference's clone+apply+inner-product
    pass structure, compiled into one program) on a TFIM-class
    Hamiltonian and a ~100-term random-support sum. The baseline runs
    the FULL term count (a term subset would flatter it: the 100-term
    per-term program thrashes where a 20-term one stays cache-warm —
    measured 2.6 vs 7 ms/term on this host) at a reduced rep count.
    The 30q TFIM plan golden is asserted host-side whatever size the
    measurement ladder lands on."""
    from quest_tpu.ops import expec as E

    on_tpu = jax.devices()[0].platform == "tpu"
    sizes = (30, 26) if on_tpu else (20, 16)
    tfim30 = E.plan_stats(_build_tfim_sum(30)[0], 30)
    for n in sizes:
        try:
            codes, coeffs = _build_random_support_sum(n)
            stats = E.plan_stats(codes, n)
            M = stats["terms"]
            q = qt_plus_state(n)
            dt_f, compile_s = _time_expec(q, codes, coeffs, reps)
            _log(f"expec n={n}: fused {M / dt_f:.0f} terms/s "
                 f"({dt_f * 1e3:.1f} ms/eval, "
                 f"{stats['expec_hbm_sweeps']} sweeps for {M} terms; "
                 f"compile {compile_s:.1f}s)")
            prior = os.environ.get("QUEST_EXPEC_FUSION")
            os.environ["QUEST_EXPEC_FUSION"] = "0"
            try:
                dt_b, base_compile_s = _time_expec(
                    q, codes, coeffs, max(2, reps // 3))
            finally:
                if prior is None:
                    del os.environ["QUEST_EXPEC_FUSION"]
                else:
                    os.environ["QUEST_EXPEC_FUSION"] = prior
            base_rate = M / dt_b
            _log(f"expec n={n}: baseline {base_rate:.0f} terms/s "
                 f"({dt_b * 1e3:.1f} ms/eval, "
                 f"{stats['baseline_hbm_sweeps']} passes; compile "
                 f"{base_compile_s:.1f}s) -> speedup "
                 f"{dt_b / dt_f:.1f}x")

            tfim_codes, tfim_coeffs = _build_tfim_sum(n)
            tfim_stats = E.plan_stats(tfim_codes, n)
            dt_t, tfim_compile_s = _time_expec(q, tfim_codes, tfim_coeffs,
                                               reps)
            _log(f"expec n={n} TFIM ({tfim_stats['terms']} terms): "
                 f"{tfim_stats['terms'] / dt_t:.0f} terms/s in "
                 f"{tfim_stats['expec_hbm_sweeps']} sweeps")
            return {
                "expec_metric": (f"Pauli-sum terms/sec @ {n}q statevec, "
                                 f"{M}-term random-support sum (grouped "
                                 f"fused engine)"),
                "expec_value": round(M / dt_f, 1),
                "expec_unit": "terms/sec",
                "expec_compile_s": round(compile_s, 1),
                "expec_terms": M,
                "expec_groups": stats["expec_groups"],
                "expec_hbm_sweeps": stats["expec_hbm_sweeps"],
                "expec_baseline_hbm_sweeps": stats["baseline_hbm_sweeps"],
                "expec_baseline_value": round(base_rate, 1),
                "expec_baseline_note": ("QUEST_EXPEC_FUSION=0: the "
                                        "legacy per-term pass "
                                        "structure, full term count"),
                "expec_speedup": round(dt_b / dt_f, 2),
                "expec_tfim_terms": tfim_stats["terms"],
                "expec_tfim_value": round(tfim_stats["terms"] / dt_t, 1),
                "expec_tfim_hbm_sweeps": tfim_stats["expec_hbm_sweeps"],
                "expec_tfim30_hbm_sweeps": tfim30["expec_hbm_sweeps"],
                "expec_tfim30_baseline_hbm_sweeps":
                    tfim30["baseline_hbm_sweeps"],
            }
        except Exception:
            _log(f"expec n={n} failed; trying next size down:\n"
                 f"{traceback.format_exc()}")
    return None


def _build_durable_circuit(n: int, layers: int = 16, seed: int = 11):
    """The durable scenario's workload: rotation layers split by random
    2q unitaries on far-apart qubits. The cross-band unitaries are XLA
    passthrough launches, so the banded durable plan has ~4 genuine cut
    points per layer — a plain rotation block at one band would fuse
    into a single launch and leave nothing to checkpoint between. One
    home, shared with scripts/check_durable_golden.py so the gate
    measures the same circuit the bench does."""
    from quest_tpu.circuit import Circuit

    rng = np.random.default_rng(seed)
    c = Circuit(n)
    for layer in range(layers):
        for q in range(n):
            c.rx(q, float(rng.uniform(0, 2 * np.pi)))
            c.ry(q, float(rng.uniform(0, 2 * np.pi)))
        m = rng.normal(size=(4, 4)) + 1j * rng.normal(size=(4, 4))
        u, _ = np.linalg.qr(m)
        c.gate(u, (layer % (n // 2), n - 1 - (layer % (n // 2))))
    return c


def _build_elastic_circuit(n: int, layers: int = 3, seed: int = 7):
    """The elastic-resume pins' workload (docs/RESILIENCE.md §elastic):
    a circuit whose ARITHMETIC is mesh-portable, so an elastic resume
    on a different device/host count can be pinned BIT-identical to an
    uninterrupted native run on the target mesh (general circuits
    resume eps-close: band contractions reassociate per chunk shape).
    The portability rules, each verified empirically on this backend
    (tests/test_elastic.py):

      * rotations (rx/ry) only on qubits < 7, each isolated in its OWN
        band operator by a cross-band cz blocker — a single embedded 1q
        gate contracts with <= 2 products per output component, which
        every chunk shape with local_n >= 8 reduces identically (a
        merged multi-qubit operator or a >= 4-product complex row
        reassociates per shape);
      * amplitude reaches qubits >= 7 only through PERMUTATION gates
        (CNOT — moves are exact on the band path AND the sharded
        pair-exchange path, which otherwise disagree on fma usage);
      * phases via cz only (exact -1 multiplies everywhere).

    Run the pins under QUEST_SCHEDULE=0: the scheduler's diagonal
    pooling hoists the blockers away and re-merges the rotations. One
    home, shared by tests/test_elastic.py, tests/_elastic_worker.py and
    scripts/check_elastic_golden.py."""
    from quest_tpu.circuit import Circuit

    if n < 8:
        # the portability contract itself needs local_n >= 8 on every
        # mesh, so a sub-8q register can never be in scope — and the
        # high-qubit transfer below would index control h-7 < 0
        raise ValueError(
            f"the mesh-portable elastic circuit needs n >= 8 (its "
            f"arithmetic-portability rules require local_n >= 8 on "
            f"every tested mesh), got {n}")
    rng = np.random.default_rng(seed)
    c = Circuit(n)
    for layer in range(layers):
        for q in range(7):
            c.cz(q, n - 1)
            ang = float(rng.uniform(0, 2 * np.pi))
            (c.rx if (layer + q) % 2 == 0 else c.ry)(q, ang)
        if layer == 0:
            for h in range(7, n):
                c.cnot(h - 7, h)
        for h in range(7, n):
            c.cz(h, (h + layer) % 7)
    return c


def _measure_durable(n: int = 18, layers: int = 16, every: int = 64,
                     reps: int = 3):
    """The `bench.py durable` scenario (docs/RESILIENCE.md §durable):
    run the durable executor over the banded engine with checkpointing
    every `every` steps, derive the checkpoint overhead from the
    executor's OWN `durable_checkpoint_s` histogram (per-cut sentinel +
    gather + atomic-write cost over the same run's wall time — one
    instrumented run, not a noisy wall-clock A/B difference), and prove
    one seeded preemption-at-a-boundary resumes to bit-identical
    amplitudes. Emits durable_* JSON keys; the golden gate holds the
    overhead fraction <= 10% of the sweep time
    (scripts/check_durable_golden.py)."""
    import hashlib
    import shutil
    import tempfile

    import quest_tpu as qt
    from quest_tpu.resilience import FaultPlan, faults, run_durable
    from quest_tpu.resilience.durable import _build_steps
    from quest_tpu.serve import metrics

    circ = _build_durable_circuit(n, layers)
    q0 = qt.init_debug_state(qt.create_qureg(n))
    steps, _info = _build_steps(circ, n, False, "banded", False, None)
    num_steps = len(steps)
    hist = metrics.REGISTRY.histogram("durable_checkpoint_s")
    td = tempfile.mkdtemp(prefix="quest-durable-bench-")
    try:
        def one(tag):
            c0, s0 = hist.count, hist.sum
            t0 = time.perf_counter()
            out = run_durable(circ, q0, os.path.join(td, tag),
                              every=every, engine="banded")
            _sync(out.amps)
            wall = time.perf_counter() - t0
            return wall, hist.sum - s0, hist.count - c0, out

        one("warm")                     # compile warm-up
        wall_s = float("inf")
        ckpt_s = 0.0
        ckpts = 0
        overhead = float("inf")
        out_ck = None
        for _ in range(reps):
            wall, csum, ccount, out_ck = one("ck")
            # best-of-reps PER REP: a transient disk spike in one rep's
            # save (or a GC pause in its sweep) should not define the
            # steady-state overhead
            frac = csum / max(wall - csum, 1e-9)
            if frac < overhead:
                overhead, wall_s, ckpt_s, ckpts = frac, wall, csum, ccount
        digest = hashlib.sha256(
            np.asarray(jax.device_get(out_ck.amps)).tobytes()
        ).hexdigest()

        # seeded preemption at a boundary, then resume: the final hash
        # must equal the uninterrupted run's
        d = os.path.join(td, "resume")
        # kill DERIVED from the cadence — halfway through the post-stamp
        # stretch — so it provably lands after the first checkpoint
        # whatever the planner makes of the circuit (num_steps//2 only
        # cleared `every` by numeric coincidence)
        kill_at = every + max(1, (num_steps - every) // 2)
        plan = FaultPlan().inject("durable.preempt",
                                  after_n=kill_at, times=1)
        preempted = False
        with faults.active(plan):
            try:
                run_durable(circ, q0, d, every=every, engine="banded")
            except faults.InjectedFault:
                preempted = True
        from quest_tpu import checkpoint as _ckpt
        # the kill must land AFTER a stamp, or the "resume" silently
        # degrades to a restart-from-op-0 and the gate verifies nothing
        # about checkpoint restore
        resumed_from_ckpt = bool(_ckpt.step_dirs(d))
        out_res = run_durable(circ, q0, d, every=every, engine="banded")
        resume_digest = hashlib.sha256(
            np.asarray(jax.device_get(out_res.amps)).tobytes()
        ).hexdigest()

        return {
            "metric": f"durable checkpoint overhead @ {n}q banded "
                      f"(every={every})",
            "value": round(overhead, 4),
            "unit": "fraction of sweep time",
            "durable_steps": num_steps,
            "durable_every": every,
            "durable_checkpoints": ckpts,
            "durable_overhead_frac": round(overhead, 4),
            "durable_checkpoint_ms": round(
                1e3 * ckpt_s / max(ckpts, 1), 3),
            "durable_step_ms": round(
                1e3 * (wall_s - ckpt_s) / num_steps, 3),
            "durable_wall_s": round(wall_s, 4),
            "durable_preempted": preempted,
            "durable_resumed_from_checkpoint": resumed_from_ckpt,
            "durable_resume_bitexact": resume_digest == digest,
            "durable_hash": digest[:16],
        }
    finally:
        shutil.rmtree(td, ignore_errors=True)


def durable_main():
    """`python bench.py durable` — the durable-executor scenario alone,
    one JSON line of durable_* keys (docs/RESILIENCE.md §durable)."""
    rec = _measure_durable()
    print(json.dumps(rec))
    if not rec["durable_resume_bitexact"]:
        raise SystemExit(1)


def qt_plus_state(n: int):
    """|+>^n register (every Pauli string has a nonzero expectation
    there — the timing is structure-independent anyway)."""
    import quest_tpu as qt
    return qt.init_plus_state(qt.create_qureg(n, dtype=np.complex64))


# docs/EVOLUTION.md §energy drift: an order-2 TFIM quench at dt=0.05
# conserves <H> to O(dt^2) per unit coupling — the bench/golden bound is
# the documented ceiling per term, generous against f32 reduction noise
TROTTER_DT = 0.05
TROTTER_DRIFT_PER_TERM = 2e-3


def _measure_evolution(steps: int = 50, reps: int = 3):
    """The `bench.py evolution` scenario (docs/EVOLUTION.md): steps/s of
    a TFIM quench (order-2 Trotter, d>=50 steps) through the pooled
    fused emission vs the honest per-term baseline
    (QUEST_TROTTER_FUSION=0 — the legacy per-term eager dispatch, one
    flip-form pass per term application), plus the per-step energy
    drift of the fused quench against the documented bound. The 30q
    TFIM plan golden (trot_hbm_sweeps_per_step <= 3 vs >= 15 per-term)
    is asserted host-side whatever size the measurement ladder lands
    on (scripts/check_evolution_golden.py holds the gate).

    The CPU ladder sits at 16q, not the 20q the expec scenario uses:
    off-chip the fused step is bound by the banded engine's dense
    128-wide band contractions (free on the MXU — the design target —
    but ~5x the per-amp flops of the baseline's elementwise flip-form
    passes), which at bandwidth-bound sizes masks the
    dispatch-aggregation win the scenario exists to measure; at 16q
    the comparison reflects passes and dispatches, the thing the 30q
    sweep golden models (measured on this host with the interleaved
    best-of A/B: 4-5x @ 16q, falling toward ~1.3x by 20q — the chip
    point is the TPU run)."""
    from quest_tpu import evolution as EV
    from quest_tpu.ops import expec as E

    on_tpu = jax.devices()[0].platform == "tpu"
    sizes = (30, 26) if on_tpu else (16, 14)
    t30 = EV.trotter_plan_stats(
        E.PauliSum.of(*_build_tfim_sum(30), 30), TROTTER_DT, order=2,
        steps=steps)
    for n in sizes:
        try:
            spec = E.PauliSum.of(*_build_tfim_sum(n), n)
            stats = EV.trotter_plan_stats(spec, TROTTER_DT, order=2,
                                          steps=steps)
            q0 = qt_plus_state(n)

            def quench(m):
                # no observables in the timed legs: drift is measured
                # by a dedicated energy_every=5 run below, and the
                # per-term baseline leg records nothing either
                t0 = time.perf_counter()
                res = EV.run_evolution(
                    spec, TROTTER_DT, m, state=q0, order=2,
                    observables=[])
                _sync(res.state.amps)
                return time.perf_counter() - t0, res

            def legacy(m):
                prior = os.environ.get("QUEST_TROTTER_FUSION")
                os.environ["QUEST_TROTTER_FUSION"] = "0"
                try:
                    return quench(m)
                finally:
                    if prior is None:
                        del os.environ["QUEST_TROTTER_FUSION"]
                    else:
                        os.environ["QUEST_TROTTER_FUSION"] = prior

            compile_s, _ = quench(1)           # warm the step program
            quench(steps)                      # warm the full program
            legacy(1)                          # warm the eager workers
            # per-step drift at the golden gate's 5-step cadence,
            # UNTIMED: the timed legs dispatch one chunk, whose
            # endpoint energies would reduce the documented per-step
            # contract to an |E_final - E_0| check that a mid-run
            # excursion returning to E_0 slips past
            res_d = EV.run_evolution(
                spec, TROTTER_DT, steps, state=q0, order=2,
                energy_every=5, observables=[spec])
            drift = float(np.abs(res_d.energies[:, 0]
                                 - res_d.energies[0, 0]).max())
            base_steps = max(4, steps // 5)
            dt_f = dt_b = float("inf")
            # INTERLEAVED best-of A/B: this host's throughput swings
            # run-to-run far more than either leg's own noise, so
            # timing all fused reps then all baseline reps lets one
            # load swing bias a whole leg — alternating legs hands
            # both sides the same weather
            # record=False on BOTH timed legs: drift comes from the
            # dedicated run above, and the baseline leg records
            # nothing — a fused leg paying live expec reductions would
            # understate its own advantage
            for _ in range(reps):
                dt_f = min(dt_f, quench(steps)[0])
                dt_b = min(dt_b, legacy(base_steps)[0])
            base_rate = base_steps / dt_b
            _log(f"evolution n={n}: fused {steps / dt_f:.1f} steps/s "
                 f"({stats['hbm_sweeps_per_step']:.0f} sweeps/step, "
                 f"energy drift {drift:.2e}; compile {compile_s:.1f}s)")
            _log(f"evolution n={n}: per-term baseline "
                 f"{base_rate:.1f} steps/s "
                 f"({stats['baseline_hbm_sweeps_per_step']} passes/step) "
                 f"-> speedup {(steps / dt_f) / base_rate:.1f}x")

            drift_bound = TROTTER_DRIFT_PER_TERM * stats["terms"]
            return {
                "trot_metric": (f"order-2 Trotter steps/sec @ {n}q TFIM "
                                f"quench, d={steps} (pooled fused "
                                f"emission)"),
                "trot_value": round(steps / dt_f, 2),
                "trot_unit": "steps/sec",
                "trot_steps_per_s": round(steps / dt_f, 2),
                "trot_steps": steps,
                "trot_dt": TROTTER_DT,
                "trot_compile_s": round(compile_s, 1),
                "trot_terms": stats["terms"],
                "trot_frames": stats["frames"],
                "trot_diag_groups": stats["diag_groups"],
                "trot_hbm_sweeps_per_step": stats["hbm_sweeps_per_step"],
                "trot_baseline_hbm_sweeps_per_step":
                    stats["baseline_hbm_sweeps_per_step"],
                "trot_energy_drift": drift,
                "trot_energy_drift_bound": drift_bound,
                "trot_energy_drift_ok": bool(drift <= drift_bound),
                "trot_baseline_steps_per_s": round(base_rate, 2),
                "trot_baseline_note": ("QUEST_TROTTER_FUSION=0: legacy "
                                       "per-term eager dispatch, one "
                                       "flip-form pass per term "
                                       "application"),
                "trot_speedup": round((steps / dt_f) / base_rate, 2),
                "trot30_hbm_sweeps_per_step":
                    t30["hbm_sweeps_per_step"],
                "trot30_baseline_hbm_sweeps_per_step":
                    t30["baseline_hbm_sweeps_per_step"],
            }
        except Exception:
            _log(f"evolution n={n} failed; trying next size down:\n"
                 f"{traceback.format_exc()}")
    return None


def evolution_main():
    """`python bench.py evolution` — the Trotter-evolution scenario
    alone, one JSON line of trot_* keys (docs/EVOLUTION.md). Exits
    nonzero when the 30q plan golden or the energy-drift contract
    breaks (the measured speedup is reported, not gated — the CPU-host
    gate lives in scripts/check_evolution_golden.py)."""
    rec = _measure_evolution()
    if rec is None:
        raise SystemExit(1)
    print(json.dumps(rec))
    if not (rec["trot30_hbm_sweeps_per_step"] <= 3
            and rec["trot30_baseline_hbm_sweeps_per_step"]
            >= 5 * rec["trot30_hbm_sweeps_per_step"]
            and rec["trot_energy_drift_ok"]):
        raise SystemExit(1)


def _measure_autotune(n: int, reps: int = 3):
    """The plan-autotuner scenario (docs/PLANNING.md): chooser-vs-
    forced-engine throughput spread on the headline circuit, the plan
    search's wall time, and the persistent cache's cold/warm hit
    profile — the numbers that justify (or indict) letting the priced
    chooser route dispatch. Runs in a throwaway plan-cache directory so
    the cold/warm split is THIS process's, not an earlier run's."""
    import tempfile

    from quest_tpu import plan as P
    from quest_tpu.ops import pallas_band as PB
    from quest_tpu.state import basis_planes

    c = _build_circuit(n)
    rec = {"plan_metric": f"plan autotune spread ({n}q headline)",
           "plan_unit": "x (worst forced engine / chosen)"}
    with tempfile.TemporaryDirectory() as d:
        old = os.environ.get("QUEST_PLAN_CACHE_DIR")
        os.environ["QUEST_PLAN_CACHE_DIR"] = d
        P.reset_cache_stats()
        try:
            t0 = time.perf_counter()
            plan = P.autotune(c)
            rec["plan_search_ms"] = round(
                (time.perf_counter() - t0) * 1e3, 2)
            cold = P.cache_stats()
            t0 = time.perf_counter()
            P.autotune(c)
            rec["plan_warm_ms"] = round(
                (time.perf_counter() - t0) * 1e3, 2)
            warm = P.cache_stats()
        finally:
            if old is None:
                os.environ.pop("QUEST_PLAN_CACHE_DIR", None)
            else:
                os.environ["QUEST_PLAN_CACHE_DIR"] = old
    rec.update({
        "plan_engine": plan.engine,
        "plan_incumbent": plan.incumbent,
        "plan_candidates": len(plan.candidates),
        "plan_cache_cold": cold["searches"],
        "plan_cache_warm": warm["hits"],
    })

    def time_engine(fn):
        amps = basis_planes(0, n=n, rdt=np.float32)
        amps = fn(amps)                       # compile + warm
        _sync(amps)
        t0 = time.perf_counter()
        for _ in range(reps):
            amps = fn(amps)
        _sync(amps)
        return (time.perf_counter() - t0) / reps * 1e3

    forced = {"pergate": c.compiled(n, False, donate=True),
              "banded": c.compiled_banded(n, False, donate=True)}
    if PB.usable(n):
        fused = c.compiled_fused(n, False, donate=True)
        # the fused program runs on the banked (2, rows, LANES) layout
        forced["fused"] = (lambda a: fused(
            a.reshape(2, -1, PB.LANES)).reshape(2, -1))
    ms = {}
    for name, fn in forced.items():
        try:
            ms[name] = time_engine(fn)
        except Exception:
            _log(f"autotune scenario: forced {name} failed\n"
                 f"{traceback.format_exc()}")
    for name, v in ms.items():
        rec[f"plan_forced_{name}_ms"] = round(v, 3)
    chosen_ms = ms.get(plan.engine)
    if chosen_ms is not None and ms:
        rec["plan_chosen_ms"] = round(chosen_ms, 3)
        rec["plan_value"] = round(max(ms.values()) / chosen_ms, 2)
    return rec


def autotune_main():
    """`python bench.py autotune [n]` — the plan-autotuner scenario
    alone, one JSON line of plan_* keys (docs/PLANNING.md). Exits
    nonzero when the chosen engine is measurably slower than the best
    forced engine by more than 20% — the chooser must not regress the
    circuits it prices."""
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 16
    rec = _measure_autotune(n)
    print(json.dumps(rec))
    unknown = set(rec) - HEADLINE_JSON_KEYS
    assert not unknown, (
        f"autotune scenario emitted unregistered key(s) "
        f"{sorted(unknown)}: add them to HEADLINE_JSON_KEYS")
    chosen = rec.get("plan_chosen_ms")
    forced = [v for k, v in rec.items()
              if k.startswith("plan_forced_") and v is not None]
    if chosen is not None and forced and chosen > 1.2 * min(forced):
        _log(f"REGRESSION: chosen engine {rec['plan_engine']} at "
             f"{chosen} ms/app is >20% above the best forced engine "
             f"({min(forced)} ms)")
        raise SystemExit(1)


# ---------------------------------------------------------------------------
# the workload gallery (`bench.py gallery`, docs/TRANSPILE.md)
# ---------------------------------------------------------------------------

#: the gallery's circuit classes, in HEADLINE_JSON_KEYS order
GALLERY_CLASSES = ("qft", "qaoa", "rcs", "adder", "ghz")


def _qasm_cphase_lines(theta: float, a: int, b: int):
    """cu1(theta) in the rebased exporter form rz/cx/rz/cx/rz — the
    5-op chain foreign corpora actually ship (Q-GEAR's observation),
    which resynth2q collapses back to one poolable diagonal."""
    return [f"rz({theta / 2}) q[{a}];", f"cx q[{a}],q[{b}];",
            f"rz({-theta / 2}) q[{b}];", f"cx q[{a}],q[{b}];",
            f"rz({theta / 2}) q[{b}];"]


def _qasm_ccx_lines(a: int, b: int, c: int):
    """ccx in the standard Clifford+T decomposition (15 ops) — the form
    a rebased adder netlist arrives in."""
    return [f"h q[{c}];", f"cx q[{b}],q[{c}];", f"tdg q[{c}];",
            f"cx q[{a}],q[{c}];", f"t q[{c}];", f"cx q[{b}],q[{c}];",
            f"tdg q[{c}];", f"cx q[{a}],q[{c}];", f"t q[{b}];",
            f"t q[{c}];", f"h q[{c}];", f"cx q[{a}],q[{b}];",
            f"t q[{a}];", f"tdg q[{b}];", f"cx q[{a}],q[{b}];"]


def build_gallery_qasm(n: int, depth: int = 4, seed: int = 20):
    """The in-repo QASMBench-style corpus (ROADMAP item 5): five
    circuit classes as OpenQASM-2 text in the rebased 1q+CX basis a
    foreign exporter emits — NOT the native builder calls — so the
    import path (and its QUEST_TRANSPILE routing) is exactly what a
    real corpus would exercise. Returns {class: qasm_text}."""
    rng = np.random.default_rng(seed)
    head = ["OPENQASM 2.0;", 'include "qelib1.inc";',
            f"qreg q[{n}];", f"creg c[{n}];"]
    out = {}

    # QFT: h + decomposed controlled-phase ladder + swaps as 3 cx
    lines = list(head)
    for i in range(n):
        lines.append(f"h q[{i}];")
        for j in range(i + 1, n):
            lines += _qasm_cphase_lines(np.pi / (1 << (j - i)), j, i)
    for i in range(n // 2):
        a, b = i, n - 1 - i
        lines += [f"cx q[{a}],q[{b}];", f"cx q[{b}],q[{a}];",
                  f"cx q[{a}],q[{b}];"]
    out["qft"] = "\n".join(lines)

    # QAOA (ring MaxCut): cx.rz.cx cost terms + h.rz.h mixers
    lines = list(head)
    for i in range(n):
        lines.append(f"h q[{i}];")
    for l in range(depth):
        g, b = 0.4 + 0.1 * l, 0.3 + 0.05 * l
        for i in range(n):
            j = (i + 1) % n
            lines += [f"cx q[{i}],q[{j}];", f"rz({2 * g}) q[{j}];",
                      f"cx q[{i}],q[{j}];"]
        for i in range(n):
            lines += [f"h q[{i}];", f"rz({2 * b}) q[{i}];",
                      f"h q[{i}];"]
    out["qaoa"] = "\n".join(lines)

    # supremacy-style RCS: rz.ry.rz euler triples + cz brickwork
    lines = list(head)
    for l in range(depth):
        for i in range(n):
            a1, a2, a3 = rng.uniform(-np.pi, np.pi, 3)
            lines += [f"rz({a1}) q[{i}];", f"ry({a2}) q[{i}];",
                      f"rz({a3}) q[{i}];"]
        for i in range(l % 2, n - 1, 2):
            lines.append(f"cz q[{i}],q[{i + 1}];")
    out["rcs"] = "\n".join(lines)

    # Cuccaro ripple-carry adder: MAJ/UMA blocks with the toffolis in
    # their 15-op Clifford+T form (qubit layout: c, a0, b0, a1, b1, ...)
    w = (n - 1) // 2                       # operand width
    lines = list(head)
    for i in range(n):
        if rng.uniform() < 0.5:
            lines.append(f"x q[{i}];")     # seeded input operands
    prev = 0
    maj, uma = [], []
    for k in range(w):
        a, b = 1 + 2 * k, 2 + 2 * k
        maj += [f"cx q[{a}],q[{b}];", f"cx q[{a}],q[{prev}];"]
        maj += _qasm_ccx_lines(prev, b, a)
        uma = (_qasm_ccx_lines(prev, b, a)
               + [f"cx q[{a}],q[{prev}];", f"cx q[{prev}],q[{b}];"]
               + uma)
        prev = a
    out["adder"] = "\n".join(lines + maj + uma)

    # GHZ with a mid-circuit measurement splitting the stream in two
    lines = list(head)
    lines.append("h q[0];")
    for i in range(n - 1):
        lines.append(f"cx q[{i}],q[{i + 1}];")
    lines.append("measure q[0] -> c[0];")
    for i in range(n - 1, 0, -1):
        lines.append(f"cx q[{i - 1}],q[{i}];")
    lines.append("h q[0];")
    out["ghz"] = "\n".join(lines)
    return out


def _gallery_circuits(n: int, transpile):
    """Import the corpus with the transpiler forced on/off (the same
    routing a real QASM workload gets from QUEST_TRANSPILE)."""
    from quest_tpu.circuit import Circuit
    return {cls: Circuit.from_qasm(text, transpile=transpile)
            for cls, text in build_gallery_qasm(n).items()}


def _time_serve_apply(circ, n: int, reps: int):
    """Requests/s for one circuit class through a warmed ServeEngine —
    the per-class throughput column of the gallery table."""
    from quest_tpu.serve import ServeEngine, metrics, warmup
    rng = np.random.default_rng(3)
    states = rng.standard_normal((reps, 2, 1 << n)).astype(np.float32)
    states /= np.sqrt((states ** 2).sum(axis=(1, 2), keepdims=True))
    with ServeEngine(max_wait_ms=5.0, max_batch=8,
                     registry=metrics.Registry()) as eng:
        warmup(eng, [circ])
        eng.submit(circ, state=states[0]).result(timeout=600)
        t0 = time.perf_counter()
        futs = [eng.submit(circ, state=s) for s in states]
        for f in futs:
            f.result(timeout=600)
        return reps / (time.perf_counter() - t0)


def _time_measured(circ, n: int, reps: int):
    """Shots/s of a dynamic (mid-circuit-measurement) class through
    compiled_measured — serve's apply/trajectory paths both reject
    measure ops, so the GHZ column rides the dynamic-circuit engine."""
    import jax.numpy as jnp
    fn = circ.compiled_measured(n, False, donate=False)
    amps = jnp.zeros((2, 1 << n), dtype=jnp.float32).at[0, 0].set(1.0)
    out = fn(amps, jax.random.PRNGKey(0))
    _sync(out[0])
    t0 = time.perf_counter()
    for i in range(reps):
        out = fn(amps, jax.random.PRNGKey(i))
    _sync(out[0])
    return reps / (time.perf_counter() - t0)


def _measure_gallery(n: int, reps: int = 32):
    """The gallery table: per class, raw-vs-transpiled op counts,
    predicted HBM sweeps (fusion.plan_stats full_state_passes — the
    planner's own cost axis) and measured serve throughput, with the
    A/B keyed on QUEST_TRANSPILE auto vs 0. Wall-clock is reported
    per class whether it wins or not."""
    from quest_tpu import transpile as TR

    rec = {"gallery_metric":
           f"workload gallery ({n}q, transpile auto vs off)",
           "gallery_unit": "classes with >= 1.5x predicted-sweep win",
           "gallery_n": n}
    raw = _gallery_circuits(n, transpile=False)
    # auto = exactly what QUEST_TRANSPILE=auto ships to the engines
    old = os.environ.get("QUEST_TRANSPILE")
    os.environ["QUEST_TRANSPILE"] = "auto"
    try:
        auto = _gallery_circuits(n, transpile=None)
    finally:
        if old is None:
            os.environ.pop("QUEST_TRANSPILE", None)
        else:
            os.environ["QUEST_TRANSPILE"] = old
    wins = 0
    for cls in GALLERY_CLASSES:
        cr, ca = raw[cls], auto[cls]
        sweeps_r, _ = TR.stream_cost(cr)
        sweeps_a, _ = TR.stream_cost(ca)
        rec[f"gallery_{cls}_ops_raw"] = len(cr.ops)
        rec[f"gallery_{cls}_ops_auto"] = len(ca.ops)
        rec[f"gallery_{cls}_sweeps_raw"] = sweeps_r
        rec[f"gallery_{cls}_sweeps_auto"] = sweeps_a
        ratio = (round(sweeps_r / sweeps_a, 2)
                 if sweeps_r and sweeps_a else None)
        rec[f"gallery_{cls}_sweep_ratio"] = ratio
        if ratio is not None and ratio >= 1.5:
            wins += 1
        try:
            timer = (_time_measured if cls == "ghz"
                     else _time_serve_apply)
            rps_r = timer(cr, n, reps)
            rps_a = timer(ca, n, reps)
            rec[f"gallery_{cls}_rps_raw"] = round(rps_r, 1)
            rec[f"gallery_{cls}_rps_auto"] = round(rps_a, 1)
            rec[f"gallery_{cls}_speedup"] = round(rps_a / rps_r, 2)
        except Exception:
            _log(f"gallery: {cls} throughput pass failed\n"
                 f"{traceback.format_exc()}")
        _log(f"gallery {cls}: {len(cr.ops)} -> {len(ca.ops)} ops, "
             f"sweeps {sweeps_r} -> {sweeps_a} "
             f"(ratio {ratio}), speedup "
             f"{rec.get(f'gallery_{cls}_speedup')}")
    rec["gallery_value"] = wins
    return rec


def gallery_main():
    """`python bench.py gallery [n]` — the QASM workload gallery, one
    JSON line of gallery_* keys (docs/TRANSPILE.md). Exits nonzero
    when transpile auto wins < 1.5x predicted sweeps on fewer than 3
    of the 5 classes — the ISSUE-20 acceptance gate."""
    # off-chip the serve path must stay sub-kernel-tier (same split as
    # the serve scenario: CPU Pallas would need interpret mode)
    default_n = 16 if jax.devices()[0].platform == "tpu" else 9
    n = int(sys.argv[2]) if len(sys.argv) > 2 else default_n
    rec = _measure_gallery(n)
    print(json.dumps(rec))
    unknown = set(rec) - HEADLINE_JSON_KEYS
    assert not unknown, (
        f"gallery scenario emitted unregistered key(s) "
        f"{sorted(unknown)}: add them to HEADLINE_JSON_KEYS")
    if rec["gallery_value"] < 3:
        _log(f"REGRESSION: transpile auto delivers a >=1.5x predicted-"
             f"sweep win on only {rec['gallery_value']} of "
             f"{len(GALLERY_CLASSES)} gallery classes (need 3)")
        raise SystemExit(1)


def _build_vqe_ansatz(n: int, layers: int, seed: int = 5):
    """Hardware-efficient VQE ansatz for the training scenario: ry+rz
    rotation layers split by brickwork CNOTs — every rotation is one
    trainable parameter on the adjoint walk (2*layers*n of them)."""
    from quest_tpu.circuit import Circuit
    rng = np.random.default_rng(seed)
    c = Circuit(n)
    for _ in range(layers):
        for q in range(n):
            c.ry(q, float(rng.uniform(-np.pi, np.pi)))
        for q in range(0, n - 1, 2):
            c.cnot(q, q + 1)
        for q in range(n):
            c.rz(q, float(rng.uniform(-np.pi, np.pi)))
        for q in range(1, n - 1, 2):
            c.cnot(q, q + 1)
    return c


def _build_qaoa_circuit(n: int, layers: int, seed: int = 9):
    """Ring-MaxCut QAOA: |+>^n, then per layer a ZZ parity rotation on
    every ring edge (the cost unitary) and an rx mixer on every qubit —
    the multi-qubit-parity side of the adjoint walk's parameter
    families, where taped residuals are widest per parameter."""
    from quest_tpu.circuit import Circuit
    rng = np.random.default_rng(seed)
    c = Circuit(n)
    for q in range(n):
        c.h(q)
    for _ in range(layers):
        gamma = float(rng.uniform(0.1, np.pi))
        beta = float(rng.uniform(0.1, np.pi))
        for q in range(n):
            c.multi_rotate_z(tuple(sorted((q, (q + 1) % n))), gamma)
        for q in range(n):
            c.rx(q, beta)
    return c


def _time_grad_steps(fn, theta0, steps: int, lr: float = 0.05):
    """Wall-time `steps` optimizer steps (value_and_grad + SGD update)
    through an already-warmed grad program; returns (seconds, final
    theta) so legs can assert they did real work."""
    import jax.numpy as jnp
    th = jnp.asarray(theta0, jnp.float32)
    t0 = time.perf_counter()
    for _ in range(steps):
        _v, g = fn(th)
        th = th - lr * g
    _sync(th)
    return time.perf_counter() - t0, th


def _measure_training(reps: int = 3, steps: int = 5):
    """The `bench.py training` scenario (docs/AUTODIFF.md): optimizer
    steps/s of a VQE step (hardware-efficient ansatz, TFIM energy) and
    a QAOA step (ring MaxCut) under the adjoint engine vs the taped
    (jax.grad) baseline, interleaved best-of A/B legs (the PR-13 timing
    discipline), plus the capacity model's memory rows: adjoint peak
    (3 registers + masks, depth-independent) vs taped residuals
    ((P+2) registers), and the widest trainable width each engine fits
    under the modeled HBM. The CPU wall-clock ratio is reported
    honestly (~1.2-1.4x on this host — both engines are bandwidth-bound
    off-chip); the 3x+ claim is the capacity cliff: past the taped
    fit width only the adjoint engine trains at all
    (scripts/check_adjoint_golden.py gates the model)."""
    from quest_tpu import adjoint as AD
    from quest_tpu.ops import expec as E

    on_tpu = jax.devices()[0].platform == "tpu"
    sizes = (26, 24, 22) if on_tpu else (12, 10)
    layers = 4 if on_tpu else 2
    for n in sizes:
        try:
            vqe = _build_vqe_ansatz(n, layers)
            ham = E.PauliSum.of(*_build_tfim_sum(n), n)
            t0 = time.perf_counter()
            f_adj = AD.value_and_grad(vqe, ham, engine="adjoint")
            f_tap = AD.value_and_grad(vqe, ham, engine="taped")
            th0 = f_adj.initial_params
            va, ga = f_adj(th0)
            vt, gt = f_tap(th0)
            compile_s = time.perf_counter() - t0
            parity = float(np.max(np.abs(np.asarray(ga)
                                         - np.asarray(gt))))
            scale = max(1.0, float(np.max(np.abs(np.asarray(gt)))))
            # interleaved best-of A/B: alternate the legs so one host
            # load swing cannot bias a whole engine's measurement
            dt_a = dt_t = float("inf")
            for _ in range(reps):
                dt_a = min(dt_a, _time_grad_steps(f_adj, th0, steps)[0])
                dt_t = min(dt_t, _time_grad_steps(f_tap, th0, steps)[0])
            qaoa = _build_qaoa_circuit(n, max(1, layers // 2))
            q_adj = AD.value_and_grad(qaoa, ham, engine="adjoint")
            q_tap = AD.value_and_grad(qaoa, ham, engine="taped")
            qth0 = q_adj.initial_params
            q_adj(qth0), q_tap(qth0)            # warm the programs
            dq_a = dq_t = float("inf")
            for _ in range(reps):
                dq_a = min(dq_a, _time_grad_steps(q_adj, qth0, steps)[0])
                dq_t = min(dq_t, _time_grad_steps(q_tap, qth0, steps)[0])

            P_vqe = f_adj.num_params
            depth = len(vqe.ops)
            cap = AD.capacity_stats(n, P_vqe, depth, np.float32)

            def widest(engine_key):
                best = 0
                for m in range(8, 41):
                    c = AD.capacity_stats(m, 2 * layers * m,
                                          depth, np.float32)
                    if c[engine_key]:
                        best = m
                return best

            rec = {
                "grad_metric": (f"VQE optimizer steps/sec @ {n}q, "
                                f"P={P_vqe} (adjoint engine)"),
                "grad_value": round(steps / dt_a, 3),
                "grad_unit": "steps/sec",
                "grad_compile_s": round(compile_s, 1),
                "grad_n": n,
                "grad_params": P_vqe,
                "grad_depth": depth,
                "grad_steps_per_s_adjoint": round(steps / dt_a, 3),
                "grad_steps_per_s_taped": round(steps / dt_t, 3),
                "grad_speedup": round(dt_t / dt_a, 3),
                "grad_qaoa_params": q_adj.num_params,
                "grad_qaoa_steps_per_s_adjoint": round(steps / dq_a, 3),
                "grad_qaoa_steps_per_s_taped": round(steps / dq_t, 3),
                "grad_qaoa_speedup": round(dq_t / dq_a, 3),
                "grad_engine_auto": AD.value_and_grad(
                    vqe, ham).engine,
                "grad_adjoint_peak_bytes": cap["adjoint_peak_bytes"],
                "grad_taped_residual_bytes": cap["taped_residual_bytes"],
                "grad_residual_ratio": round(
                    cap["taped_residual_bytes"]
                    / cap["adjoint_peak_bytes"], 2),
                "grad_widest_trainable_n_adjoint": widest("adjoint_fits"),
                "grad_widest_trainable_n_taped": widest("taped_fits"),
                "grad_parity": parity,
            }
            _log(f"training n={n}: adjoint {steps / dt_a:.2f} steps/s "
                 f"vs taped {steps / dt_t:.2f} (VQE, {dt_t / dt_a:.2f}x); "
                 f"QAOA {steps / dq_a:.2f} vs {steps / dq_t:.2f}; "
                 f"grad parity {parity:.2e}; widest trainable "
                 f"{rec['grad_widest_trainable_n_adjoint']}q adjoint vs "
                 f"{rec['grad_widest_trainable_n_taped']}q taped "
                 f"(modeled HBM)")
            rec["_parity_ok"] = bool(parity <= 1e-4 * scale)
            return rec
        except Exception:
            _log(f"training n={n} failed; trying next size down:\n"
                 f"{traceback.format_exc()}")
    return None


def training_main():
    """`python bench.py training` — the adjoint-vs-taped training
    scenario alone, one JSON line of grad_* keys (docs/AUTODIFF.md).
    Exits nonzero when the two engines' gradients disagree beyond the
    f32 parity bound — the speed legs are reported, not gated here (the
    CPU-host gates live in scripts/check_adjoint_golden.py)."""
    rec = _measure_training()
    if rec is None:
        raise SystemExit(1)
    parity_ok = rec.pop("_parity_ok")
    print(json.dumps(rec))
    unknown = set(rec) - HEADLINE_JSON_KEYS
    assert not unknown, (
        f"training scenario emitted unregistered key(s) "
        f"{sorted(unknown)}: add them to HEADLINE_JSON_KEYS")
    if not parity_ok:
        _log(f"REGRESSION: adjoint vs taped gradient parity "
             f"{rec['grad_parity']:.3e} beyond the f32 bound")
        raise SystemExit(1)


def expec_main():
    """`python bench.py expec` — the expectation-engine scenario alone,
    one JSON line of expec_* keys (docs/EXPECTATION.md)."""
    rec = _measure_expec()
    if rec is None:
        raise SystemExit(1)
    print(json.dumps(rec))


def serve_main():
    """`python bench.py serve` — the serving scenario alone, one JSON
    line of serve_* keys (kept out of the default headline run: it is
    a multi-pass closed-loop benchmark, docs/SERVING.md)."""
    rec = _measure_serve()
    print(json.dumps(rec))


_MULTICHIP_WORKER = r'''
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
import json, os, sys
import numpy as np
sys.path.insert(0, %(repo)r)
from jax.sharding import Mesh
import bench
from quest_tpu import precision
from quest_tpu.env import AMP_AXIS
from quest_tpu.parallel.introspect import sharded_schedule

# f64 registers: the comms trajectory must be comparable to the
# committed f64 goldens (scripts/check_comm_golden.py, 672 B deep-global)
precision.set_default_dtype(np.complex128)

D = 8
mesh = Mesh(np.array(jax.devices()[:D]), (AMP_AXIS,))
scenarios = {
    "headline": (bench._build_circuit(14), 14),
    "deepglobal": (bench._build_deep_global_circuit(6, 6), 6),
}
# topology knob passthrough (scripts/tpu_pod_bench.sh exports it on a
# real pod); the dryrun default prices the hosts=2 model so the
# trajectory always carries a DCI column
topology_spec = os.environ.get("QUEST_COMM_TOPOLOGY", "hosts=2")
out = {"metric": "multichip comm plan (8-device dryrun mesh)",
       "unit": "bytes/device",
       "topology": topology_spec}
for name, (c, n) in scenarios.items():
    for engine in ("banded", "pergate"):
        for tag, spec in (("", "0"), ("hier_", topology_spec)):
            os.environ["QUEST_COMM_TOPOLOGY"] = spec
            rec = sharded_schedule(c.ops, n, False, mesh, engine=engine)
            # the plan->predict->assert contract, INSIDE the bench: a
            # comm trajectory whose planned and lowered schedules
            # disagree is a predictor drift, not a measurement — and
            # the ICI/DCI split must tile the asserted total exactly
            assert rec["comm_matches_hlo"], (name, engine, tag, rec)
            pre = f"{name}_{engine}_{tag}"
            out[pre + "comm_exchanges"] = rec["comm_exchanges"]
            out[pre + "comm_bytes"] = rec["comm_bytes"]
            out[pre + "comm_collectives"] = (rec["collective_exchanges"]
                                             + rec["all_reduces"])
            out[pre + "comm_strategy"] = rec["comm_strategy"]
            if tag:
                out[pre + "comm_ici_bytes"] = rec["comm_ici_bytes"]
                out[pre + "comm_dci_bytes"] = rec["comm_dci_bytes"]
                out[pre + "comm_dci_exchanges"] = \
                    rec["comm_dci_exchanges"]
                out[pre + "topology"] = rec["comm_topology"]
# headline trajectory keys for MULTICHIP_r*.json (banded = the pod
# path; the flat record keeps the PR-8 columns comparable, the hier_
# record carries the topology round's DCI split)
out["value"] = out["deepglobal_banded_comm_bytes"]
out["comm_exchanges"] = out["deepglobal_banded_comm_exchanges"]
out["comm_bytes"] = out["deepglobal_banded_comm_bytes"]
out["comm_collectives"] = out["deepglobal_banded_comm_collectives"]
out["comm_ici_bytes"] = out["deepglobal_banded_hier_comm_ici_bytes"]
out["comm_dci_bytes"] = out["deepglobal_banded_hier_comm_dci_bytes"]
out["comm_dci_exchanges"] = \
    out["deepglobal_banded_hier_comm_dci_exchanges"]
print(json.dumps(out))
'''


def multichip_main():
    """`python bench.py multichip` — the comm-planner scenario: lower
    the headline + deep-global circuits over the 8-device dryrun mesh
    (a subprocess with virtual CPU devices, the dryrun_multichip
    recipe), assert the PLANNED comm_stats equal XLA's lowered
    collective accounting, and emit one JSON line of comm_* keys so
    MULTICHIP_r*.json carries a comms trajectory
    (docs/DISTRIBUTED.md)."""
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append("--xla_force_host_platform_device_count=8")
    env["XLA_FLAGS"] = " ".join(flags)
    code = _MULTICHIP_WORKER % {"repo": REPO}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    if r.returncode != 0:
        _log(f"multichip worker failed:\n{r.stderr[-3000:]}")
        raise SystemExit(1)
    print(r.stdout.strip().splitlines()[-1])


def main():
    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if on_tpu:
        sizes, reps = (30, 28, 26, 24, 22), 5
    else:
        sizes, reps = (24, 22, 20), 2

    gates_per_sec = None
    n = None
    engine = compile_s = None
    for cand in sizes:
        try:
            gates_per_sec, engine, compile_s = _measure_jax(cand, reps)
            n = cand
            break
        except Exception:
            _log(f"size n={cand} failed; trying next size down:\n"
                 f"{traceback.format_exc()}")
            continue
    if gates_per_sec is None:
        _log("benchmark failed at every size")
        raise SystemExit(1)

    baseline_gps, baseline_src = _baseline_gates_per_sec(n)
    vs_baseline = gates_per_sec / baseline_gps
    _log(f"baseline source: {baseline_src} ({baseline_gps:.2f} gates/s @ {n}q) "
         f"— the reference build runs PRECISION=1 on ONE host CPU core "
         f"(this host has one; its OpenMP build rejects modern GCC)")

    density_ops, density_nd, density_compile_s = _measure_density(reps=3)
    f64_gps, f64_n, f64_compile_s = _measure_f64(reps=2)
    chain_gps, chain_compile_s = _measure_chain(n, reps)
    rcs_s, rcs_gates, rcs_compile_s = _measure_rcs()
    traj_rec = _measure_trajectories()
    sweeps, sweep_stages, pipeline_rec = _sweep_metrics(_build_circuit, n)
    chain_sweeps, chain_sweep_stages, _ = _sweep_metrics(
        _build_chain_circuit, n)

    line = {
        "metric": f"single-qubit gates/sec @ {n}q statevec ({platform})",
        "value": round(gates_per_sec, 2),
        "unit": "gates/sec",
        "vs_baseline": round(vs_baseline, 3),
        "baseline_note": "reference PRECISION=1 on one host CPU core",
        "engine": engine,
        "compile_s": round(compile_s, 1),
    }
    if sweeps is not None:
        line["hbm_sweeps"] = sweeps
        line["sweep_stages"] = sweep_stages
    if pipeline_rec is not None:
        line.update(pipeline_rec)
    if density_ops is not None:
        line["density_metric"] = (f"channel+gate ops/sec @ {density_nd}q "
                                  f"density ({platform})")
        line["density_value"] = round(density_ops, 2)
        line["density_unit"] = "ops/sec"
        line["density_compile_s"] = round(density_compile_s, 1)
    if f64_gps is not None:
        line["f64_metric"] = (f"single-qubit gates/sec @ {f64_n}q "
                              f"statevec f64/MXU-limb ({platform})")
        line["f64_value"] = round(f64_gps, 2)
        line["f64_unit"] = "gates/sec"
        line["f64_compile_s"] = round(f64_compile_s, 1)
    # the f64-at-capacity record (docs/PRECISION.md): the chunk-bounded
    # limb sizing at 28q is CPU-computable, so it is ALWAYS emitted;
    # the measured throughput key lands when a chip run reaches 28q
    try:
        from quest_tpu.ops import apply as _A
        f64cap = _A.f64_capacity_stats(28, hbm_bytes=_hbm_limit())
        line["f64_28q_peak_bytes"] = f64cap["peak_bytes"]
        line["f64_28q_fits_hbm"] = f64cap["fits_hbm"]
        line["f64_28q_chunk_elems"] = f64cap["chunk_elems"]
    except Exception:
        _log(f"f64 28q capacity record failed:\n{traceback.format_exc()}")
    if f64_gps is not None and f64_n == 28:
        line["f64_28q_value"] = round(f64_gps, 2)
        line["f64_28q_unit"] = "gates/sec"
    if rcs_s is not None:
        line["rcs_metric"] = f"RCS depth-20 @ 30q wall-clock ({platform})"
        line["rcs_value"] = round(rcs_s, 3)
        line["rcs_unit"] = "s/run"
        line["rcs_gates_per_sec"] = round(rcs_gates / rcs_s, 1)
        line["rcs_compile_s"] = round(rcs_compile_s, 1)
    if chain_gps is not None:
        line["chain_metric"] = (f"dependent-chain gates/sec @ {n}q "
                                f"statevec, fusion-resistant ({platform})")
        line["chain_value"] = round(chain_gps, 2)
        line["chain_unit"] = "gates/sec"
        line["chain_compile_s"] = round(chain_compile_s, 1)
        if chain_sweeps is not None:
            line["chain_hbm_sweeps"] = chain_sweeps
            line["chain_sweep_stages"] = chain_sweep_stages
    if traj_rec is not None:
        line.update(traj_rec)
    # print BEFORE the schema gate: a chip session's measurements must
    # never be discarded over a bookkeeping miss — the assert still
    # fails the run loudly for CI
    print(json.dumps(line))
    unknown = set(line) - HEADLINE_JSON_KEYS
    assert not unknown, (
        f"headline JSON emitted unregistered key(s) {sorted(unknown)}: "
        f"add them to HEADLINE_JSON_KEYS so the trajectory files keep "
        f"a parseable schema")


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "serve":
        serve_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "expec":
        expec_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "multichip":
        multichip_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "durable":
        durable_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "fleet":
        fleet_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "evolution":
        evolution_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "autotune":
        autotune_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "gallery":
        gallery_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "training":
        training_main()
    elif len(sys.argv) > 1:
        raise SystemExit(f"unknown bench scenario {sys.argv[1]!r} "
                         f"(known: serve, fleet, expec, multichip, "
                         f"durable, evolution, autotune, gallery, "
                         f"training; no argument = headline run)")
    else:
        main()
