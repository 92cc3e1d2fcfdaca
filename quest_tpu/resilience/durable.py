"""Durable execution: mid-circuit checkpointing, preemption-tolerant
resume, and corruption sentinels (docs/RESILIENCE.md §durable).

The reference can only restart a run from gate 0 and trusts every
amplitude blindly; on preemptible pods a 30q+ job measured in hours
makes "lose the run" the dominant failure cost (the TPU brute-force
paper's operating regime, arXiv:2111.10466). `run_durable` executes a
circuit in STEPS cut at the engines' own launch boundaries — the
sweep-plan parts of the fused engine, fusion-plan items of the banded
and sharded engines, shot chunks of the trajectory engine; NEVER
mid-kernel — and checkpoints the amplitude planes plus a cursor every
`QUEST_DURABLE_EVERY` steps through quest_tpu.checkpoint's atomic
versioned chain:

  * RESUME: a rerun of the same call finds the newest VALID checkpoint
    under `directory`, verifies its cursor against the re-derived plan
    (engine, step count, keyed-knob mode key, and — on the sharded
    engine — the relabel `_PermTracker` permutation at the cut), and
    continues from the cut. Interrupted and uninterrupted runs execute
    the IDENTICAL per-step program sequence, so the final amplitudes
    are BIT-IDENTICAL (pinned per engine in tests/test_durable.py).
  * CORRUPTION ON DISK: every checkpoint's per-plane SHA-256 digests
    are verified at load (checkpoint.py format 3); a corrupt checkpoint
    is skipped LOUDLY (stderr + `durable_corrupt_checkpoints_skipped`)
    in favor of the previous valid one — never silently consumed.
  * CORRUPTION IN FLIGHT: cheap on-device sentinel reductions run at
    checkpoint cadence — statevector norm drift vs the run's baseline,
    density trace + hermiticity residual (`QUEST_INTEGRITY`, budget
    `QUEST_INTEGRITY_TOL`). A trip raises typed `IntegrityError` and
    REFUSES to stamp the checkpoint, so a NaN'd or drifted state can
    never poison the resume chain.

Fault sites `durable.step` / `durable.preempt` (plus `checkpoint.save`
/ `checkpoint.load`) make every path provable: a seeded FaultPlan kills
a deep run K times at random boundaries — including mid-save — and the
chaos soak pins that it still completes with the exact uninterrupted
amplitudes (tests/test_durable.py).

Metrics (serve.metrics.REGISTRY): counters `durable_steps_run`,
`durable_checkpoints_saved`, `durable_resumes`,
`durable_corrupt_checkpoints_skipped`, `durable_sentinel_trips`; gauge
`durable_last_checkpoint_step`.

This module imports jax and is therefore loaded LAZILY by
quest_tpu.resilience.__getattr__ — the rest of the resilience package
stays stdlib-only (env.py's knob parser imports it).
"""

from __future__ import annotations

import hashlib
import os
import sys
import time as _time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from quest_tpu import checkpoint as ckpt
from quest_tpu import validation
from quest_tpu.resilience import faults
from quest_tpu.serve import metrics as _metrics
from quest_tpu.state import Qureg


class DurableError(validation.QuESTError):
    """A durable resume could not be reconciled with the re-derived
    plan: the cursor's engine/step-count/mode-key/permutation disagrees
    with what this process would execute (a keyed-knob flip or circuit
    edit between save and resume). The message names the field and the
    expected/got values — resuming anyway would execute the wrong
    program suffix over the checkpointed amplitudes."""


class IntegrityError(validation.QuESTError):
    """An in-flight corruption sentinel tripped: the state's cheap
    invariant (statevector norm / density trace+hermiticity) drifted
    beyond QUEST_INTEGRITY_TOL from the run's baseline — NaN poisoning,
    a silently corrupt plane, or a non-CPTP evolution. The checkpoint
    at this cut was NOT stamped (docs/RESILIENCE.md §durable)."""


def _registry_of(registry: Optional[_metrics.Registry]
                 ) -> _metrics.Registry:
    return registry if registry is not None else _metrics.REGISTRY


def _counter(name: str, registry: Optional[_metrics.Registry] = None):
    return _registry_of(registry).counter(name)


def _ops_sha(ops) -> str:
    """Value fingerprint of a circuit's op stream — kinds, qubits AND
    operand bytes. The cursor's op COUNT alone cannot catch an edited
    rotation angle (same count, same plan shape, different program);
    resuming across one would splice two different circuits' amplitude
    prefixes silently."""
    h = hashlib.sha256()
    for op in ops:
        h.update(repr((op.kind, op.targets, op.controls,
                       op.cstates)).encode())
        if op.operand is not None:
            try:
                h.update(np.asarray(op.operand).tobytes())
            except Exception:       # nested structures (classical ops)
                h.update(repr(op.operand).encode())
    return h.hexdigest()[:32]


def _state_fingerprint(state: Qureg) -> str:
    """Cheap value fingerprint of the INITIAL register, stored in the
    cursor and re-derived at resume from the caller's own argument: a
    rerun that passes a different initial state (or dtype) must fail
    typed, not splice prefixes. Small registers hash every amplitude;
    huge ones hash shape/dtype plus a leading slice — a full host
    gather per run is the cost this executor exists to avoid."""
    amps = state.amps
    h = hashlib.sha256()
    h.update(repr((tuple(amps.shape), str(amps.dtype))).encode())
    if amps.size <= (1 << 22):
        payload = np.asarray(jax.device_get(amps))
    else:
        payload = np.asarray(jax.device_get(amps[:, :4096]))
    h.update(memoryview(np.ascontiguousarray(payload)).cast("B"))
    return h.hexdigest()[:32]


_ELASTIC_FP_FNS: dict = {}


def _state_fingerprint_elastic(state: Qureg) -> str:
    """MESH-INDEPENDENT exact fingerprint of the initial register, for
    the elastic cursor (docs/RESILIENCE.md §elastic): the float-sum
    fingerprints above round differently per mesh (a psum of shard
    partials reassociates), so an elastic resume on a different device
    or host count could never match them. This one reduces the raw
    amplitude BITS with modular uint32 arithmetic — a plain bit-sum and
    an index-weighted bit-sum, both wraparound-exact and fully
    associative/commutative — so the value is BIT-EQUAL on any mesh
    that holds the same amplitudes (and across hosts of a gang, where
    the cursor must agree byte-for-byte)."""
    amps = state.amps
    key = (tuple(amps.shape), str(amps.dtype))
    fn = _ELASTIC_FP_FNS.get(key)
    if fn is None:
        def f(a):
            bits = jax.lax.bitcast_convert_type(a, jnp.uint32).reshape(-1)
            idx = jax.lax.iota(jnp.uint32, bits.shape[0])
            s1 = jnp.sum(bits, dtype=jnp.uint32)
            # +1 gives every position a DISTINCT nonzero weight (mod
            # 2^32), so moving one amplitude between positions changes
            # the weighted sum even though the plain sum is unchanged
            s2 = jnp.sum(bits * (idx + jnp.uint32(1)), dtype=jnp.uint32)
            return s1, s2
        fn = _ELASTIC_FP_FNS[key] = jax.jit(f)
    vals = [int(v) for v in fn(amps)]
    h = hashlib.sha256()
    h.update(repr((key, vals)).encode())
    return h.hexdigest()[:32]


_GANG_FP_FNS: dict = {}


def _state_fingerprint_gang(state: Qureg) -> str:
    """Fingerprint of a MULTI-HOST register: the gang cursor must be
    IDENTICAL on every host (load_step_gang rejects torn saves), so the
    per-host byte hash above cannot ride it — no host can read its
    peers' shards without a gather. Instead: shape/dtype plus three
    replicated global reductions (sum, sum of squares, max magnitude),
    computed by the SAME SPMD program on every host and therefore
    bit-equal across them; a different initial state or dtype still
    fails typed at resume."""
    amps = state.amps
    key = (tuple(amps.shape), str(amps.dtype))
    fn = _GANG_FP_FNS.get(key)
    if fn is None:
        def f(a):
            return (jnp.sum(a), jnp.sum(a * a), jnp.max(jnp.abs(a)))
        fn = _GANG_FP_FNS[key] = jax.jit(f)
    vals = [float(v) for v in fn(amps)]
    h = hashlib.sha256()
    h.update(repr((key, vals)).encode())
    return h.hexdigest()[:32]


# ---------------------------------------------------------------------------
# step plans: the circuit cut at launch boundaries, per engine
# ---------------------------------------------------------------------------


def _resolve_state_engine(engine, n: int, is_f32: bool, mesh) -> str:
    from quest_tpu.ops import pallas_band as PB
    if mesh is not None:
        if engine not in (None, "sharded"):
            raise ValueError(
                f"engine {engine!r} does not take a mesh; pass "
                f"engine='sharded' (or None) with mesh=")
        return "sharded"
    if engine == "sharded":
        raise ValueError("engine='sharded' requires mesh=")
    if engine not in (None, "fused", "banded"):
        raise ValueError(
            f"engine must be None, 'fused', 'banded' or 'sharded', "
            f"got {engine!r}")
    if engine in (None, "fused") and PB.usable(n) and is_f32:
        return "fused"
    # compiled_fused's own fallback: f64 planes and sub-kernel-tier
    # registers ride the banded XLA program
    return "banded"


def _build_steps(circuit, n: int, density: bool, engine: str,
                 interpret: bool, mesh) -> Tuple[List, dict]:
    """(steps, info) for one engine's durable plan: `steps` is the list
    of independently-jitted per-launch programs (cached on the circuit,
    so a resume in a warm process retraces NOTHING — the zero-retrace
    pin), `info` the plan fingerprint the cursor validates against.
    Cuts reuse the engines' own planners — pallas_band.segment_plan /
    sweep_plan for the fused engine, fusion.plan items for banded and
    sharded — so a cut can never land mid-kernel."""
    from quest_tpu.circuit import (_apply_banded_items, _engine_mode_key,
                                   _xla_part_applier)
    from quest_tpu.ops import fusion as F
    from quest_tpu.ops import pallas_band as PB

    key = ("durable", engine, n, density, interpret,
           mesh if mesh is not None else None, _engine_mode_key())
    cached = circuit._compiled.get(key)
    if cached is not None:
        return cached

    perm_ops = None
    devices = 1
    if engine == "fused":
        flat = circuit._planned_flat(n, density)
        item_attr: list = []
        items = F.plan(flat, n, bands=PB.plan_bands(n), attr=item_attr)
        seg_attr: list = []
        seg_parts = PB.segment_plan(items, n, attr=seg_attr)
        if PB.sweep_enabled():
            part_attr: list = []
            parts = PB.sweep_plan(seg_parts, n, attr=part_attr,
                                  part_attrs=seg_attr)
        else:
            parts, part_attr = list(seg_parts), list(seg_attr)
        # per-STEP flat-op attribution: parts index items, items index
        # flat ops
        step_attr = [frozenset().union(*(item_attr[i] for i in pa))
                     if pa else frozenset() for pa in part_attr]
        seg_cache: dict = {}
        steps = []
        for part in parts:
            if part[0] == "segment":
                seg = PB.compile_segment_cached(
                    seg_cache, tuple(part[1]), n, interpret=interpret)
                fn = (lambda a, seg=seg, arrays=part[2]: seg(a, arrays))
            else:
                fn = _xla_part_applier(part, n)
            steps.append(jax.jit(fn))
        layout = "fused"
        flat_used, exec_items = flat, None
    elif engine == "banded":
        flat = circuit._planned_flat(n, density)
        item_attr = []
        items = F.plan(flat, n, attr=item_attr)
        steps = [jax.jit(lambda a, it=it: _apply_banded_items(a, n, (it,)))
                 for it in items]
        layout = "flat"
        step_attr = item_attr
        flat_used, exec_items = flat, items
    else:                                   # sharded
        import math
        from quest_tpu.parallel import sharded as S
        devices = int(mesh.devices.size)
        local_n = n - int(math.log2(devices))
        bands = S._shard_bands(n, local_n)
        cinfo: dict = {}
        flat_r = S.engine_flat(circuit.ops, n, density, local_n,
                               bands=bands, comm_info=cinfo)
        item_attr = []
        planned = F.plan(flat_r, n, bands=bands, attr=item_attr)
        items = cinfo.get("items")
        if items is None:
            items = planned
        elif not _plans_align(items, planned):
            # the comm planner handed back a plan the deterministic
            # re-plan does not reproduce — attribution would be
            # misaligned (a mis-mapped boundary would double-apply an
            # op on elastic resume), so the elastic boundary map
            # degrades to "no portable boundaries" (strict resume is
            # untouched)
            item_attr = None
        steps = [S.compile_plan_items_sharded((it,), n, mesh)
                 for it in items]
        layout = "sharded"
        step_attr = item_attr
        flat_used, exec_items = flat_r, items
        # the relabel-permutation trajectory at every cut: perm_ops[k]
        # is the GateOp stream behind items[:k] that replay_perm
        # fingerprints (band-composed ops expose no op; relabel events
        # and explicit SWAPs do — see relabel.replay_perm)
        perm_ops = []
        acc: list = []
        for it in items:
            op = getattr(it, "op", None)
            perm_ops.append(tuple(acc))
            if op is not None:
                acc.append(op)
        perm_ops.append(tuple(acc))

    sched = circuit._planned_flat(n, density)
    ops_done_at = _boundary_ops_done(flat_used, step_attr, exec_items,
                                     len(steps))
    info = {
        "engine": engine,
        "n": n,
        "density": density,
        "num_steps": len(steps),
        "mode_key": repr(_engine_mode_key()),
        "circuit_ops": len(circuit.ops),
        "layout": layout,
        "devices": devices,
        "mesh": mesh,
        "perm_ops": perm_ops,
        # elastic boundary bookkeeping (docs/RESILIENCE.md §elastic):
        # the SCHEDULED canonical op stream is mesh-independent (the
        # relabel rewrites only remap/insert), so a cut that consumed
        # exactly its first m ops can re-enter any other mesh's plan at
        # a boundary with the same count
        "sched_sha": _ops_sha(sched),
        "ops_total": len(sched),
        "ops_done_at": ops_done_at,
    }
    circuit._compiled[key] = (steps, info)
    return steps, info


def _plans_align(items, planned) -> bool:
    """STRUCTURAL equality of the comm planner's item list and the
    attribution re-plan — length alone could mask a same-length plan
    that composes ops differently (under-counting ops_done by one and
    double-applying a gate on elastic resume). Both lists wrap the SAME
    flat-stream op objects, so exposed ops compare by identity; band
    items compare by geometry + the qubit sets that drove composition."""
    if len(items) != len(planned):
        return False
    for a, b in zip(items, planned):
        if type(a) is not type(b):
            return False
        if getattr(a, "op", None) is not getattr(b, "op", None):
            return False
        if (getattr(a, "ql", None) != getattr(b, "ql", None)
                or getattr(a, "w", None) != getattr(b, "w", None)
                or getattr(a, "nondiag", None) != getattr(b, "nondiag",
                                                          None)
                or getattr(a, "touched", None) != getattr(b, "touched",
                                                          None)):
            return False
    return True


def _boundary_ops_done(flat_used, step_attr, exec_items,
                       num_steps: int) -> List[Optional[int]]:
    """ops_done_at[b] for every step boundary b in [0, num_steps]: the
    number of CANONICAL (scheduled-stream) ops fully consumed by steps
    [0, b) when that boundary is PORTABLE — the consumed ops form an
    exact prefix of the canonical stream, nothing straddles the cut,
    and every relabel-pass-inserted layout op before it is VISIBLE to
    the perm replay (an inserted SWAP the planner composed into a band
    operator moves data replay_perm cannot see — canonicalization would
    be wrong from that step on) — else None. Boundary 0 is always
    portable (restart from op 0). `step_attr` is the per-step flat-op
    attribution (None = attribution unavailable: only boundary 0
    stays portable)."""
    from quest_tpu.parallel import relabel as R

    out: List[Optional[int]] = [0]
    if step_attr is None:
        return out + [None] * num_steps
    nflat = len(flat_used)
    canon_of: List[Optional[int]] = []
    m = 0
    for op in flat_used:
        if R.is_inserted_layout_op(op):
            canon_of.append(None)
        else:
            canon_of.append(m)
            m += 1
    first = [num_steps] * nflat
    last = [-1] * nflat
    poison = num_steps + 1
    for k, srcs in enumerate(step_attr):
        for p in srcs:
            first[p] = min(first[p], k)
            last[p] = max(last[p], k)
            if canon_of[p] is None and exec_items is not None:
                # layout ops must ride op-exposing items (PassOp for
                # relabel events, DiagItem never): a band-composed one
                # is invisible to the perm replay — poison every
                # boundary past its item
                if getattr(exec_items[k], "op", None) is not flat_used[p]:
                    poison = min(poison, k)
    canon_total = m
    for b in range(1, num_steps + 1):
        if b > poison:
            out.append(None)
            continue
        done = 0
        hi = -1
        ok = True
        for p in range(nflat):
            consumed = last[p] < b and last[p] >= 0
            touched = first[p] < b
            if consumed != touched:
                ok = False          # an op straddles the cut
                break
            if consumed and canon_of[p] is not None:
                done += 1
                hi = max(hi, canon_of[p])
        # prefix check: the consumed canonical ops must be exactly
        # 0..done-1 of the scheduled stream
        if ok and hi == done - 1:
            out.append(done)
        else:
            out.append(None)
    # a fully-consumed plan must land on the full canonical count —
    # anything else means attribution lost ops; degrade loudly-safe
    if out[num_steps] is not None and out[num_steps] != canon_total:
        out[num_steps] = None
    return out


def _cut_perm(info: dict, step: int) -> Optional[List[int]]:
    """The relabel `_PermTracker` permutation at cut `step` (sharded
    engine only): which logical qubit sits at which physical position
    when the first `step` plan items have executed."""
    if info["engine"] != "sharded":
        return None
    import math
    from quest_tpu.parallel import relabel as R
    local_n = info["n"] - int(math.log2(info["devices"]))
    return R.replay_perm(info["perm_ops"][step], info["n"], local_n)


# ---------------------------------------------------------------------------
# layouts: each engine's native amplitude view <-> the (2, 2^n) planes
# ---------------------------------------------------------------------------


def _to_layout(amps, info: dict):
    from quest_tpu.ops import pallas_band as PB
    if info["layout"] == "fused":
        return jnp.asarray(amps).reshape(2, -1, PB.LANES)
    if info["layout"] == "sharded":
        from quest_tpu.parallel.mesh import amp_sharding
        sharding = amp_sharding(info["mesh"])
        if jax.process_count() > 1:
            # multi-host: the caller's register is already a global
            # array (pass it through); a resume's reassembled host
            # planes must enter via make_array_from_callback — a
            # device_put cannot target non-addressable devices
            if isinstance(amps, jax.Array) \
                    and not amps.is_fully_addressable:
                return amps.reshape(2, -1)
            arr = np.asarray(amps).reshape(2, -1)
            return jax.make_array_from_callback(
                arr.shape, sharding, lambda idx: arr[idx])
        return jax.device_put(jnp.asarray(amps).reshape(2, -1),
                              sharding)
    return jnp.asarray(amps).reshape(2, -1)


def _from_layout(amps, info: dict):
    return amps.reshape(2, -1)


# ---------------------------------------------------------------------------
# corruption sentinels: cheap on-device invariants at checkpoint cadence
# ---------------------------------------------------------------------------

_SENTINEL_FNS: dict = {}


def _sentinel_values(amps, info: dict) -> dict:
    """The state's cheap integrity invariants, as host floats: one
    reduction pass for a statevector (norm), trace + hermiticity
    residual for a density register — orders cheaper than a sweep, and
    NaN anywhere fails every comparison (NaN <= tol is False)."""
    density = info["density"]
    key = ("dm" if density else "sv", info["n"], amps.shape,
           str(amps.dtype))
    fn = _SENTINEL_FNS.get(key)
    if fn is None:
        if density:
            nq = info["n"] // 2          # rho is 2^nq x 2^nq

            def f(a):
                v = a.reshape(2, 1 << nq, 1 << nq)
                # flat index r + c*2^nq => v[plane, c, r]
                tr_re = jnp.trace(v[0])
                tr_im = jnp.trace(v[1])
                herm = jnp.maximum(
                    jnp.max(jnp.abs(v[0] - v[0].T)),
                    jnp.max(jnp.abs(v[1] + v[1].T)))
                return tr_re, tr_im, herm
        else:
            def f(a):
                return (jnp.sum(a.astype(jnp.float32) ** 2),)
        fn = _SENTINEL_FNS[key] = jax.jit(f)
    vals = [float(v) for v in fn(amps)]
    if density:
        return {"trace_re": vals[0], "trace_im": vals[1],
                "herm_residual": vals[2]}
    return {"norm": vals[0]}


def _check_integrity(vals: dict, baseline: dict, tol: float,
                     step, registry=None) -> None:
    for name, got in vals.items():
        ref = float(baseline.get(name, 0.0))
        # relative drift with a floor of 1: registers need not be
        # normalized (init_debug_state is not), so the budget scales
        # with the invariant's own magnitude and becomes absolute for
        # unit-scale invariants (norm/trace of normalized states)
        drift = abs(got - ref) / max(1.0, abs(ref))
        if not (drift <= tol):           # NaN-safe: NaN fails the <=
            _counter("durable_sentinel_trips", registry).inc()
            raise IntegrityError(
                f"Integrity sentinel tripped at step {step}: {name} = "
                f"{got!r}, baseline {ref!r}, drift beyond the "
                f"QUEST_INTEGRITY_TOL budget {tol} — the state is "
                f"corrupt (NaN poisoning or a bad plane); REFUSING to "
                f"stamp a checkpoint from it (docs/RESILIENCE.md "
                f"§durable)")


# ---------------------------------------------------------------------------
# cursor + resume chain
# ---------------------------------------------------------------------------


def _validate_cursor(cursor: dict, want: dict, path: str) -> None:
    """Every field of the re-derived plan must match the checkpointed
    cursor — resuming across a drifted plan would run the wrong program
    suffix over the cut amplitudes. Raises DurableError naming the
    first mismatching field."""
    for field, expect in want.items():
        got = cursor.get(field)
        if got != expect:
            raise DurableError(
                f"Invalid durable resume: checkpoint {path!r} was cut "
                f"under {field}={got!r}, but this process would execute "
                f"{field}={expect!r} — a keyed knob flip or circuit "
                f"change between save and resume; finish the run under "
                f"the original configuration (or clear the checkpoint "
                f"directory to restart from op 0)")


def _latest_valid(directory: str, kind: str, registry=None):
    """Newest checkpoint under `directory` that loads AND digests
    cleanly, scanning newest -> oldest: corrupt or unreadable entries
    are skipped LOUDLY (stderr + counter) in favor of older ones —
    never silently consumed. Returns (meta, arrays, cursor, path) or
    None when no valid checkpoint exists (the run restarts from op
    0). A GANG-format step (written by a multi-host run) is a typed
    mesh mismatch, not corruption: restarting from op 0 over a valid
    multi-host chain would silently discard it."""
    for step, path in reversed(ckpt.step_dirs(directory)):
        if ckpt.is_gang_step(path):
            raise DurableError(
                f"Invalid durable resume: checkpoint {path!r} was "
                f"written by a multi-host gang run; resume it on the "
                f"same mesh, or pass elastic=True to re-enter it on "
                f"this one (docs/RESILIENCE.md §elastic)")
        try:
            meta, arrays = ckpt.load_arrays(path, require=("planes",))
            cursor = meta.get("extra")
            if not isinstance(cursor, dict) or cursor.get("kind") != kind:
                raise ckpt.CheckpointError(
                    f"Invalid checkpoint: {path!r} carries no "
                    f"{kind!r} durable cursor")
            # belt to the meta self-digest's suspenders: the cursor's
            # cut index must agree with the committed directory name (a
            # save-side bug writing the wrong step would pass digests)
            cut = cursor.get("step", cursor.get("shots_done"))
            if int(cut) != step:
                raise ckpt.CheckpointError(
                    f"Invalid checkpoint: {path!r} carries cursor cut "
                    f"{cut!r}, directory name says {step}")
        except (ckpt.CheckpointError, OSError, TypeError, ValueError,
                faults.InjectedFault) as e:
            # TypeError/ValueError: a parseable-but-malformed cursor
            # (e.g. no 'step' field) is corruption, not a crash — the
            # scan's contract is skip-loudly-to-older
            # InjectedFault: the checkpoint.load site's default error —
            # its documented contract is that the resume chain SKIPS to
            # an older checkpoint, so the injected failure must prove
            # the fallback, not take the run down
            _counter("durable_corrupt_checkpoints_skipped",
                     registry).inc()
            print(f"[durable] SKIPPING corrupt checkpoint {path!r} "
                  f"({e}); falling back to the previous one",
                  file=sys.stderr, flush=True)
            continue
        return meta, arrays, cursor, path
    return None


def _latest_valid_gang(directory: str, kind: str, registry=None):
    """Gang counterpart of _latest_valid: newest COMMITTED gang
    checkpoint whose every shard digests cleanly and whose per-host
    cursors agree. Validity is a pure function of the shared directory
    (load_step_gang verifies ALL shards on every host), so every host
    independently lands on the SAME checkpoint — a mid-save kill left
    its step uncommitted, and corruption anywhere skips the whole gang
    to the same older cut. Returns (cursor, planes, path) or None."""
    for step, path in reversed(ckpt.step_dirs(directory)):
        if os.path.exists(os.path.join(path, "qureg_meta.json")):
            raise DurableError(
                f"Invalid durable resume: checkpoint {path!r} was "
                f"written by a single-process run, but this is a "
                f"multi-host gang resume; resume it on the writing "
                f"mesh, or pass elastic=True to re-enter it on this "
                f"one (docs/RESILIENCE.md §elastic)")
        try:
            metas, planes = ckpt.load_step_gang(path, kind_extra=kind)
            cursor = metas[0].get("extra")
            cut = cursor.get("step")
            if int(cut) != step:
                raise ckpt.CheckpointError(
                    f"Invalid checkpoint: {path!r} carries cursor cut "
                    f"{cut!r}, directory name says {step}")
        except (ckpt.CheckpointError, OSError, TypeError, ValueError,
                faults.InjectedFault) as e:
            # TypeError/ValueError: a parseable-but-malformed cursor
            # (e.g. no 'step' field) is corruption, not a crash — the
            # scan's contract is skip-loudly-to-older
            _counter("durable_corrupt_checkpoints_skipped",
                     registry).inc()
            print(f"[durable] SKIPPING corrupt gang checkpoint "
                  f"{path!r} ({e}); falling back to the previous one",
                  file=sys.stderr, flush=True)
            continue
        return cursor, planes, path
    return None


def _iter_valid_elastic(directory: str, registry=None):
    """Format-agnostic scan for ELASTIC resume (docs/RESILIENCE.md
    §elastic): yields every step checkpoint — plain single-process
    (canonical or legacy physical layout) or multi-host gang — that
    loads and digests cleanly, newest first, in CANONICAL LOGICAL ORDER
    via checkpoint.load_step_elastic. Corrupt/unreadable entries skip
    loudly to older ones, exactly like the strict scanners; the caller
    advances past entries the target mesh cannot re-enter. Yields
    (cursor, canonical_planes, path)."""
    for step, path in reversed(ckpt.step_dirs(directory)):
        try:
            cursor, planes = ckpt.load_step_elastic(path)
            cut = cursor.get("step")
            if int(cut) != step:
                raise ckpt.CheckpointError(
                    f"Invalid checkpoint: {path!r} carries cursor cut "
                    f"{cut!r}, directory name says {step}")
        except (ckpt.CheckpointError, OSError, TypeError, ValueError,
                faults.InjectedFault) as e:
            # TypeError/ValueError: a parseable-but-malformed cursor
            # (e.g. no 'step' field) is corruption, not a crash — the
            # scan's contract is skip-loudly-to-older
            _counter("durable_corrupt_checkpoints_skipped",
                     registry).inc()
            print(f"[durable] SKIPPING corrupt checkpoint {path!r} "
                  f"({e}); falling back to the previous one",
                  file=sys.stderr, flush=True)
            continue
        yield cursor, planes, path


def _enter_elastic(want, elastic_want, cursor_extra, info, state,
                   directory: str, registry=None):
    """Elastic re-entry (docs/RESILIENCE.md §elastic): walk the chain
    newest->oldest and re-enter the first checkpoint THIS plan can
    continue. Per checkpoint:

      * a mismatched sched_sha / state_efp / dtype / density / ops_total
        (or cursor_extra descriptor) raises typed DurableError — elastic
        never relaxes WHAT is computed, only where;
      * a pre-elastic cursor (no sched_sha) falls back to the STRICT
        field validation: on the writing mesh it resumes tolerantly, on
        a changed mesh it rejects typed (old checkpoints never resume
        wrong);
      * a cut this mesh's plan has no matching portable boundary for
        (ops_done is None, or the target compositions straddle that
        count) skips LOUDLY to an older checkpoint — op 0 is always
        portable, so the walk terminates correctly.

    Returns (start_step, layouted_amps, baseline) or None (no usable
    checkpoint: start from op 0)."""
    from quest_tpu.parallel import relabel as R

    for cursor, canon, path in _iter_valid_elastic(directory, registry):
        if "sched_sha" not in cursor:
            _validate_cursor(cursor, want, path)
            step = int(cursor["step"])
            perm = _cut_perm(info, step)
            _validate_cursor(cursor, {"perm": perm}, path)
            b = step
        else:
            _validate_cursor(cursor, elastic_want, path)
            if cursor_extra:
                _validate_cursor(cursor, cursor_extra, path)
            m = cursor.get("ops_done")
            b = (info["ops_done_at"].index(m)
                 if m is not None and m in info["ops_done_at"] else None)
            if b is None:
                print(f"[durable] checkpoint {path!r} cut at canonical "
                      f"op {m!r} has no portable boundary in this "
                      f"mesh's plan; falling back to an older "
                      f"checkpoint (docs/RESILIENCE.md §elastic)",
                      file=sys.stderr, flush=True)
                continue
            perm = _cut_perm(info, b)
        if canon.shape != state.amps.shape:
            raise DurableError(
                f"Invalid durable resume: checkpoint {path!r} holds "
                f"planes of shape {tuple(canon.shape)}, register "
                f"expects {tuple(state.amps.shape)}")
        planes = np.asarray(canon).astype(state.real_dtype)
        if perm:
            planes = R.physicalize_planes(planes, perm)
        _counter("durable_resumes", registry).inc()
        if (cursor.get("devices") != info["devices"]
                or cursor.get("engine") != info["engine"]):
            _counter("durable_elastic_resumes", registry).inc()
        return b, _to_layout(planes, info), cursor.get("baseline")
    return None


def _clear_chain(directory: str) -> None:
    """A COMPLETED run consumes its resume chain: the checkpoints exist
    to finish this run, and leaving them would make a later run over
    the same directory resume mid-circuit with a different initial
    state."""
    import shutil
    for _, path in ckpt.step_dirs(directory):
        shutil.rmtree(path, ignore_errors=True)
    ckpt.sweep_stale(directory)


# ---------------------------------------------------------------------------
# the durable executor: state engines
# ---------------------------------------------------------------------------


def run_durable(circuit, state: Qureg, directory: str, *,
                every: int = None, engine: str = None, mesh=None,
                interpret: bool = False, keep: int = None,
                elastic: Optional[bool] = None,
                cursor_extra: Optional[dict] = None,
                registry: Optional[_metrics.Registry] = None) -> Qureg:
    """Apply `circuit` to `state` durably: execute the engine's own
    launch plan step by step, checkpoint planes + cursor every `every`
    steps (default QUEST_DURABLE_EVERY) under `directory`, and — when a
    valid checkpoint already exists there — RESUME from it instead of
    op 0. The final register is bit-identical to an uninterrupted run
    whatever mix of preemptions, mid-save crashes and on-disk
    corruption happened in between, because interrupted and
    uninterrupted runs execute the identical per-step program sequence
    and a corrupt checkpoint is never consumed (tests/test_durable.py;
    docs/RESILIENCE.md §durable).

    engine: None auto-resolves like apply_fused (Pallas kernels on the
    kernel tier at f32, banded XLA otherwise); 'fused' / 'banded' pin
    it; mesh= selects the sharded banded engine (its relabel
    permutation rides the cursor and is re-verified at resume). On a
    MULTI-HOST mesh (jax.process_count() > 1) checkpointing is
    GANG-CONSISTENT: every cursor step writes one shared checkpoint
    through checkpoint.save_step_gang's two-phase commit — each host
    stamps its shard, the last stamp commits atomically, a host killed
    mid-save leaves the step uncommitted on EVERY host — and resume
    validity is a pure function of the shared directory, so all hosts
    independently resume the same cut, bit-identical to an
    uninterrupted run (tests/test_gang.py; docs/RESILIENCE.md
    §gang-consistent durable). Noise
    channels run through the density engines as usual; for trajectory
    unraveling use run_durable_trajectories. Integrity sentinels run at
    checkpoint cadence (QUEST_INTEGRITY / QUEST_INTEGRITY_TOL); a
    completed run removes its own checkpoint chain. `registry` redirects
    the durable_* metrics (default: the process-wide
    serve.metrics.REGISTRY) — the serve fleet's replicas pass their own
    registry so a fleet soak's durable tallies ride the same snapshot
    as its fleet_* metrics. `cursor_extra` adds workload-descriptor
    fields (JSON-serializable) to every cursor, VALIDATED at resume
    like the plan fields — quest_tpu.evolution's deep quenches stamp
    their Trotter steps/order/dt through it (docs/EVOLUTION.md).

    `elastic` (default: the QUEST_DURABLE_ELASTIC knob, off) makes the
    resume MESH-INDEPENDENT (docs/RESILIENCE.md §elastic): a checkpoint
    chain written by D devices across H hosts — including a gang chain
    — re-enters THIS call's mesh (any D'/H', including single-device
    and single->sharded) by reassembling the planes in canonical
    logical order, re-verifying every source digest, matching the
    cursor's canonical op count against this plan's portable step
    boundaries, and re-deriving the comm plan / relabel permutation for
    the new mesh. What still rejects typed: a different circuit or
    scheduled stream (sched_sha), a different initial state (the exact
    bit-sum state_efp), a different dtype, and cursor_extra mismatches
    — elastic relaxes only WHERE the run executes, never WHAT it
    computes. A checkpoint whose cut is not portable to this mesh
    skips LOUDLY to an older one (op 0 is always portable). Without
    elastic, a mesh mismatch rejects typed exactly as before."""
    from quest_tpu.env import knob_value

    if circuit.num_qubits != state.num_qubits:
        raise ValueError("circuit/register size mismatch")
    circuit._reject_measure("run_durable")
    n = state.num_state_qubits
    density = state.is_density
    every = int(every) if every is not None else knob_value(
        "QUEST_DURABLE_EVERY")
    if every < 1:
        raise ValueError(f"every must be >= 1, got {every}")
    is_f32 = state.real_dtype == np.dtype(np.float32)
    engine = _resolve_state_engine(engine, n, is_f32, mesh)
    steps, info = _build_steps(circuit, n, density, engine, interpret,
                               mesh)
    integrity = knob_value("QUEST_INTEGRITY")
    tol = knob_value("QUEST_INTEGRITY_TOL")
    if elastic is None:
        elastic = bool(knob_value("QUEST_DURABLE_ELASTIC"))
    # multi-host gang mode: one gang-consistent checkpoint per cursor
    # step (two-phase commit across the mesh's processes — all hosts
    # stamp or none do, checkpoint.save_step_gang), cursor fields
    # computed so they are bit-equal on every host
    gang = mesh is not None and jax.process_count() > 1

    want = {
        "engine": engine,
        # interpret-mode kernels round differently from compiled ones,
        # and a different mesh width changes the shard layout: both
        # must match the save-side plan exactly, like every other field
        "interpret": bool(interpret),
        "devices": info["devices"],
        "num_steps": info["num_steps"],
        "mode_key": info["mode_key"],
        "circuit_ops": info["circuit_ops"],
        "plan_sha": _ops_sha(circuit.ops),
        "state_fp": (_state_fingerprint_gang(state) if gang
                     else _state_fingerprint(state)),
    }
    # mesh-independent cursor fields: every state cursor carries them
    # (whether or not THIS run is elastic), so any chain can later be
    # picked up by an elastic resume on different hardware
    # (docs/RESILIENCE.md §elastic)
    elastic_want = {
        "sched_sha": info["sched_sha"],
        "ops_total": info["ops_total"],
        "state_efp": _state_fingerprint_elastic(state),
        "dtype": str(state.real_dtype),
        "density": density,
    }
    if cursor_extra:
        # workload-level descriptor fields (e.g. the Trotter
        # steps/order/dt of quest_tpu.evolution's deep quenches): they
        # ride EVERY cursor and are VALIDATED at resume exactly like
        # the plan fields — a rerun under a different workload
        # descriptor fails typed instead of splicing prefixes. Values
        # must be JSON-serializable (the checkpoint meta self-digest
        # canonicalizes them).
        reserved = (set(want) | set(elastic_want)
                    | {"kind", "step", "perm", "baseline", "layout",
                       "ops_done"})
        overlap = set(cursor_extra) & reserved
        if overlap:
            raise ValueError(
                f"cursor_extra may not shadow reserved cursor fields "
                f"{sorted(overlap)}")
        want.update(cursor_extra)
    start, baseline = 0, None
    if elastic:
        resume = _enter_elastic(want, elastic_want, cursor_extra,
                                info, state, directory, registry)
        if resume is not None:
            start, amps, baseline = resume
        else:
            amps = _to_layout(state.amps, info)
    else:
        if gang:
            found = _latest_valid_gang(directory, "state", registry)
        else:
            found = _latest_valid(directory, "state", registry)
        if found is not None:
            if gang:
                cursor, planes, path = found
            else:
                meta, arrays, cursor, path = found
                planes = arrays["planes"]
            _validate_cursor(cursor, want, path)
            step = int(cursor["step"])
            perm = _cut_perm(info, step)
            _validate_cursor(cursor, {"perm": perm}, path)
            if planes.shape != state.amps.shape:
                raise DurableError(
                    f"Invalid durable resume: checkpoint {path!r} holds "
                    f"planes of shape {tuple(planes.shape)}, register "
                    f"expects {tuple(state.amps.shape)}")
            if cursor.get("layout") == "canonical" and perm:
                # canonical-order checkpoint (the save-side normalizes,
                # docs/RESILIENCE.md §elastic): re-enter the validated
                # cut's physical layout — an exact index permutation,
                # so the strict round trip stays bit-identical
                from quest_tpu.parallel import relabel as R
                planes = R.physicalize_planes(np.asarray(planes), perm)
            amps = _to_layout(planes.astype(state.real_dtype), info)
            start = step
            baseline = cursor.get("baseline")
            _counter("durable_resumes", registry).inc()
        else:
            amps = _to_layout(state.amps, info)
    if baseline is None and integrity:
        baseline = _sentinel_values(amps, info)

    for i in range(start, len(steps)):
        if faults.ACTIVE:
            faults.check("durable.step", step=i, engine=engine)
            faults.check("durable.preempt", step=i, engine=engine)
        amps = steps[i](amps)
        _counter("durable_steps_run", registry).inc()
        done = i + 1
        if done % every == 0 and done < len(steps):
            # drain the async step queue BEFORE the checkpoint timer:
            # the first sync point would otherwise absorb the pending
            # steps' compute into the measured checkpoint cost
            jax.block_until_ready(amps)
            t0 = _time.perf_counter()
            if integrity:
                _check_integrity(_sentinel_values(amps, info), baseline,
                                 tol, done, registry)
            perm_cut = _cut_perm(info, done)
            cursor = dict(want, **elastic_want, kind="state", step=done,
                          perm=perm_cut, baseline=baseline,
                          ops_done=info["ops_done_at"][done],
                          layout="physical" if gang else "canonical")
            stamped = True
            if gang:
                # gang shards stay in the PHYSICAL layout (no host
                # holds its peers' canonical columns without a
                # collective); the perm in the digested cursor makes
                # the checkpoint's meaning writer-independent — the
                # elastic loader normalizes at reassembly
                # (checkpoint.load_step_elastic)
                committed = ckpt.save_step_gang(
                    directory, done,
                    qureg=state.replace_amps(_from_layout(amps, info)),
                    extra=cursor, keep=keep)
                # the commit may land on any host; count a saved
                # checkpoint only when the committed dir is actually
                # observable — a gang save a killed peer never stamped
                # must not advance the metric (a slower peer
                # committing later is counted by THAT host)
                stamped = (committed is not None
                           or os.path.isdir(ckpt.step_path(directory,
                                                           done)))
            else:
                # normalize to CANONICAL LOGICAL ORDER before digesting
                # (docs/RESILIENCE.md §elastic): the shard file's
                # meaning no longer depends on the writer's relabel
                # history — an exact index permutation, undone at
                # strict resume bit-identically
                planes_np = np.asarray(
                    jax.device_get(_from_layout(amps, info)))
                if perm_cut:
                    from quest_tpu.parallel import relabel as R
                    planes_np = R.canonicalize_planes(planes_np,
                                                      perm_cut)
                ckpt.save_step(directory, done,
                               qureg=Qureg(amps=planes_np,
                                           num_qubits=state.num_qubits,
                                           is_density=state.is_density),
                               extra=cursor, keep=keep)
            if stamped:
                _counter("durable_checkpoints_saved", registry).inc()
                _registry_of(registry).gauge(
                    "durable_last_checkpoint_step").set(done)
            # per-cut cost (sentinel + host gather + atomic write):
            # bench.py's durable scenario derives its overhead fraction
            # from this histogram — one instrumented run instead of a
            # noisy wall-clock A/B difference
            _registry_of(registry).histogram("durable_checkpoint_s").observe(
                _time.perf_counter() - t0)
    if integrity:
        # the run's exit gate: a durable run must never RETURN a
        # corrupt state silently either — same sentinel, same budget
        _check_integrity(_sentinel_values(amps, info), baseline, tol,
                         "final", registry)
    out = state.replace_amps(_from_layout(amps, info))
    _clear_chain(directory)
    return out


# ---------------------------------------------------------------------------
# the durable executor: trajectory engine
# ---------------------------------------------------------------------------


def _key_fingerprint(key) -> str:
    try:
        data = jax.random.key_data(key)
    except Exception:
        data = key
    return hashlib.sha256(
        np.ascontiguousarray(jax.device_get(data)).tobytes()
    ).hexdigest()[:32]


def run_durable_trajectories(circuit, key, shots: int, directory: str, *,
                             every: int = None, chunk: int = None,
                             engine: str = None, interpret: bool = False,
                             keep: int = None,
                             registry: Optional[_metrics.Registry] = None):
    """Durable counterpart of trajectories.run_batched: run `shots`
    stochastic trajectories of a noisy Circuit in the SAME bucket-sized
    chunks run_batched would dispatch (trajectories._bucket_for), and
    checkpoint the accumulated (shots_done, 2, 2^n) planes + (shots_done,
    C) draws plus a cursor every `every` chunks. The cursor carries the
    root key's fingerprint, so a resumed run provably continues the
    exact `split(key, shots)` chain — completed shots load from the
    checkpoint, remaining shots re-dispatch from their own keys, and
    the result is bit-identical to an uninterrupted run (and to
    run_batched at the same chunking). Per-shot norm sentinels run at
    checkpoint cadence (every trajectory is a normalized statevector by
    construction). Returns (planes, draws) exactly like run_batched;
    `observable=` reductions are deliberately unsupported here — the
    planes ARE the resume payload.

    COST NOTE: each checkpoint stores the FULL accumulated payload
    (delta-chained checkpoints would break keep-last-K retention — the
    corrupt-skip fallback needs every surviving checkpoint to be
    self-contained), so total checkpoint bytes grow quadratically in
    shot count at fixed cadence. Size `every` to the failure rate, not
    the chunk count; shot counts whose planes don't comfortably fit in
    host memory should reduce with run_batched(observable=) instead of
    running durably."""
    from quest_tpu import trajectories as T
    from quest_tpu.circuit import _engine_mode_key
    from quest_tpu.env import knob_value

    n = circuit.num_qubits
    shots = int(shots)
    if shots < 1:
        raise ValueError(f"shots must be >= 1, got {shots}")
    every = int(every) if every is not None else knob_value(
        "QUEST_DURABLE_EVERY")
    if every < 1:
        raise ValueError(f"every must be >= 1, got {every}")
    integrity = knob_value("QUEST_INTEGRITY")
    tol = knob_value("QUEST_INTEGRITY_TOL")
    engine = T._resolve_engine(engine, n, interpret)
    bucket = T._bucket_for(shots, chunk)
    fn = T._compiled_traj(circuit, n, bucket, engine, interpret)
    keys = jax.random.split(key, shots)
    want = {
        "engine": engine,
        "interpret": bool(interpret),
        "bucket": bucket,
        "shots": shots,
        "mode_key": repr(_engine_mode_key()),
        "circuit_ops": len(circuit.ops),
        "plan_sha": _ops_sha(circuit.ops),
        "key_fp": _key_fingerprint(key),
    }

    planes_acc: list = []
    draws_acc: list = []
    shots_done = 0
    found = _latest_valid(directory, "traj", registry)
    if found is not None:
        meta, arrays, cursor, path = found
        _validate_cursor(cursor, want, path)
        shots_done = int(cursor["shots_done"])
        planes_acc.append(np.asarray(arrays["planes"]))
        draws_acc.append(np.asarray(arrays["draws"]))
        _counter("durable_resumes", registry).inc()

    chunks_done = 0
    for lo in range(shots_done, shots, bucket):
        if faults.ACTIVE:
            faults.check("durable.step", shot=lo, engine=engine)
            faults.check("durable.preempt", shot=lo, engine=engine)
        # the SAME chunk dispatch (slice/pad/unpad) run_batched uses —
        # the bit-identity pin depends on the loops never diverging
        planes, draws = T._dispatch_chunk(fn, keys, lo, bucket)
        planes_acc.append(np.asarray(planes))
        draws_acc.append(np.asarray(draws))
        _counter("durable_steps_run", registry).inc()
        shots_done = min(lo + bucket, shots)
        chunks_done += 1
        if chunks_done % every == 0 and shots_done < shots:
            t0 = _time.perf_counter()
            all_planes = np.concatenate(planes_acc, axis=0)
            all_draws = np.concatenate(draws_acc, axis=0)
            planes_acc, draws_acc = [all_planes], [all_draws]
            if integrity:
                norms = np.sum(all_planes.astype(np.float32) ** 2,
                               axis=(1, 2))
                worst = int(np.argmax(np.abs(norms - 1.0)))
                _check_integrity(
                    {"norm": float(norms[worst])}, {"norm": 1.0}, tol,
                    f"shot {worst} (of {shots_done} done)", registry)
            cursor = dict(want, kind="traj", shots_done=shots_done)
            ckpt.save_step(directory, shots_done,
                           arrays={"planes": all_planes,
                                   "draws": all_draws},
                           extra=cursor, keep=keep)
            _counter("durable_checkpoints_saved", registry).inc()
            _registry_of(registry).gauge("durable_last_checkpoint_step").set(
                shots_done)
            _registry_of(registry).histogram("durable_checkpoint_s").observe(
                _time.perf_counter() - t0)
    planes = (planes_acc[0] if len(planes_acc) == 1
              else np.concatenate(planes_acc, axis=0))
    draws = (draws_acc[0] if len(draws_acc) == 1
             else np.concatenate(draws_acc, axis=0))
    if integrity:
        # exit gate: every trajectory is a normalized statevector by
        # construction — a NaN'd or drifted shot must fail loudly
        norms = np.sum(planes.astype(np.float32) ** 2, axis=(1, 2))
        worst = int(np.argmax(np.abs(norms - 1.0)))
        _check_integrity({"norm": float(norms[worst])}, {"norm": 1.0},
                         tol, f"final (shot {worst})", registry)
    _clear_chain(directory)
    return jnp.asarray(planes), jnp.asarray(draws)
