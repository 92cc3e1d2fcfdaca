"""Explicit shard_map circuit engine: the reference's distributed schedule,
re-thought for ICI.

Mapping from the reference (QuEST/src/CPU/QuEST_cpu_distributed.c):

  reference mechanism                          | here
  ---------------------------------------------|---------------------------
  chunkId / numChunks                          | lax.axis_index over the mesh
  halfMatrixBlockFitsInChunk (:356-361)        | static `target < local_n` test
  getChunkPairId = id XOR 2^(q-log2 chunk)     | ppermute permutation table
    (:303-312)                                 |   [(i, i ^ 2^gbit)]
  exchangeStateVectors MPI_Sendrecv (:481-509) | lax.ppermute of the chunk
  swap-to-local for multi-target gates         | half-chunk ppermute swap
    (:1441-1483)                               |   (_swap_global_local)
  diagonal ops never communicate               | device-bit-indexed diagonal
    (QuEST_cpu.c:2940-3109)                    |   reduction (_diagonal_op)
  MPI_Allreduce reductions                     | lax.psum

Everything below runs INSIDE one shard_map over the 1-D amplitude mesh; the
whole circuit is a single XLA program, so purely-local stretches fuse and
the collectives are laid out by the compiler over ICI.

The per-device chunk is a (2, 2^local_n) plane pair (see quest_tpu.state)
holding amplitudes whose top log2(D) index bits equal the device index —
"global" qubits. A gate is local iff all its targets are below local_n; the
op dispatch is static (targets are trace-time constants), exactly as the
reference's local/distributed split is resolved per call.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from quest_tpu import compat
from quest_tpu import cplx
from quest_tpu.env import AMP_AXIS
from quest_tpu import validation as val
from quest_tpu.ops import apply as A
from quest_tpu.parallel import comm as C
from quest_tpu.state import Qureg


def _pair_perm(num_devices: int, gbit: int):
    """Partner table: device i <-> i XOR 2^gbit (ref getChunkPairId,
    QuEST_cpu_distributed.c:303-312)."""
    return [(i, i ^ (1 << gbit)) for i in range(num_devices)]


def _split_controls(controls, cstates, local_n):
    loc_c, loc_s, glob = [], [], []
    for c, s in zip(controls, cstates):
        if c < local_n:
            loc_c.append(c)
            loc_s.append(s)
        else:
            glob.append((c - local_n, s))
    return tuple(loc_c), tuple(loc_s), tuple(glob)


def _global_pred(dev, glob_controls):
    """Traced scalar bool: this device's chunk satisfies all global-qubit
    controls (the whole chunk shares those bits)."""
    pred = None
    for bit, want in glob_controls:
        p = ((dev >> bit) & 1) == want
        pred = p if pred is None else pred & p
    return pred


def _mask_blend(new, old, local_n, loc_c, loc_s, pred):
    """Keep `new` only where local control mask AND global predicate hold.
    new/old are (2, 2^local_n) plane pairs."""
    if not loc_c and pred is None:
        return new
    if loc_c:
        dims, axis_of = A.seg_view(local_n, tuple(sorted(loc_c, reverse=True)))
        mask = A.control_mask(len(dims), axis_of, loc_c, loc_s)
        if pred is not None:
            mask = mask & pred
        shape = (2,) + dims
        return jnp.where(mask, new.reshape(shape),
                         old.reshape(shape)).reshape(2, -1)
    return jnp.where(pred, new, old)


def _sliced_ppermute(block, D, gbit):
    """One pair exchange of `block` ((2, x) planes), split into
    QUEST_EXCHANGE_SLICES independent collective-permutes — or
    QUEST_EXCHANGE_SLICES_DCI when device bit `gbit` crosses the host
    boundary of the QUEST_COMM_TOPOLOGY model (comm.effective_slices is
    the shared clamp and comm.Topology.link_of the shared classifier,
    so the predicted and lowered collective counts agree at any knob
    value and per link class). Slicing lets the compiler overlap
    transfer with the consuming compute on real ICI/DCI —
    structure-verifiable on the CPU mesh; wall-clock A/B deferred to
    first chip run (docs/DISTRIBUTED.md)."""
    s = C.effective_slices(block.shape[-1],
                           C.topology(D).link_of(gbit, D))
    if s == 1:
        return lax.ppermute(block, AMP_AXIS, _pair_perm(D, gbit))
    xs = block.reshape(2, s, -1)
    recv = [lax.ppermute(xs[:, i], AMP_AXIS, _pair_perm(D, gbit))
            for i in range(s)]
    return jnp.concatenate(recv, axis=1)


def _swap_global_local(chunk, dev, D, gbit, l, local_n):
    """Distributed SWAP of global qubit (device bit `gbit`) with local qubit
    l — a half-chunk ppermute (the reference exchanges full chunks for this,
    QuEST_cpu.c:3539-3578; half is sufficient because only amplitudes whose
    two swapped bits differ move)."""
    dims, axis_of = A.seg_view(local_n, (l,))
    t = chunk.reshape((2,) + dims)
    ax = 1 + axis_of[l]
    g = (dev >> gbit) & 1
    moving = lax.dynamic_slice_in_dim(t, 1 - g, 1, axis=ax)
    recv = _sliced_ppermute(moving.reshape(2, -1), D, gbit).reshape(
        moving.shape)
    t = lax.dynamic_update_slice_in_dim(t, recv, 1 - g, axis=ax)
    return t.reshape(2, -1)


def _butterfly_1q(chunk, dev, *, D, local_n, m_pair, gbit, loc_c=(),
                  loc_s=(), pred=None):
    """Single-qubit butterfly on GLOBAL bit `gbit` via one full-chunk
    pair exchange (ref statevec_compactUnitary distributed path,
    :846-881), sliced per QUEST_EXCHANGE_SLICES with the combine
    consuming each received slice independently. `m_pair` may be a
    TRACED (re, im) pair — only scalar selects touch it — which is how
    the adjoint engine (quest_tpu/adjoint.py) runs parametric rx/ry on
    a global target without leaving the sharded body."""
    mybit = (dev >> gbit) & 1
    mre = jnp.asarray(m_pair[0], dtype=chunk.dtype)
    mim = jnp.asarray(m_pair[1], dtype=chunk.dtype)
    # chunk with bit 0 holds "up" amps: new_up = m00*up + m01*lo;
    # bit 1 holds "lo": new_lo = m10*up + m11*lo
    dre = jnp.where(mybit == 0, mre[0, 0], mre[1, 1])
    die = jnp.where(mybit == 0, mim[0, 0], mim[1, 1])
    ore = jnp.where(mybit == 0, mre[0, 1], mre[1, 0])
    oie = jnp.where(mybit == 0, mim[0, 1], mim[1, 0])

    def combine(part, recv):
        re, im = part[0], part[1]
        rre, rim = recv[0], recv[1]
        return jnp.stack([
            dre * re - die * im + ore * rre - oie * rim,
            dre * im + die * re + ore * rim + oie * rre,
        ])

    s = C.effective_slices(chunk.shape[-1],
                           C.topology(D).link_of(gbit, D))
    if s == 1:
        recv = lax.ppermute(chunk, AMP_AXIS, _pair_perm(D, gbit))
        new = combine(chunk, recv)
    else:
        xs = chunk.reshape(2, s, -1)
        parts = []
        for i in range(s):
            recv = lax.ppermute(xs[:, i], AMP_AXIS,
                                _pair_perm(D, gbit))
            parts.append(combine(xs[:, i], recv))
        new = jnp.concatenate(parts, axis=1)
    return _mask_blend(new, chunk, local_n, loc_c, loc_s, pred)


def _matrix_op(chunk, dev, *, D, local_n, m_pair, targets, controls, cstates):
    """General k-qubit matrix gate on the local chunk, distributing over
    global target qubits when needed. Concrete operands with global
    targets are specialized by STRUCTURE before falling back to generic
    swap-to-local (the analogue of the reference's per-channel distributed
    kernels, QuEST_cpu_distributed.c:545-697):

    - diagonal matrix (dephasing-class superops, diagonal gates): routed
      as a diagonal op — ZERO communication. NOTE this deliberately
      exempts diagonal operands from the E_CANNOT_FIT_MULTI_QUBIT_MATRIX
      fit check below: the reference rejects any dense-form matrix whose
      global targets exceed the free local slots
      (QuEST_validation.c:121) because its kernels must relabel; the
      diagonal path needs no relabeling, so the same call SUCCEEDS here
      — a strict capability extension, tested in
      test_distributed.py::test_diagonal_matrix_exempt_from_fit_check;
    - two targets with exactly one global (outer-qubit channels whose
      column-space copy crosses the shard boundary, and crossing 2q
      gates): ONE direct pair exchange, shipping only the slices the
      cross-block actually reads (half-chunk for damping- AND
      depolarising-class channels — their cross-blocks each read one
      row-slice — full chunk for dense cross-blocks like generic
      crossing 2q unitaries; either way at most half of swap-to-local's
      swap-in + swap-out round trip).

    Measured (benchmarks/channel_bytes.py, 8-device mesh): outer-qubit
    damping 4096 -> 2048 bytes per channel; dephasing 4096 -> 0.

    The routing decision itself lives in comm.matrix_route — shared with
    the comm planner's predictor, so the planned exchange schedule
    cannot drift from what executes here."""
    sup = C.dense_operand(m_pair, len(targets))
    route = C.matrix_route(sup, tuple(targets), tuple(controls), local_n)

    if route[0] == "diagonal":
        return _diagonal_op(chunk, dev, local_n=local_n,
                            d_pair=cplx.pack(np.diagonal(sup)),
                            targets=targets, controls=(), cstates=())
    if route[0] == "pair2t":
        _, _, t, jg, gbit = route
        return _pair_exchange_2t(chunk, dev, D=D, local_n=local_n,
                                 sup=sup, t=t, jg=jg, gbit=gbit)

    if route[0] == "local":
        loc_c, loc_s, glob_c = _split_controls(controls, cstates, local_n)
        pred = _global_pred(dev, glob_c)
        # local controls are handled inside apply_matrix; only the global
        # predicate needs an outer blend
        new = A.apply_matrix(chunk, local_n, m_pair, targets, loc_c, loc_s)
        if pred is not None:
            new = jnp.where(pred, new, chunk)
        return new

    if route[0] == "butterfly":
        loc_c, loc_s, glob_c = _split_controls(controls, cstates, local_n)
        pred = _global_pred(dev, glob_c)
        return _butterfly_1q(chunk, dev, D=D, local_n=local_n,
                             m_pair=m_pair, gbit=route[1], loc_c=loc_c,
                             loc_s=loc_s, pred=pred)

    # multi-target with global targets: swap each global target into a local
    # position, apply locally, swap back (ref :1441-1483). Slots not holding
    # targets are eligible — including control qubits, whose role then moves
    # to the vacated global position (the reference's ctrlMask fixup under
    # relabeling, QuEST_cpu_distributed.c:1457-1466).
    glob_targets = [t for t in targets if t >= local_n]
    slots = [q for q in range(local_n) if q not in targets]
    ctrl_slots = set(controls)
    slots.sort(key=lambda q: (q in ctrl_slots, q))  # prefer non-control slots
    if len(slots) < len(glob_targets):
        from quest_tpu.validation import QuESTError
        raise QuESTError(
            "Invalid number of target qubits: the matrix cannot fit in a "
            f"single device chunk (targets {targets} need "
            f"{len(glob_targets)} local slots, only {len(slots)} exist; "
            "ref E_CANNOT_FIT_MULTI_QUBIT_MATRIX, QuEST_validation.c:121)")
    relabeled = list(targets)
    new_controls = list(controls)
    swaps = []
    for gt in glob_targets:
        l = slots.pop(0)
        swaps.append((gt - local_n, l))
        relabeled[relabeled.index(gt)] = l
        if l in ctrl_slots:  # control at slot l now lives at global pos gt
            new_controls[new_controls.index(l)] = gt
        chunk = _swap_global_local(chunk, dev, D, gt - local_n, l, local_n)
    loc_c, loc_s, glob_c = _split_controls(new_controls, cstates, local_n)
    pred = _global_pred(dev, glob_c)
    new = A.apply_matrix(chunk, local_n, m_pair, relabeled, loc_c, loc_s)
    if pred is not None:
        new = jnp.where(pred, new, chunk)
    chunk = new
    for gbit, l in reversed(swaps):
        chunk = _swap_global_local(chunk, dev, D, gbit, l, local_n)
    return chunk


def _diagonal_op(chunk, dev, *, local_n, d_pair, targets, controls, cstates):
    """Diagonal gate: never communicates. Global-target axes of the diagonal
    table are resolved by indexing with the device's fixed bit (the TPU
    analogue of the reference's global-index parity reads,
    QuEST_cpu.c:2940-3109)."""
    loc_c, loc_s, glob_c = _split_controls(controls, cstates, local_n)
    pred = _global_pred(dev, glob_c)
    k = len(targets)
    dre = jnp.asarray(d_pair[0], dtype=chunk.dtype).reshape((2,) * k)
    dim_ = jnp.asarray(d_pair[1], dtype=chunk.dtype).reshape((2,) * k)
    # diag index bit j <-> targets[j] <-> table axis (k-1-j). Reduce global
    # axes first (ascending j removes the highest remaining axis each time,
    # leaving lower axes untouched).
    for j in range(k):
        if targets[j] >= local_n:
            bit = (dev >> (targets[j] - local_n)) & 1
            dre = lax.dynamic_index_in_dim(dre, bit, axis=k - 1 - j,
                                           keepdims=False)
            dim_ = lax.dynamic_index_in_dim(dim_, bit, axis=k - 1 - j,
                                            keepdims=False)
    loc_targets = [t for t in targets if t < local_n]
    if loc_targets:
        new = A.apply_diagonal(chunk, local_n,
                               (dre.reshape(-1), dim_.reshape(-1)),
                               loc_targets, loc_c, loc_s)
        if pred is not None:
            new = jnp.where(pred, new, chunk)
        return new
    # d is a traced complex scalar pair
    re, im = chunk[0], chunk[1]
    new = jnp.stack([re * dre - im * dim_, re * dim_ + im * dre])
    return _mask_blend(new, chunk, local_n, loc_c, loc_s, pred)


def _parity_op(chunk, dev, *, local_n, targets, angle):
    """exp(-i angle/2 Z...Z): local sign tensor x traced global sign scalar."""
    rdt = chunk.dtype
    gsign = None
    for t in targets:
        if t >= local_n:
            s = 1.0 - 2.0 * ((dev >> (t - local_n)) & 1).astype(rdt)
            gsign = s if gsign is None else gsign * s
    loc = tuple(sorted((t for t in targets if t < local_n), reverse=True))
    dims, axis_of = A.seg_view(local_n, loc)
    sign = None
    for t in loc:
        shape = [1] * len(dims)
        shape[axis_of[t]] = 2
        vec = jnp.array([1.0, -1.0], dtype=rdt).reshape(shape)
        sign = vec if sign is None else sign * vec
    if sign is None:
        sign = jnp.ones((), dtype=rdt)
    if gsign is not None:
        sign = sign * gsign
    half = jnp.asarray(angle, dtype=rdt) / 2.0
    cosf = jnp.cos(half)
    sinf = jnp.sin(half) * sign
    re = chunk[0].reshape(dims)
    im = chunk[1].reshape(dims)
    nre = re * cosf + im * sinf
    nim = im * cosf - re * sinf
    return jnp.stack([nre.reshape(-1), nim.reshape(-1)])


def _all_ones_op(chunk, dev, *, local_n, term_pair, qubits):
    """Phase `term` on amplitudes whose listed qubits are ALL 1; global
    qubits contribute a per-device scalar predicate."""
    rdt = chunk.dtype
    glob = [(q - local_n, 1) for q in qubits if q >= local_n]
    loc = [q for q in qubits if q < local_n]
    tre = jnp.asarray(term_pair[0], dtype=rdt).reshape(())
    tim = jnp.asarray(term_pair[1], dtype=rdt).reshape(())
    pred = _global_pred(dev, glob)
    if pred is not None:
        tre = jnp.where(pred, tre, jnp.ones((), dtype=rdt))
        tim = jnp.where(pred, tim, jnp.zeros((), dtype=rdt))
    if loc:
        return A.apply_phase_on_all_ones(chunk, local_n, loc, (tre, tim))
    re, im = chunk[0], chunk[1]
    return jnp.stack([re * tre - im * tim, re * tim + im * tre])


def _pair_exchange_2t(chunk, dev, *, D, local_n, sup, t, jg, gbit):
    """Two-target operator with local target `t` and the other target on
    device bit `gbit` (matrix index bit `jg`): split the 4x4 operator by
    the global index bit into same-block and cross-block 2x2s, exchange
    only what the cross-block reads."""
    rdt = chunk.dtype
    g = (dev >> gbit) & 1

    # block split + the cross-blocks' read sets come from the comm
    # planner's shared helper, so the half-vs-full exchange decision
    # here and the predicted byte count are one computation
    same, cross, need = C.pair2t_blocks(sup, jg)

    def tr(mats):  # traced per-device 2x2 (re, im) pair
        p0, p1 = cplx.pack(mats[0]), cplx.pack(mats[1])
        sel = (g == 0)
        return (jnp.where(sel, jnp.asarray(p0[0], rdt), jnp.asarray(p1[0], rdt)),
                jnp.where(sel, jnp.asarray(p0[1], rdt), jnp.asarray(p1[1], rdt)))

    new = A.apply_matrix(chunk, local_n, tr(same), (t,))

    if all(len(nd) <= 1 for nd in need):
        # half-chunk exchange: each device ships the single row-slice its
        # partner reads (ref exchangePairStateVectorHalves semantics)
        nv = [nd[0] if nd else 0 for nd in need]
        dims, axis_of = A.seg_view(local_n, (t,))
        ax = 1 + axis_of[t]
        tview = chunk.reshape((2,) + dims)
        send_idx = jnp.where(g == 0, nv[1], nv[0])
        moving = lax.dynamic_slice_in_dim(tview, send_idx, 1, axis=ax)
        recv = _sliced_ppermute(moving.reshape(2, -1), D, gbit).reshape(
            moving.shape)
        # cross contribution: out(r) += cross[g][r, need[g]] * recv
        col = [np.asarray(cross[gv])[:, nv[gv]] for gv in (0, 1)]
        shape = [1] * len(dims)
        shape[axis_of[t]] = 2

        def coef(part):
            a = jnp.asarray(part(col[0]), rdt).reshape(shape)
            b = jnp.asarray(part(col[1]), rdt).reshape(shape)
            return jnp.where(g == 0, a, b)

        cre, cim = coef(np.real), coef(np.imag)
        rre, rim = recv[0], recv[1]
        add_re = cre * rre - cim * rim
        add_im = cre * rim + cim * rre
        out = new.reshape((2,) + dims)
        out = out.at[0].add(add_re).at[1].add(add_im)
        return out.reshape(2, -1)

    # dense cross-block (generic crossing 2q unitaries; 1q channels all
    # take the half-chunk branch above): one full-chunk exchange
    recv = _sliced_ppermute(chunk, D, gbit)
    return new + A.apply_matrix(recv, local_n, tr(cross), (t,))


def _relabel_op(chunk, *, local_n, slots):
    """Whole-register relabel event: swap every device bit j with local
    slot slots[j] in ONE all-to-all collective (bytes: (1 - 1/D) of the
    chunk — vs one whole-chunk pair exchange PER global 1q gate on the
    plain schedule, ref exchangeStateVectors,
    QuEST_cpu_distributed.c:481-509). The slot bits are transposed to a
    leading axis whose value equals the destination device index; the
    received blocks land at slot-bit positions equal to the SOURCE
    device index, which is the same layout — so the inverse transpose
    restores the standard chunk view. Planned by
    parallel.relabel.plan_full_relabels; validated bit-exactly against
    a host bit-swap oracle (tests/test_lazy_relabel.py)."""
    g = len(slots)
    # only the slot bits get their own axes; the bits between them stay
    # merged segments: a rank-(local_n + 1) view of size-2 axes pads
    # every tiny minor tile to the TPU's (8, 128), which at 22q on four
    # chips held 1 GiB of temporaries for an 8 MiB shard (PR 21)
    dims, axis_of = A.seg_view(local_n, tuple(sorted(slots, reverse=True)))
    axes_front = [1 + axis_of[q] for q in reversed(slots)]
    rest = [a for a in range(1, len(dims) + 1) if a not in axes_front]
    perm = [0] + axes_front + rest
    x = chunk.reshape((2,) + dims).transpose(perm).reshape(2, 1 << g, -1)
    y = lax.all_to_all(x, AMP_AXIS, split_axis=1, concat_axis=1)
    y = y.reshape((2,) + tuple(dims[a - 1] for a in perm[1:]))
    inv = np.argsort(perm)
    return y.transpose(list(inv)).reshape(2, -1)


def _apply_gateop(chunk, dev, *, D, local_n, density, op):
    """One GateOp (possibly + its conjugate column-space copy for density
    registers, ref QuEST.c:8-10) on the local chunk."""
    n = local_n + int(math.log2(D))
    shift = n // 2 if density else 0

    if op.kind == "relabel":
        return _relabel_op(chunk, local_n=local_n, slots=op.operand)

    if op.kind == "superop":
        # channel superoperator on [targets, targets+N]: one matrix op on
        # the doubled register, both spaces at once (no dual); _matrix_op
        # specializes by structure (diagonal / single-crossing-target)
        from quest_tpu.ops.matrices import superop_targets
        return _matrix_op(chunk, dev, D=D, local_n=local_n,
                          m_pair=cplx.pack(op.operand),
                          targets=list(superop_targets(op.targets, shift)),
                          controls=(), cstates=())

    def one(chunk, targets, controls, conj):
        if op.kind == "parity":
            ang = -op.operand if conj else op.operand
            return _parity_op(chunk, dev, local_n=local_n, targets=targets,
                              angle=ang)
        if op.kind == "allones":
            t = np.conj(op.operand) if conj else op.operand
            return _all_ones_op(chunk, dev, local_n=local_n,
                                term_pair=cplx.pack(t), qubits=targets)
        operand = np.conj(op.operand) if conj else op.operand
        pair = cplx.pack(operand)
        if op.kind == "diagonal":
            return _diagonal_op(chunk, dev, local_n=local_n, d_pair=pair,
                                targets=targets, controls=controls,
                                cstates=op.cstates)
        return _matrix_op(chunk, dev, D=D, local_n=local_n, m_pair=pair,
                          targets=targets, controls=controls,
                          cstates=op.cstates)

    chunk = one(chunk, op.targets, op.controls, conj=False)
    if density:
        chunk = one(chunk, tuple(t + shift for t in op.targets),
                    tuple(c + shift for c in op.controls), conj=True)
    return chunk


def engine_flat(ops: Sequence, n: int, density: bool, local_n: int,
                lazy: bool = False, relabel: bool = None,
                sched_stats: Optional[dict] = None,
                bands: Sequence = None,
                comm_info: Optional[dict] = None):
    """The flat op list the banded/fused sharded engines EXECUTE:
    flatten_ops plus the one relabel-rewrite policy. The single home of
    that policy — parallel.introspect reads plan statistics through
    this same function, so the reported schedule cannot drift from the
    executed one. relabel=None means AUTO under QUEST_COMM_PLAN
    (the comm planner picks the cheapest of plain/coalesce/
    relabel-events/lazy by predicted comm_stats bytes through the
    engine's own fusion-plan pricing — parallel/comm.py; `bands` is the
    calling engine's band layout so the pricing matches what it runs)
    and plan_full_relabels when the knob is off; requesting both lazy
    and relabel explicitly raises. `sched_stats`, when a dict, receives
    the scheduler's counters from the SAME scheduler run that produced
    the returned list; `comm_info` likewise receives the comm planner's
    strategy + per-candidate costs, plus — when the auto path ran —
    the winning candidate's fusion plan under "items" so callers don't
    re-run F.plan on the identical input (introspect's consumers)."""
    from quest_tpu.circuit import flatten_ops
    from quest_tpu.ops import fusion as F

    if lazy and relabel:
        raise ValueError("lazy and relabel are mutually exclusive "
                         "relabeling strategies; pick one")
    # the commutation-aware scheduler runs BEFORE relabel planning: a
    # reorder changes which qubits co-occur between exchanges, so the
    # relabel pass must see the order that will actually execute (its
    # composition-aware A/B guard then accepts or rejects events
    # against the SCHEDULED list; composed diagonals price at zero
    # exchange cost — diagonals never communicate at any position)
    flat0 = flatten_ops(ops, n, density)
    if sched_stats is None:
        flat = F.maybe_schedule(flat0, n)
    else:
        enabled = F._schedule_enabled()
        sched, stats = F.schedule(flat0, n)
        stats["enabled"] = enabled
        sched_stats.update(stats)
        flat = sched if enabled else list(flat0)
    if lazy:
        from quest_tpu.parallel.relabel import lazy_relabel_ops
        if comm_info is not None:
            comm_info.update({"strategy": "lazy"})
        return lazy_relabel_ops(flat, n, local_n)
    if relabel is None and C.plan_enabled():
        chosen, info = C.choose_plan(
            flat, n, local_n, engine="banded",
            bands=bands if bands is not None else _shard_bands(n, local_n))
        if comm_info is not None:
            comm_info.update(info)
        return chosen
    if relabel or relabel is None:
        from quest_tpu.parallel.relabel import plan_full_relabels
        if comm_info is not None:
            comm_info.update({"strategy": "relabel"})
        return plan_full_relabels(flat, n, local_n)
    if comm_info is not None:
        comm_info.update({"strategy": "plain"})
    return flat


def comm_plan_record(ops: Sequence, n: int, density: bool,
                     devices: int) -> dict:
    """The plan IR's 'comm' record (quest_tpu/plan.py; re-emitted
    bit-for-bit by Circuit.plan_stats): the comm planner's PREDICTED
    collective schedule for the banded/fused sharded engines over
    `devices`, built through the SAME policy home they execute
    (engine_flat + the comm predictor) so the report cannot drift from
    the lowered program. Pure host math — no mesh, no compile."""
    from quest_tpu import precision
    from quest_tpu.ops import fusion as F

    if devices < 2 or devices & (devices - 1):
        raise ValueError(
            f"devices must be a power of two >= 2, got {devices}")
    g = devices.bit_length() - 1
    local_n = n - g
    if local_n < 1:
        raise ValueError(
            f"register too small to shard over {devices} devices "
            f"(ref E_DISTRIB_QUREG_TOO_SMALL)")
    cinfo: dict = {}
    bands = _shard_bands(n, local_n)
    flat_r = engine_flat(ops, n, density, local_n,
                         bands=bands, comm_info=cinfo)
    items = cinfo.get("items")
    if items is None:
        items = F.plan(flat_r, n, bands=bands)
    rdt = precision.real_dtype_of(precision.get_default_dtype())
    topo = C.topology(devices)
    ici_b = topo.ici_bits(devices) if topo.hierarchical else None
    rec = C.comm_stats(C.predict_exchanges_items(items, local_n, ici_b),
                       num_devices=devices,
                       bytes_per_real=np.dtype(rdt).itemsize,
                       topo=topo)
    rec.update({
        "devices": devices,
        "comm_strategy": cinfo.get("strategy", "plain"),
        "comm_plan_enabled": C.plan_enabled(),
        "comm_topology": topo.describe(devices),
        "relabel_events": sum(1 for op in flat_r
                              if op.kind == "relabel"),
    })
    return rec


def pergate_flat(ops: Sequence, n: int, density: bool, local_n: int,
                 lazy: bool = False,
                 comm_info: Optional[dict] = None) -> List:
    """The flat op list the PER-GATE engine (compile_circuit_sharded)
    executes — flatten (duals explicit, superops doubled) plus the comm
    planner's per-circuit choice under QUEST_COMM_PLAN (priced per
    routed op, the per-gate engine's real cost: no band composition).
    The single home of that policy, shared with parallel.introspect so
    the reported per-gate schedule cannot drift from the executed one.
    lazy=True forces the legacy lazy rewrite; QUEST_COMM_PLAN=0 keeps
    the reference-faithful plain schedule."""
    from quest_tpu.circuit import flatten_ops
    from quest_tpu.parallel.relabel import lazy_relabel_ops

    flat = flatten_ops(ops, n, density)
    if lazy:
        if comm_info is not None:
            comm_info.update({"strategy": "lazy"})
        return lazy_relabel_ops(flat, n, local_n)
    if C.plan_enabled():
        chosen, info = C.choose_plan(flat, n, local_n, engine="pergate")
        if comm_info is not None:
            comm_info.update(info)
        return chosen
    if comm_info is not None:
        comm_info.update({"strategy": "plain"})
    return list(flat)


def _shard_bands(n: int, local_n: int):
    """Band layout aligned to the shard boundary: full-width bands inside
    the local chunk, width-1 bands for global (device-index) qubits — the
    distributed analogue of pallas_band.plan_bands, so composed runs stay
    local and each global qubit costs exactly one pair exchange."""
    from quest_tpu.ops.fusion import BAND_W
    bands = []
    ql = 0
    while ql < local_n:
        w = min(BAND_W, local_n - ql)
        bands.append((ql, w))
        ql += w
    for q in range(local_n, n):
        bands.append((q, 1))
    return bands


def fused_shard_bands(n: int, local_n: int):
    """The FUSED sharded engine's band layout, or None when the Pallas
    kernel cannot host the chunk (the engine then falls back to the
    banded layout). Shared by compile_circuit_sharded_fused and
    parallel.introspect so the reported plan cannot drift from the
    executed one: local bands follow the kernel's layout, global qubits
    get width-1 bands so each composes into one 2x2 pair exchange."""
    from quest_tpu.ops import pallas_band as PB
    if not PB.usable(local_n):
        return None
    return list(PB.plan_bands(local_n)) + [(q, 1)
                                           for q in range(local_n, n)]


def _band_op_sharded(chunk, dev, *, D, local_n, bop):
    """A composed BandOp on the sharded register: local bands apply as one
    in-chunk contraction; width-1 global bands ride the single-qubit pair
    exchange. Cross-shard controls become whole-chunk predicates."""
    if bop.ql >= local_n:          # global qubit: 2x2 via pair exchange
        return _matrix_op(chunk, dev, D=D, local_n=local_n,
                          m_pair=(bop.gre, bop.gim), targets=[bop.ql],
                          controls=[q for q, _ in bop.preds],
                          cstates=[s for _, s in bop.preds])
    loc_p = [(q, s) for q, s in bop.preds if q < local_n]
    glob_p = [(q - local_n, s) for q, s in bop.preds if q >= local_n]
    pred = _global_pred(dev, glob_p)
    new = A.apply_band(chunk, local_n, (bop.gre, bop.gim), bop.ql, bop.w,
                       loc_p)
    if pred is not None:
        new = jnp.where(pred, new, chunk)
    return new


def compile_circuit_sharded_banded(ops: Sequence, n: int, density: bool,
                                   mesh: Mesh, donate: bool = True,
                                   lazy: bool = False,
                                   relabel: bool = None):
    """Band-fusion engine over the mesh: the same planner that drives the
    single-chip engines (quest_tpu/ops/fusion.py), with bands aligned to
    the shard boundary. Commuting gate runs on local qubits compose into
    one contraction per band; global-qubit runs compose into one 2x2 per
    qubit (ONE ppermute pair exchange each — the reference would exchange
    once per gate, QuEST_cpu_distributed.c:846-881); cross-shard 2q
    unitaries KAK-decompose so their entangling content travels as
    communication-free parity phases.

    relabel (default on) runs the layer-amortized relabeling pass
    (parallel/relabel.py plan_full_relabels) — this engine is the f64
    pod path, and the whole-register all-to-all events cut its ICI the
    same way they cut the fused engine's: the event is a fusion BARRIER
    between band runs, so unlike lazy's per-qubit SWAPs it cannot break
    run composition. lazy=True instead rewrites through per-qubit lazy
    relabeling — measured COUNTERPRODUCTIVE here (1152 -> 1856 B on the
    deep-global testbed: the inserted SWAPs break band runs apart);
    kept for experimentation and mutually exclusive with relabel
    (requesting both explicitly raises)."""
    from quest_tpu.ops import fusion as F

    D = int(mesh.devices.size)
    g = int(math.log2(D))
    local_n = n - g
    _reject_measure_ops(ops)
    if local_n < 1:
        val._err(val.ErrorCode.E_DISTRIB_QUREG_TOO_SMALL)
    bands = _shard_bands(n, local_n)
    cinfo: dict = {}
    flat = engine_flat(ops, n, density, local_n, lazy=lazy, relabel=relabel,
                       bands=bands, comm_info=cinfo)
    items = cinfo.get("items")
    if items is None:
        items = F.plan(flat, n, bands=bands)

    def run(chunk):
        chunk = chunk.reshape(2, -1)
        dev = lax.axis_index(AMP_AXIS)
        for it in items:
            if isinstance(it, F.BandOp):
                chunk = _band_op_sharded(chunk, dev, D=D, local_n=local_n,
                                         bop=it)
            else:
                chunk = _apply_gateop(chunk, dev, D=D, local_n=local_n,
                                      density=False, op=it.op)
        return chunk

    sharded = compat.shard_map(run, mesh, P(None, AMP_AXIS),
                               P(None, AMP_AXIS))
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


def _apply_plan_item(chunk, dev, *, D, local_n, it):
    """One fusion-plan item (or bare GateOp) on the local chunk — the
    shared applier of the banded, fused and dynamic sharded engines."""
    from quest_tpu.ops import fusion as F
    if isinstance(it, F.BandOp):
        return _band_op_sharded(chunk, dev, D=D, local_n=local_n, bop=it)
    op = getattr(it, "op", it)
    return _apply_gateop(chunk, dev, D=D, local_n=local_n, density=False,
                         op=op)


def compile_plan_items_sharded(items, n: int, mesh: Mesh,
                               donate: bool = False):
    """One jitted shard_map program applying a SLICE of fusion-plan
    items to the sharded (2, 2^n) planes — the durable executor's
    per-step program (quest_tpu/resilience/durable.py): the full
    circuit's plan is cut at item boundaries (each item is one launch
    on this engine — a band contraction, a relabel all-to-all, a pair
    exchange) and each cut compiles through here, so an uninterrupted
    run and a resumed run execute the IDENTICAL program sequence and
    land on bit-identical amplitudes. Reuses the banded engine's
    shared applier (_apply_plan_item); donate defaults OFF because the
    caller snapshots the input for checkpoints."""
    D = int(mesh.devices.size)
    local_n = n - int(math.log2(D))
    if local_n < 1:
        val._err(val.ErrorCode.E_DISTRIB_QUREG_TOO_SMALL)
    items = tuple(items)

    def run(chunk):
        chunk = chunk.reshape(2, -1)
        dev = lax.axis_index(AMP_AXIS)
        for it in items:
            chunk = _apply_plan_item(chunk, dev, D=D, local_n=local_n,
                                     it=it)
        return chunk

    sharded = compat.shard_map(run, mesh, P(None, AMP_AXIS),
                               P(None, AMP_AXIS))
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


def plan_fused_structural(items, local_n: int):
    """Structural fused plan of a sharded item stream: maximal runs of
    purely-local fusion-plan items become ("segment", stages, arrays)
    parts via pallas_band.segment_plan; everything else is a
    ("sharded", item) entry (which also acts as a sweep barrier). Pure
    planning — nothing is compiled — shared by _plan_fused_parts below
    and parallel.introspect, so the reported per-shard sweep counts
    cannot drift from the executed ones."""
    from quest_tpu.ops import pallas_band as PB

    def local_only(it) -> bool:
        return all(q < local_n for q in it.qubits())

    parts = []
    run_items: list = []

    def close_run():
        nonlocal run_items
        if not run_items:
            return
        for sub in PB.segment_plan(run_items, local_n):
            if sub[0] == "segment":
                parts.append(sub)
            else:
                parts.append(("sharded", sub[1]))
        run_items = []

    for it in items:
        if local_only(it):
            run_items.append(it)
        else:
            close_run()
            parts.append(("sharded", it))
    close_run()
    return parts


def _plan_fused_parts(items, local_n: int, interpret: bool, seg_cache: dict):
    """Group maximal runs of purely-local fusion-plan items into Pallas
    kernel segments, sweep-fuse geometry-compatible consecutive
    segments into single-launch HBM sweeps (pallas_band.maybe_sweep —
    the PER-SHARD sweep decision, taken after relabel planning since
    engine_flat rewrites the op stream first), and compile each sweep.
    Returns [("kernel", applier, arrays) | ("sharded", item)]. Shared by
    the static fused engine and the dynamic (measured) engine's
    measurement-free stretches; `seg_cache` lets identical-structure
    sweeps across stretches share one compiled kernel."""
    from quest_tpu.ops import pallas_band as PB

    parts = []
    for sub in PB.maybe_sweep(plan_fused_structural(items, local_n),
                              local_n):
        if sub[0] == "segment":
            seg = PB.compile_segment_cached(seg_cache, sub[1], local_n,
                                            interpret=interpret)
            parts.append(("kernel", seg, sub[2]))
        else:
            parts.append(sub)
    return parts


def compile_circuit_sharded_fused(ops: Sequence, n: int, density: bool,
                                  mesh: Mesh, donate: bool = True,
                                  interpret: bool = False,
                                  relabel: bool = None):
    """The Pallas band-segment engine over the device mesh: the pod-scale
    composition of the two fastest paths in the framework. Runs of
    purely-local fused items (band contractions, diagonals, phases, pair
    stages whose qubits and control predicates all sit inside the chunk)
    execute as mega-kernel segments — many operators per HBM pass per
    device, exactly as on one chip (quest_tpu/ops/pallas_band.py) —
    while items touching global (device-index) qubits ride the explicit
    ppermute schedule between segments. The reference has no analogue:
    its distributed backend dispatches one kernel per gate per rank
    (QuEST_cpu_distributed.c:846-881); here a whole local stretch of an
    RCS layer is one kernel launch on every device simultaneously.

    relabel=True (default) first rewrites the flat ops through the
    layer-amortized relabeling pass (parallel/relabel.py
    plan_full_relabels): stretches of global-qubit matrix work run
    locally between whole-register all-to-all events, cutting both the
    collective count and the ICI bytes of deep circuits (the pass
    leaves cheap schedules untouched — events only fire where they pay
    for themselves).

    interpret=True runs the kernels in the Pallas interpreter (CPU-mesh
    testing)."""
    from quest_tpu.ops import fusion as F
    from quest_tpu.ops import pallas_band as PB

    D = int(mesh.devices.size)
    g = int(math.log2(D))
    local_n = n - g
    _reject_measure_ops(ops)
    if local_n < 1:
        val._err(val.ErrorCode.E_DISTRIB_QUREG_TOO_SMALL)
    bands = fused_shard_bands(n, local_n)
    if bands is None:
        # the Pallas kernel cannot host this chunk: banded fallback,
        # forwarding `relabel` so a plain-vs-relabeled ablation stays
        # honest. NOT silent when the caller asked for interpret-mode
        # kernels — those do not exist on the banded path, and a
        # dropped flag here once turned a relabel test into a false
        # positive (caught in review, r4)
        if interpret:
            import sys
            print(f"[sharded] local_n={local_n} below the kernel tier's "
                  f"minimum: falling back to the BANDED engine; the "
                  f"interpret argument does not apply there",
                  file=sys.stderr)
        return compile_circuit_sharded_banded(ops, n, density, mesh,
                                              donate, relabel=relabel)

    cinfo: dict = {}
    flat = engine_flat(ops, n, density, local_n, relabel=relabel,
                       bands=bands, comm_info=cinfo)
    items = cinfo.get("items")
    if items is None:
        items = F.plan(flat, n, bands=bands)
    parts = _plan_fused_parts(items, local_n, interpret, {})

    def apply_sharded_item(chunk, dev, it):
        return _apply_plan_item(chunk, dev, D=D, local_n=local_n, it=it)

    def run(chunk):
        chunk = chunk.reshape(2, -1)
        dev = lax.axis_index(AMP_AXIS)
        if chunk.dtype != jnp.float32:
            # the kernels are f32-only; f64 registers keep full precision
            # on the XLA banded schedule over the same plan
            for it in items:
                chunk = apply_sharded_item(chunk, dev, it)
            return chunk
        for part in parts:
            if part[0] == "kernel":
                out = part[1](chunk.reshape(2, -1, PB.LANES), part[2])
                chunk = out.reshape(2, -1)
            else:
                chunk = apply_sharded_item(chunk, dev, part[1])
        return chunk

    # check_vma=False: pallas_call's out_shape carries no varying-mesh-axes
    # annotation, and every value here is explicitly per-device anyway
    sharded = compat.shard_map(run, mesh, P(None, AMP_AXIS),
                               P(None, AMP_AXIS), check_vma=False)
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


def compile_circuit_sharded_fused_batched(ops: Sequence, n: int,
                                          density: bool, mesh: Mesh,
                                          batch: int, donate: bool = True,
                                          interpret: bool = False,
                                          relabel: bool = None):
    """BATCHED Pallas fused engine over the mesh: fn((B, 2, 2^n) planes
    sharded as P(None, None, AMP_AXIS)) — the batch axis stays LOCAL to
    the amplitude mesh, so every device holds all B states of ITS
    amplitude shard. Purely-local runs execute as batched sweep
    launches per device (one leading batch grid dimension,
    pallas_band.compile_segment batch=B): the per-shard launch count of
    a B-shot workload does not scale with B, exactly like the
    single-chip batched engine. Items touching global (device-index)
    qubits ride the explicit collective schedule jax.vmap'ed over the
    batch — a ppermute/all-to-all with a leading batch axis moves B
    messages over the SAME device permutation, no extra collectives.
    f64 registers fall back to the vmapped banded schedule over the
    same plan; below the kernel tier every item runs vmapped-banded."""
    from quest_tpu.ops import fusion as F
    from quest_tpu.ops import pallas_band as PB

    D = int(mesh.devices.size)
    g = int(math.log2(D))
    local_n = n - g
    _reject_measure_ops(ops)
    if local_n < 1:
        val._err(val.ErrorCode.E_DISTRIB_QUREG_TOO_SMALL)
    bands = fused_shard_bands(n, local_n)
    eff_bands = bands if bands is not None else _shard_bands(n, local_n)
    cinfo: dict = {}
    flat = engine_flat(ops, n, density, local_n, relabel=relabel,
                       bands=eff_bands, comm_info=cinfo)
    items = cinfo.get("items")
    if items is None:
        items = F.plan(flat, n, bands=eff_bands)
    parts = None
    if bands is not None:
        parts = []
        seg_cache: dict = {}
        for sub in PB.maybe_sweep(plan_fused_structural(items, local_n),
                                  local_n):
            if sub[0] == "segment":
                seg = PB.compile_segment_cached(
                    seg_cache, tuple(sub[1]), local_n,
                    interpret=interpret, batch=batch)
                parts.append(("kernel", seg, sub[2]))
            else:
                parts.append(sub)
    elif interpret:
        import sys
        print(f"[sharded] batched engine: local_n={local_n} below the "
              f"kernel tier's minimum; every item runs on the vmapped "
              f"BANDED schedule (interpret does not apply there)",
              file=sys.stderr)

    def run(chunkb):
        chunkb = chunkb.reshape(batch, 2, -1)
        dev = lax.axis_index(AMP_AXIS)

        def vmapped(it):
            return jax.vmap(lambda ch, it=it: _apply_plan_item(
                ch, dev, D=D, local_n=local_n, it=it))
        if parts is None or chunkb.dtype != jnp.float32:
            for it in items:
                chunkb = vmapped(it)(chunkb)
            return chunkb
        for part in parts:
            if part[0] == "kernel":
                out = part[1](chunkb.reshape(batch, 2, -1, PB.LANES),
                              part[2])
                chunkb = out.reshape(batch, 2, -1)
            else:
                chunkb = vmapped(part[1])(chunkb)
        return chunkb

    sharded = compat.shard_map(run, mesh, P(None, None, AMP_AXIS),
                               P(None, None, AMP_AXIS), check_vma=False)
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


def _reject_measure_ops(ops):
    """The static sharded schedules don't thread keys/outcomes; dynamic
    circuits have their own compiler. One shared rejection for the three
    static sharded compilers."""
    if any(op.kind in ("measure", "measure_dm", "classical") for op in ops):
        from quest_tpu.validation import QuESTError
        raise QuESTError(
            "Invalid operation: this circuit contains mid-circuit "
            "measurements; use compile_circuit_sharded_measured (or "
            "Circuit.apply_sharded_measured) for dynamic circuits on the "
            "mesh.")


def compile_circuit_sharded(ops: Sequence, n: int, density: bool, mesh: Mesh,
                            donate: bool = True, lazy: bool = False):
    """Compile a gate sequence into ONE shard_map program over the mesh —
    the explicit, reference-faithful distributed schedule. Returns a jitted
    fn: sharded (2, 2^n) planes -> sharded (2, 2^n) planes.

    lazy=True first rewrites the (flattened) op list through lazy qubit
    relabeling (quest_tpu.parallel.relabel): global-target gates swap
    their qubit local and LEAVE it there, amortizing exchanges across
    depth (~2x less ICI on deep circuits; the reference swap-dances
    every gate, QuEST_cpu_distributed.c:1441-1483)."""
    D = int(mesh.devices.size)
    g = int(math.log2(D))
    local_n = n - g
    _reject_measure_ops(ops)
    if local_n < 1:
        val._err(val.ErrorCode.E_DISTRIB_QUREG_TOO_SMALL)
    if not density and any(op.kind == "superop" for op in ops):
        from quest_tpu.validation import QuESTError
        raise QuESTError(
            "Invalid operation: noise channels require a density-matrix "
            "register")
    if lazy or C.plan_enabled():
        # flatten + rewrite through the per-gate comm policy (the comm
        # planner's per-circuit choice, or the legacy lazy rewrite);
        # duals are explicit in the flattened list
        ops = tuple(pergate_flat(ops, n, density, local_n, lazy=lazy))
        density = False
    else:
        ops = tuple(ops)

    def run(chunk):
        chunk = chunk.reshape(2, -1)
        dev = lax.axis_index(AMP_AXIS)
        for op in ops:
            chunk = _apply_gateop(chunk, dev, D=D, local_n=local_n,
                                  density=density, op=op)
        return chunk

    sharded = compat.shard_map(run, mesh, P(None, AMP_AXIS),
                               P(None, AMP_AXIS))
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


def _measure_op_sharded(chunk, dev, key, *, D, local_n, qubit, density,
                        eps):
    """Mid-circuit measurement inside the shard_map schedule: local
    partial probability + psum (the reference's MPI_Allreduce,
    QuEST_cpu_distributed.c:1263-1277), identical outcome draw on every
    device (same key), local branchless collapse — including GLOBAL
    qubits, where a device's whole chunk lives on one side of the
    butterfly and either renormalizes or zeroes."""
    n = local_n + int(math.log2(D))
    if density:
        # diagonal probability: rho[k,k] with bit `qubit` of k == 0.
        # col bits are the TOP half; this shard holds cols [c0, c0+cols)
        dim = 1 << (n // 2)
        cols_local = chunk.shape[1] // dim
        c0 = dev * cols_local
        mat = chunk[0].reshape(cols_local, dim)
        idx = c0 + jnp.arange(cols_local)
        diag = jnp.take_along_axis(mat, idx[:, None], axis=1)[:, 0]
        keep = ((idx >> qubit) & 1) == 0
        p0 = lax.psum(jnp.sum(jnp.where(keep, diag, 0.0)), AMP_AXIS)
    elif qubit < local_n:
        pre, post = 1 << (local_n - 1 - qubit), 1 << qubit
        re = chunk[0].reshape(pre, 2, post)[:, 0, :]
        im = chunk[1].reshape(pre, 2, post)[:, 0, :]
        p0 = lax.psum(jnp.sum(re * re + im * im), AMP_AXIS)
    else:
        mybit = (dev >> (qubit - local_n)) & 1
        local = jnp.sum(chunk * chunk)
        p0 = lax.psum(jnp.where(mybit == 0, local, 0.0), AMP_AXIS)

    key, sub = jax.random.split(key)
    u = jax.random.uniform(sub, dtype=p0.dtype)
    outcome = jnp.where(p0 < eps, 1,
                        jnp.where(1.0 - p0 < eps, 0,
                                  (u > p0).astype(jnp.int32)))
    prob = jnp.maximum(jnp.where(outcome == 0, p0, 1.0 - p0), eps)

    rdt = chunk.dtype
    if density:
        nq = n // 2
        qubits = tuple(sorted({qubit, qubit + nq}, reverse=True))
        dims, axis_of = A.seg_view(local_n, tuple(q for q in qubits
                                                  if q < local_n))
        mask = None
        for q in qubits:
            if q < local_n:
                m = A.bit_tensor(len(dims), axis_of[q]) == outcome
            else:
                m = ((dev >> (q - local_n)) & 1) == outcome
            mask = m if mask is None else mask & m
        factor = jnp.where(mask, 1.0 / prob, 0.0).astype(rdt)
        new = jnp.stack([chunk[0].reshape(dims) * factor,
                         chunk[1].reshape(dims) * factor])
        return new.reshape(2, -1), key, outcome
    if qubit < local_n:
        dims, axis_of = A.seg_view(local_n, (qubit,))
        keep = A.bit_tensor(len(dims), axis_of[qubit]) == outcome
        factor = keep.astype(rdt) * lax.rsqrt(prob).astype(rdt)
        new = jnp.stack([chunk[0].reshape(dims) * factor,
                         chunk[1].reshape(dims) * factor])
        return new.reshape(2, -1), key, outcome
    mybit = (dev >> (qubit - local_n)) & 1
    factor = jnp.where(mybit == outcome,
                       lax.rsqrt(prob), 0.0).astype(rdt)
    return chunk * factor, key, outcome


def plan_measured_program(flat: Sequence, n: int, local_n: int,
                          engine: str, relabel: bool,
                          interpret: bool = False):
    """The dynamic engine's executable plan: split the FLAT op list at
    dynamic barriers (measure/classical), run the layer-amortized
    relabel pass per measurement-free stretch (each stretch restores
    standard order, so barriers always see logical qubit positions),
    and band/kernel-plan each stretch per `engine`. Returns (program,
    resolved_engine) where program is a list of ("dyn", op) |
    ("stretch", items, parts-or-None) elements. The ONE home of this
    planning — compile_circuit_sharded_measured executes it and
    parallel.introspect reports it, so the reported schedule cannot
    drift from the executed one."""
    from quest_tpu.ops import fusion as F

    bands = None
    if engine == "fused":
        bands = fused_shard_bands(n, local_n)
        if bands is None:
            # chunk below the kernel tier: banded fallback — LOUD when
            # the caller asked for interpret-mode kernels, exactly like
            # the static fused compiler (a silent version of this
            # fallback turned a relabel test into a false positive, r4)
            if interpret:
                import sys
                print(f"[sharded] dynamic engine: local_n={local_n} "
                      f"below the kernel tier's minimum; falling back "
                      f"to the BANDED engine (interpret does not apply "
                      f"there)", file=sys.stderr)
            engine = "banded"
    if engine == "banded":
        bands = _shard_bands(n, local_n)

    program = []        # ("dyn", op) | ("stretch", items, parts|None)
    seg_cache: dict = {}

    def close_stretch(stretch):
        if not stretch:
            return
        if engine != "xla":
            # per-stretch scheduling: each measurement-free stretch is a
            # static sub-schedule, reordered/composed before its relabel
            # pass exactly like the static engines (barriers themselves
            # never move — the stretch split happens first)
            stretch = F.maybe_schedule(stretch, n)
        if relabel:
            from quest_tpu.parallel.relabel import plan_full_relabels
            stretch = plan_full_relabels(stretch, n, local_n)
        if engine == "xla":
            program.append(("stretch", stretch, None))
            return
        items = F.plan(stretch, n, bands=bands)
        parts = (_plan_fused_parts(items, local_n, interpret, seg_cache)
                 if engine == "fused" else None)
        program.append(("stretch", items, parts))

    cur: list = []
    for op in flat:
        if op.kind in ("measure", "measure_dm", "classical"):
            close_stretch(cur)
            cur = []
            program.append(("dyn", op))
        else:
            cur.append(op)
    close_stretch(cur)
    return program, engine


def resolve_measured_engine(engine, relabel, banded: bool = False):
    """The ONE home of the dynamic engine's argument defaulting —
    engine=None means 'xla' (or 'banded' via the legacy bool), relabel
    defaults on for the fusing engines. Shared by the compiler below and
    Circuit.compiled_sharded_measured's cache key so equivalent calls
    always resolve to (and cache as) the same program."""
    if engine is None:
        engine = "banded" if banded else "xla"
    if engine not in ("xla", "banded", "fused"):
        raise ValueError(f"engine must be 'xla', 'banded' or 'fused', "
                         f"got {engine!r}")
    if relabel is None:
        relabel = engine in ("banded", "fused")
    return engine, relabel


def compile_circuit_sharded_measured(ops: Sequence, n: int, density: bool,
                                     mesh: Mesh, donate: bool = True,
                                     banded: bool = False,
                                     engine: str = None,
                                     relabel: bool = None,
                                     interpret: bool = False):
    """DYNAMIC circuit over the mesh: one shard_map program taking
    (sharded planes, key) and returning (planes, outcomes) — mid-circuit
    measurement (psum'd probabilities, identical draws everywhere, local
    collapse even for device-index qubits) and classical feedback, at
    pod scale. The reference must host-round-trip AND MPI-broadcast per
    measurement, and its measurement path communicates per-gate and
    fuses nothing (QuEST_cpu_distributed.c:1244-1319); here the entire
    dynamic program is one compiled dispatch AND the measurement-free
    stretches get the full static-engine treatment:

    engine: 'xla' (per-gate), 'banded' (band-fusion between measurement
    barriers), or 'fused' (banded + Pallas mega-kernel segments for the
    purely-local runs, exactly like compile_circuit_sharded_fused; f64
    registers fall back to the banded schedule over the same plan).
    The legacy `banded` bool maps to engine='banded'.

    relabel (default ON for banded/fused): each measurement-free stretch
    is a static sub-schedule — the layer-amortized relabel pass
    (parallel/relabel.py plan_full_relabels) runs PER STRETCH, so deep
    global-qubit work between measurements rides whole-register
    all-to-all events instead of per-gate exchanges. Every stretch
    restores standard qubit order before its barrier, so measurements
    and classical feedback always see logical positions (the
    'measured qubit in standard position' contract, VERDICT r4 item 4);
    the pass only emits events where they pay for themselves, so cheap
    stretches are untouched."""
    from quest_tpu import precision as _prec
    from quest_tpu.circuit import flatten_ops
    from quest_tpu.ops import fusion as F

    engine, relabel = resolve_measured_engine(engine, relabel, banded)

    D = int(mesh.devices.size)
    g = int(math.log2(D))
    local_n = n - g
    if local_n < 1:
        val._err(val.ErrorCode.E_DISTRIB_QUREG_TOO_SMALL)
    if density and (1 << (n // 2)) < D:
        from quest_tpu.validation import QuESTError
        raise QuESTError(
            "Invalid operation: dynamic density circuits need at least "
            "one density-matrix column per device (2^numQubits >= mesh "
            "size) so each shard can read its diagonal slice; use fewer "
            "devices or the static engine + eager measurement.")
    flat = flatten_ops(ops, n, density)
    n_meas = sum(1 for op in flat
                 if op.kind in ("measure", "measure_dm"))
    if not n_meas:
        from quest_tpu.validation import QuESTError
        raise QuESTError(
            "Invalid operation: compile_circuit_sharded_measured requires "
            "at least one mid-circuit measurement; use "
            "compile_circuit_sharded instead.")

    program, engine = plan_measured_program(flat, n, local_n, engine,
                                            relabel, interpret)

    def run(chunk, key):
        chunk = chunk.reshape(2, -1)
        dev = lax.axis_index(AMP_AXIS)
        eps = jnp.asarray(_prec.real_eps(chunk.dtype), dtype=chunk.dtype)
        use_kernels = chunk.dtype == jnp.float32
        outs = []
        for el in program:
            if el[0] == "dyn":
                op = el[1]
                if op.kind in ("measure", "measure_dm"):
                    chunk, key, oc = _measure_op_sharded(
                        chunk, dev, key, D=D, local_n=local_n,
                        qubit=op.targets[0],
                        density=op.kind == "measure_dm", eps=eps)
                    outs.append(oc)
                else:                       # classical feedback
                    inners, conds = op.operand
                    pred = None
                    for idx, want in conds:
                        p = outs[idx] == want
                        pred = p if pred is None else pred & p
                    new = chunk
                    for gop in inners:
                        new = _apply_gateop(new, dev, D=D, local_n=local_n,
                                            density=False, op=gop)
                    chunk = jnp.where(pred, new, chunk)
                continue
            _, items, parts = el
            if parts is not None and use_kernels:
                from quest_tpu.ops import pallas_band as PB
                for part in parts:
                    if part[0] == "kernel":
                        out = part[1](chunk.reshape(2, -1, PB.LANES),
                                      part[2])
                        chunk = out.reshape(2, -1)
                    else:
                        chunk = _apply_plan_item(chunk, dev, D=D,
                                                 local_n=local_n,
                                                 it=part[1])
            else:
                for it in items:
                    chunk = _apply_plan_item(chunk, dev, D=D,
                                             local_n=local_n, it=it)
        return chunk, jnp.stack(outs)

    sharded = compat.shard_map(run, mesh,
                               (P(None, AMP_AXIS), P()),
                               (P(None, AMP_AXIS), P()),
                               check_vma=engine != "fused")
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


def apply_circuit_sharded(q: Qureg, ops: Sequence, mesh: Mesh,
                          donate: bool = True) -> Qureg:
    """One-shot convenience wrapper around compile_circuit_sharded."""
    from quest_tpu.parallel.mesh import amp_sharding
    from quest_tpu.resilience import faults as _F
    # named fault site (docs/RESILIENCE.md): the mesh dispatch is the
    # sharded analogue of the serve engine's launch — soak runs inject
    # here to prove callers surface (not swallow) multi-device failures.
    # One module-flag read when no plan is armed.
    if _F.ACTIVE:
        _F.check("sharded.dispatch", num_qubits=q.num_qubits,
                 num_ops=len(ops))
    fn = compile_circuit_sharded(ops, q.num_state_qubits, q.is_density, mesh,
                                 donate)
    amps = jax.device_put(q.amps, amp_sharding(mesh))
    return q.replace_amps(fn(amps))
