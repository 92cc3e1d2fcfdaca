"""The plain reference and the comparison that decides `correct`.

Nothing here imports the program. The reference holds the register on
the device as f32 re/im planes, (2, 2^(n-L), 2^L), in a layout of its own
(registers/<kind>.py says which state bit sits at which position), and
applies the circuit in passes that each stream the state once, chunk by
chunk in place, so that it fits beside nothing: at 30 bits the state is
8 GiB of the chip's 15.75 GiB.

Gates on disjoint positions commute, so consecutive ops are multiplied
into one dense matrix per fixed window of positions (textbook Kronecker
algebra, 2^w x 2^w with w <= 8) and each window is one matmul pass at
HIGHEST precision. The gates' matrices come from qbench/gates. An op
that straddles two windows is applied on its own: a diagonal one (a CZ
across a window edge) as an elementwise phase pass, any other (a swap
too) as one chunked in-place pass over the positions it touches.

The comparison is a seeded sketch of the whole output: 16 projections of
every amplitude onto pseudo-random signs of its natural index, taken the
same way of the program's state and of the reference's. The compared
number `proj_gap` is the widest projection gap over the reference's norm:
about the relative L2 error of the output, so a wrong gate, a skipped
block or a lower precision shows in it.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

SKETCH = 16               # projections in the sketch
CHUNK = 1 << 24           # elements per chunk of a reference pass (128 MiB)
SKETCH_CHUNK = 1 << 20    # elements per chunk of the sketch
CROSS_CHUNK = CHUNK // 8  # of a cross pass, whose axes pad up to 8 times

# -- passes ------------------------------------------------------------------

def windows(n: int, lane_bits: int, width: int, align: int = 1):
    """[0, lane_bits) then windows of at most `width` positions above it,
    each starting at a multiple of `align`."""
    out, lo = [(0, lane_bits)], lane_bits
    while lo < n:
        hi = min(n, (lo + width) // align * align)
        out.append((lo, hi))
        lo = hi
    return out


def _embed(mat: np.ndarray, pos: Sequence[int], lo: int, w: int):
    """The 2^w matrix of `mat` on window-local bits pos - lo."""
    k = len(pos)
    idx = np.arange(1 << w)
    local = [p - lo for p in pos]
    sub = sum(((idx >> b) & 1) << j for j, b in enumerate(local))
    rest = idx & ~sum(1 << b for b in local)
    same = rest[:, None] == rest[None, :]
    full = mat.reshape(1 << k, 1 << k)[sub[:, None], sub[None, :]]
    return np.where(same, full, 0)


def plan_passes(ops, wins) -> List[tuple]:
    """Lowered ops [(positions, matrix, is_diagonal)] in circuit order to
    passes [("window", {(lo, hi): matrix}) | ("diag", [(positions, d)]) |
    ("cross", [(positions, matrix)])]."""
    passes: List[tuple] = []
    mats: Dict[tuple, np.ndarray] = {}
    diag: List[tuple] = []

    def flush():
        nonlocal mats, diag
        if mats:
            passes.append(("window", mats))
        if diag:
            passes.append(("diag", diag))
        mats, diag = {}, []

    for pos, mat, is_diag in ops:
        win = next((w for w in wins if w[0] <= min(pos) and max(pos) < w[1]),
                   None)
        # diagonal ops commute: one across windows joins the pending ones
        if (win is not None or not is_diag) and \
                set(pos) & {p for d_pos, _ in diag for p in d_pos}:
            flush()
        if win is not None:
            lo, hi = win
            g = _embed(mat, pos, lo, hi - lo)
            mats[win] = g @ mats[win] if win in mats else g
        elif is_diag:
            diag.append((tuple(pos), np.diag(mat).copy()))
        else:
            flush()
            passes.append(("cross", [(pos, mat)]))
    flush()
    return passes


# -- the reference program ----------------------------------------------------
#
# The state is two f32 planes (2, R, 2^L), re and im: XLA on the TPU splits
# a complex64 array into two such planes as temporaries, which a register
# of 8 GiB has no room for, so the reference keeps them split itself.


def _cmul(spec, g, c):
    """Complex einsum of a (2, ...) matrix with (2, ...) planes, HIGHEST."""
    def mm(a, b):
        return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)
    return jnp.stack([mm(g[0], c[0]) - mm(g[1], c[1]),
                      mm(g[0], c[1]) + mm(g[1], c[0])])


def _window_pass(x, g, lo, hi, lane_bits):
    """x: (2, R, 2^lane_bits) f32 planes, g: (2, 2^w, 2^w). In place, by
    chunks."""
    _, rows, lanes = x.shape
    if lo == 0:
        rc = max(1, min(rows, CHUNK // lanes))

        def body(i, x):
            c = lax.dynamic_slice_in_dim(x, i * rc, rc, 1)
            c = _cmul("ij,rj->ri", g, c)
            return lax.dynamic_update_slice_in_dim(x, c, i * rc, 1)
        return lax.fori_loop(0, rows // rc, body, x)

    w, cbits = hi - lo, lo - lane_bits            # C' = rows below the window
    big_w, cp = 1 << w, 1 << cbits
    a = rows // (big_w * cp)
    per_a = big_w * cp * lanes
    if per_a <= CHUNK:                             # whole groups of a
        na = min(a, CHUNK // per_a)
        v = x.reshape(2, a, big_w * cp, lanes)

        def body(i, v):
            c = lax.dynamic_slice_in_dim(v, i * na, na, 1)
            c = _cmul("ji,aim->ajm", g, c.reshape(2, na, big_w, cp * lanes))
            return lax.dynamic_update_slice_in_dim(
                v, c.reshape(2, na, big_w * cp, lanes), i * na, 1)
        return lax.fori_loop(0, a // na, body, v).reshape(x.shape)

    cs = CHUNK // (big_w * lanes)                  # one a, a slice of C'
    nc = cp // cs
    v = x.reshape(2, a, big_w, cp, lanes)

    def body(i, v):
        start = (0, i // nc, 0, (i % nc) * cs, 0)
        c = lax.dynamic_slice(v, start, (2, 1, big_w, cs, lanes))
        c = _cmul("ji,im->jm", g, c.reshape(2, big_w, cs * lanes))
        return lax.dynamic_update_slice(
            v, c.reshape(2, 1, big_w, cs, lanes), start)
    return lax.fori_loop(0, a * nc, body, v).reshape(x.shape)


def _cross_operand(mat, pos, lane_bits):
    """An op across windows as (its row bits, its matrix) for _cross_pass:
    local bits reordered so that its lane positions come first, then
    (2^r, 2^r) over the r row bits where it touches no lane, else
    (2^r, 2^L, 2^r, 2^L), each row-bit block embedded on the lanes."""
    k = len(pos)
    order = sorted(range(k), key=lambda j: pos[j] >= lane_bits)
    idx = np.arange(1 << k)
    old = sum(((idx >> t) & 1) << j for t, j in enumerate(order))
    g = np.asarray(mat)[old[:, None], old[None, :]]
    lane_pos = [pos[j] for j in order if pos[j] < lane_bits]
    rows = tuple(pos[j] - lane_bits for j in order if pos[j] >= lane_bits)
    if not lane_pos:
        return rows, g
    r, kl = 1 << len(rows), 1 << len(lane_pos)
    blocks = g.reshape(r, kl, r, kl).transpose(0, 2, 1, 3)
    big = np.stack([np.stack([_embed(b, lane_pos, 0, lane_bits) for b in row])
                    for row in blocks])
    return rows, big.transpose(0, 2, 1, 3)


def _cross_pass(x, g, row_bits, lane_bits):
    """x: (2, R, 2^lane_bits) f32 planes; g, row_bits: _cross_operand's. In
    place, by chunks: each chunk is a block of 2^c consecutive rows for
    each value of the op's row bits at or above c; the op's lower row
    bits become axes of the chunk, and the lanes stay the minor axis (one
    with fewer than 8 rows above it pads at most 8 times)."""
    _, rows, lanes = x.shape
    r = len(row_bits)
    c = min(rows.bit_length() - 1,
            max(0, (CROSS_CHUNK // lanes).bit_length() - 1 - r))
    high = [b for b in row_bits if b >= c]
    low = sorted((b for b in row_bits if b < c), reverse=True)
    offsets = [sum(((m >> t) & 1) << b for t, b in enumerate(high))
               for m in range(1 << len(high))]
    # the chunk's axes: high bits (high[-1] first), then the rows split
    # at each low bit, then the lanes
    sizes, axis, top = [2] * len(high), {}, c
    for t, b in enumerate(high):
        axis[b] = len(high) - 1 - t
    for b in low:
        sizes += [1 << (top - b - 1), 2]
        axis[b] = len(sizes) - 1
        top = b
    sizes.append(1 << top)
    op_axes = [axis[b] for b in reversed(row_bits)]
    order = op_axes + [a for a in range(len(sizes)) if a not in op_axes]
    perm = [0] + [a + 1 for a in order] + [len(sizes) + 1]
    moved = [2] + [sizes[a] for a in order] + [lanes]
    spec = "Rr,rsl->Rsl" if g.ndim == 3 else "RLrl,rsl->RsL"

    def base(i):
        v = i << c
        for b in sorted(high):
            v = ((v >> b) << (b + 1)) | (v & ((1 << b) - 1))
        return v

    def body(i, x):
        start = base(i)
        blocks = jnp.stack([lax.dynamic_slice_in_dim(x, start + o, 1 << c, 1)
                            for o in offsets], 1)
        v = blocks.reshape(2, *sizes, lanes).transpose(perm)
        v = _cmul(spec, g, v.reshape(2, 1 << r, -1, lanes))
        v = v.reshape(moved).transpose(np.argsort(perm)).reshape(blocks.shape)
        for m, o in enumerate(offsets):
            x = lax.dynamic_update_slice_in_dim(x, v[:, m], start + o, 1)
        return x
    return lax.fori_loop(0, rows >> (c + len(high)), body, x)


def _positions(i, rc, lanes):
    r = lax.broadcasted_iota(jnp.uint32, (rc, lanes), 0)
    l = lax.broadcasted_iota(jnp.uint32, (rc, lanes), 1)
    return (r + (i * rc).astype(jnp.uint32)) * jnp.uint32(lanes) + l


def _diag_pass(x, diags):
    """diags: [(positions, d (2, 2^k))]: multiply each amplitude by the
    product of its phases."""
    _, rows, lanes = x.shape
    rc = max(1, min(rows, CHUNK // lanes))

    def body(i, x):
        c = lax.dynamic_slice_in_dim(x, i * rc, rc, 1)
        p = _positions(i, rc, lanes)
        cr, ci = c[0], c[1]
        for pos, d in diags:
            li = sum(((p >> b) & 1) << j for j, b in enumerate(pos))
            zero = jnp.float32(0)
            dr = sum(jnp.where(li == m, d[0, m], zero)
                     for m in range(d.shape[1]))
            di = sum(jnp.where(li == m, d[1, m], zero)
                     for m in range(d.shape[1]))
            cr, ci = cr * dr - ci * di, cr * di + ci * dr
        return lax.dynamic_update_slice_in_dim(x, jnp.stack([cr, ci]),
                                               i * rc, 1)
    return lax.fori_loop(0, rows // rc, body, x)


def _planes(m) -> np.ndarray:
    m = np.asarray(m)
    return np.stack([m.real, m.imag]).astype(np.float32)


def reference_program(passes, lane_bits):
    """A jitted fn(state, arrays) -> state that applies `passes` to
    (2, R, 2^lane_bits) f32 planes; the matrices go in as `arrays` so the
    program is not built around them."""
    shape = []
    arrays = []
    for kind, body in passes:
        if kind == "window":
            shape.append(("window", tuple(body)))
            arrays.append([jnp.asarray(_planes(m)) for m in body.values()])
        elif kind == "diag":
            shape.append(("diag", tuple(p for p, _ in body)))
            arrays.append([jnp.asarray(_planes(d)) for _, d in body])
        elif kind == "cross":
            ops = [_cross_operand(m, p, lane_bits) for p, m in body]
            shape.append(("cross", tuple(rows for rows, _ in ops)))
            arrays.append([jnp.asarray(_planes(g)) for _, g in ops])

    def run(x, arrays):
        for (kind, keys), arrs in zip(shape, arrays):
            if kind == "window":
                for (lo, hi), g in zip(keys, arrs):
                    x = _window_pass(x, g, lo, hi, lane_bits)
            elif kind == "diag":
                x = _diag_pass(x, list(zip(keys, arrs)))
            else:
                for rows, g in zip(keys, arrs):
                    x = _cross_pass(x, g, rows, lane_bits)
        return x

    return jax.jit(run, donate_argnums=(0,)), arrays


# -- the sketch and the comparison --------------------------------------------

def _hash(i):
    """murmur3's 32-bit finaliser."""
    h = i
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def natural_index(p, layout):
    """The natural amplitude index of positions p: position bit j is
    natural bit layout[j]."""
    if layout is None:
        return p
    return sum(((p >> j) & 1) << nat for j, nat in enumerate(layout))


@functools.partial(jax.jit, static_argnames=("layout",))
def sketch(x, salt, layout=None):
    """Projections of a state onto SKETCH seeded sign patterns of the
    natural amplitude index, and its squared norm, in f32 partial sums per
    chunk (the host adds them in f64). x: (2, rows, lanes) f32 re/im
    planes; `layout` maps position bits to natural bits
    (registers/<kind>.py), None for the identity. Chunks are read inside
    the loop, so nothing state-sized is made. Returns per chunk the
    (SKETCH, 2) projections (re, im) and the norm."""
    _, rows, lanes = x.shape
    rc = max(1, min(rows, SKETCH_CHUNK // lanes))
    bits = jnp.arange(SKETCH, dtype=jnp.uint32)

    def body(i, acc):
        proj, norm = acc
        c = lax.dynamic_slice_in_dim(x, i * rc, rc, 1)
        cr, ci = c[0], c[1]
        p = _positions(i, rc, lanes)
        h = _hash(natural_index(p, layout) ^ salt)
        sign = 1.0 - 2.0 * ((h[None] >> bits[:, None, None]) & 1).astype(
            jnp.float32)
        pr = jnp.sum(sign * cr[None], axis=(1, 2))
        pi = jnp.sum(sign * ci[None], axis=(1, 2))
        return (proj.at[i].set(jnp.stack([pr, pi], -1)),
                norm.at[i].set(jnp.sum(cr * cr + ci * ci)))

    n = rows // rc
    init = (jnp.zeros((n, SKETCH, 2), jnp.float32),
            jnp.zeros((n,), jnp.float32))
    return lax.fori_loop(0, n, body, init)


def proj_gap(program_sketch, reference_sketch) -> float:
    """Widest projection gap over the reference's norm."""
    (pp, _), (rp, rn) = [(np.sum(np.asarray(p, np.float64), 0),
                          np.sum(np.asarray(n, np.float64)))
                         for p, n in (program_sketch, reference_sketch)]
    gap = np.max(np.hypot(pp[:, 0] - rp[:, 0], pp[:, 1] - rp[:, 1]))
    return float(gap / np.sqrt(rn))
