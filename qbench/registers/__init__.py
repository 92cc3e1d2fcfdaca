"""Register kinds, one file each, found by a configuration's `register`.

Each module gives the reference's layout of the state (LANE_BITS, the
windows of its passes and `layout`, which natural bit each position holds),
seeded product inputs built on the device in the program's layout and in
the reference's, and `lower`, which turns the traffic's ops into the
reference's matrices on positions."""

import jax.numpy as jnp


def kron(vs):
    """kron(vs[-1], ..., vs[0]): vs[0] on the lowest bits."""
    out = vs[0]
    for v in vs[1:]:
        out = jnp.kron(v, out)
    return out


def outer_planes(a, b, lanes):
    """(2, rows, lanes) re/im planes of a * b (broadcast; b spans the
    lanes), made in one elementwise pass in their final shape: the reshape
    merges leading axes only. Anything else leaves a state-sized temporary
    (XLA on the TPU relayouts other reshapes, materialises stacked planes
    and splits complex arrays into planes), and 8 GiB has no room for it."""
    ar, ai, br, bi = jnp.real(a), jnp.imag(a), jnp.real(b), jnp.imag(b)
    out = ar[None] * jnp.stack([br, bi]) + ai[None] * jnp.stack([-bi, br])
    return out.reshape(2, -1, lanes)
