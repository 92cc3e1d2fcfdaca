"""Register kinds, one file each, found by a configuration's `register`.

Each module gives the reference's layout of the state (LANE_BITS, the
windows of its passes and `layout`, which natural bit each position holds),
seeded product inputs built on the device in the program's layout and in
the reference's, and `lower`, which turns the traffic's ops into the
reference's matrices on positions. Each also gives the program's side:
DENSITY, `program(circuit, num_qubits, interpret)` (the compiled entry
the window drives, before lower()), `program_buffer(num_qubits)` (a
buffer of the program's state, for the first input to overwrite) and
`state_bytes(num_qubits)`, which `fused_engine` makes for a register the
fused engine runs whole; and SMALL_QUBITS, the register size of the CPU
tests."""

import jax.numpy as jnp


def fused_engine(state_bits, density: bool):
    """(program, program_buffer, state_bytes) of a register that
    Circuit.compiled_fused runs whole on one chip, in f32 (re, im)
    planes of state_bits(num_qubits) bits."""
    def program(circuit, num_qubits: int, interpret: bool):
        return circuit.compiled_fused(state_bits(num_qubits), density,
                                      donate=True, interpret=interpret)

    def program_buffer(num_qubits: int):
        from quest_tpu.state import fused_state_shape
        return jnp.zeros(fused_state_shape(state_bits(num_qubits)),
                         jnp.float32)

    def state_bytes(num_qubits: int) -> int:
        return 8 << state_bits(num_qubits)

    return program, program_buffer, state_bytes


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
