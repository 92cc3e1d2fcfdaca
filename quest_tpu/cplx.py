"""Host-side complex packing.

The framework stores amplitudes as split (re, im) float planes throughout
(see quest_tpu/state.py) and never materializes complex-dtype device
buffers. All complex data enters programs as (re, im) float pairs
produced by `pack`; results leave as float planes reassembled on the
host (state.to_dense). The layout was first forced by a remote backend
that could not move complex arrays; a directly attached TPU moves
complex64 and bakes complex64 constants without trouble (chip_smoke.py
phase b checks a round trip). The planes stay because every engine
shares them: the Pallas kernels and MXU contractions work on f32 planes,
and a TPU has no complex128 for the f64 tier at all.

Incidentally this matches the reference's storage model, which also keeps
real and imaginary parts in separate arrays (QuEST.h ComplexArray).
"""

from __future__ import annotations

from typing import Tuple

import numpy as np


def pack(x) -> Tuple[np.ndarray, np.ndarray]:
    """Host side: complex ndarray -> contiguous (re, im) float64 pair,
    safe to pass as jit arguments or bake into traced programs."""
    x = np.asarray(x)
    # np.array (not ascontiguousarray — that promotes 0-d to (1,))
    return (np.array(x.real, dtype=np.float64, order="C"),
            np.array(x.imag, dtype=np.float64, order="C"))
