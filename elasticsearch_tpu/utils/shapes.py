"""Static-shape helpers.

XLA traces one program per distinct input shape, so every variable-length
structure (postings slices, query term lists, doc counts) is padded to a
power-of-two bucket. This bounds the number of compiled variants to
O(log n) per program while keeping shapes static inside jit — the TPU
analogue of Lucene's arbitrary-length postings iterators. Where every
padded slot is paid for on the device (the tail window's chunk count),
``half_step_bucket`` pads to the finer ladder 1, 2, 3, 4, 6, 8, 12, …:
twice the variants, under half the waste.

``pow2_bucket``/``half_step_bucket``/``round_up`` are also tpulint's recognized
lattice-lowering points: the shape-flow pass (R017, recompile storms)
classifies any value that passed through them as PaddedPow2 —
acceptable as a program cache key — while a raw ``len()``/``.shape``
stays DataDependent and is flagged when it reaches a program factory
or jit static. A size that must bypass bucketing for a documented
reason is declared at the call site with ``# tpulint: bucketed``.
"""
from __future__ import annotations

import numpy as np


def pow2_bucket(n: int, minimum: int = 8) -> int:
    """Smallest power of two >= max(n, minimum)."""
    n = max(int(n), minimum)
    return 1 << (n - 1).bit_length()


def half_step_bucket(n: int, minimum: int = 1) -> int:
    """Smallest of 1, 2, 3, 4, 6, 8, 12, 16, 24, … (the powers of two and
    1.5x each) >= max(n, minimum): under 50% padding where ``pow2_bucket``
    pads under 100%, for at most 2·log2(n) + 2 shape classes up to n."""
    p = pow2_bucket(n, minimum)
    mid = (p >> 2) * 3  # the step between p/2 and p
    return mid if mid >= max(int(n), minimum) else p


def pad_to(arr: np.ndarray, length: int, fill, axis: int = 0) -> np.ndarray:
    """Pad `arr` along `axis` to `length` with `fill` (no-op if already there)."""
    cur = arr.shape[axis]
    if cur == length:
        return arr
    if cur > length:
        raise ValueError(f"cannot pad axis of size {cur} down to {length}")
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, length - cur)
    return np.pad(arr, widths, constant_values=fill)


def round_up(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple
