"""Shared TPU-kernel constants and helpers."""

import jax

NEG_INF = -1e30
LANES = 128          # TPU vector lane width (last-dim tile)
SUBLANES = 8         # TPU vector sublane count (second-to-last-dim tile)


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter: only on the CPU
    backend.  Called where a kernel is traced (never at import), so
    importing the kernels initialises no backend."""
    return jax.default_backend() == "cpu"


def aligned_block(n: int, want: int) -> int:
    """Block length along an axis of length ``n`` that the chip's tiling
    accepts as the second-to-last block dimension: the largest multiple
    of ``SUBLANES`` that divides ``n`` and is at most ``want``, or ``n``
    itself (a block equal to the whole axis is always legal) when ``n``
    fits in ``want`` or no such multiple exists."""
    if n <= want:
        return n
    for b in range(want - want % SUBLANES, 0, -SUBLANES):
        if n % b == 0:
            return b
    return n


__all__ = ["NEG_INF", "LANES", "SUBLANES", "interpret_mode",
           "aligned_block"]
