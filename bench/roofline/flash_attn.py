"""Causal prefill attention of one request in one layer: queries at
positions ``shared .. length - 1``, each over keys ``0 .. its position``,
or over the last ``window`` of them in a layer with an attention window
(``dims["windows"]``, see ``roofline/__init__.py``).  Bound by compute at
serving lengths."""

from typing import Optional

BYTES = 2          # bf16 q, k, v, out


def keys(length: int, shared: int = 0, window: Optional[int] = None) -> int:
    """Keys attended by the queries at ``shared .. length - 1``."""
    def upto(n):                       # by the queries at 0 .. n - 1
        if window is None or n <= window:
            return n * (n + 1) // 2
        return window * (window + 1) // 2 + (n - window) * window
    return upto(length) - upto(shared)


def flops(dims: dict, length: int, shared: int = 0,
          window: Optional[int] = None) -> float:
    return 4.0 * dims["heads"] * dims["dh"] * keys(length, shared, window)


def bytes_moved(dims: dict, length: int, shared: int = 0,
                window: Optional[int] = None) -> float:
    q_rows = length - shared
    first = 0 if window is None else max(0, shared - window + 1)
    return BYTES * dims["dh"] * (2 * dims["heads"] * q_rows      # q, out
                                 + 2 * dims["kv"] * (length - first))  # k, v


def roofline_s(dims: dict, peak: dict, length: int, shared: int = 0,
               window: Optional[int] = None) -> float:
    return max(flops(dims, length, shared, window)
               / peak["bf16_flops_per_s"],
               bytes_moved(dims, length, shared, window)
               / peak["hbm_bytes_per_s"])
