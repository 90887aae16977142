"""Paged decode attention of one step in one layer: each active row's
single query over its ``keys`` valid cache positions (the new token's
included), at most ``window`` of them in a layer with an attention window
(``dims["windows"]``, see ``roofline/__init__.py``), with the new k and v
written.  Bound by bandwidth: a row moves ``4·kv·dh`` bytes per key for
``4·heads·dh`` operations."""

from typing import Optional

BYTES = 2          # bf16 cache


def _keys(keys, window: Optional[int]) -> float:
    if window is not None:
        keys = [min(k, window) for k in keys]
    return float(sum(keys))


def flops(dims: dict, keys, window: Optional[int] = None) -> float:
    return 4.0 * dims["heads"] * dims["dh"] * _keys(keys, window)


def bytes_moved(dims: dict, keys, window: Optional[int] = None) -> float:
    rows = len(keys)
    return BYTES * dims["dh"] * (2 * dims["kv"] * _keys(keys, window)  # k, v
                                 + 2 * dims["heads"] * rows         # q, out
                                 + 2 * dims["kv"] * rows)           # write


def roofline_s(dims: dict, peak: dict, keys,
               window: Optional[int] = None) -> float:
    """One call: every row of the step together."""
    return max(flops(dims, keys, window) / peak["bf16_flops_per_s"],
               bytes_moved(dims, keys, window) / peak["hbm_bytes_per_s"])
