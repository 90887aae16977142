"""Paged decode attention of one step in one layer: each active row's
single query over its ``keys`` valid cache positions (the new token's
included), with the new k and v written.  Bound by bandwidth: a row
moves ``4·kv·dh`` bytes per key for ``4·heads·dh`` operations."""

BYTES = 2          # bf16 cache


def flops(dims: dict, keys) -> float:
    return 4.0 * dims["heads"] * dims["dh"] * float(sum(keys))


def bytes_moved(dims: dict, keys) -> float:
    rows = len(keys)
    return BYTES * dims["dh"] * (2 * dims["kv"] * float(sum(keys))  # k, v
                                 + 2 * dims["heads"] * rows         # q, out
                                 + 2 * dims["kv"] * rows)           # write


def roofline_s(dims: dict, peak: dict, keys) -> float:
    """One call: every row of the step together."""
    return max(flops(dims, keys) / peak["bf16_flops_per_s"],
               bytes_moved(dims, keys) / peak["hbm_bytes_per_s"])
