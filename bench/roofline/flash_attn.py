"""Causal prefill attention of one request in one layer: queries at
positions ``shared .. length - 1``, each over keys ``0 .. its position``.
Bound by compute at serving lengths."""

BYTES = 2          # bf16 q, k, v, out


def flops(dims: dict, length: int, shared: int = 0) -> float:
    keys = length * (length + 1) // 2 - shared * (shared + 1) // 2
    return 4.0 * dims["heads"] * dims["dh"] * keys     # QK^T and PV


def bytes_moved(dims: dict, length: int, shared: int = 0) -> float:
    q_rows = length - shared
    return BYTES * dims["dh"] * (2 * dims["heads"] * q_rows      # q, out
                                 + 2 * dims["kv"] * length)      # k, v


def roofline_s(dims: dict, peak: dict, length: int, shared: int = 0
               ) -> float:
    return max(flops(dims, length, shared) / peak["bf16_flops_per_s"],
               bytes_moved(dims, length, shared) / peak["hbm_bytes_per_s"])
