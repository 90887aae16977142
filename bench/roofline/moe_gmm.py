"""The grouped expert matmuls of one kind of step (prefill or decode),
summed over its calls and layers: ``pairs`` routed (token, expert)
pairs and ``hit`` experts that held at least one pair, counted per layer
(the engine's ``moe_pairs.*`` and ``moe_experts_hit.*`` counters).

Operations: ``2 · 3 · d · expert_ff`` per pair (gate, up and down
projections).  Bytes: the three weight matrices of every expert hit,
``3 · d · expert_ff · 2``, read once per call, plus the activations each
of the three calls moves: the gate and up calls read a pair's ``d``
inputs and write its ``expert_ff`` outputs (bf16), the down call reads
``expert_ff`` and writes ``d`` in float32.

A kind is put at the larger of its compute time and its memory time.
That is exact where every call of the kind sits on one side of the ridge
point (at 32 decode rows a call routes 256 pairs over about 63 experts,
4 pairs an expert, far below it), and low by at most the smaller of the
two times otherwise."""

BYTES = 2          # bf16 weights and activations
OUT_BYTES = 4      # float32 output of the down projection


def flops(dims: dict, pairs: float) -> float:
    return 2.0 * 3 * dims["d"] * dims["expert_ff"] * pairs


def bytes_moved(dims: dict, pairs: float, hit: float) -> float:
    d, f = dims["d"], dims["expert_ff"]
    weights = 3.0 * d * f * BYTES * hit
    acts = pairs * (2 * BYTES * (d + f)              # gate, up: in and out
                    + BYTES * f + OUT_BYTES * d)     # down: in and out
    return weights + acts


def roofline_s(dims: dict, peak: dict, pairs: float, hit: float) -> float:
    return max(flops(dims, pairs) / peak["bf16_flops_per_s"],
               bytes_moved(dims, pairs, hit) / peak["hbm_bytes_per_s"])
