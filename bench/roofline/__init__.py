"""Operation and byte counts of the kernels and steps the benchmark
reads, and the chips' peaks (``peaks.json``).

Each count is of the work the model requires at a call's real shapes:
the prompt's real tokens and the cache's valid positions, never a padded
bucket or block.  So no kernel can read above its roofline by doing less
padding, and the count does not depend on what implements the call.

``dims`` is the dictionary a configuration's reference module
(``reference/<module>.py``) derives from it.  Every count reads

    d, heads, kv, dh, ff, vocab, layers

(model width, query and key/value heads, head size, MLP width,
vocabulary, layers), and these optional keys, which a reference of a
model with more than one kind of layer fills in:

    windows     one entry per layer: the layer's attention window in
                positions, or None for full attention.  A query attends
                over at most that many keys, its own included.
    moe         one entry per layer: True where the layer's MLP is a
                mixture of experts.  Such a layer reads
    experts     routed experts (the router's width),
    top_k       experts per token,
    expert_ff   one expert's width,
    shared_ff   the width of an expert every token passes through (0 or
                absent: none).

Without them every layer is dense with full attention, and each count
takes the form ``layers × (one layer)`` it has had from the first.
"""

from typing import List, Optional, Tuple


def layer_kinds(dims: dict) -> List[Tuple[Optional[int], bool, int]]:
    """``(window, moe, number of layers)`` for each kind of layer, in the
    order the kinds first occur."""
    n = dims["layers"]
    windows = dims.get("windows", [None] * n)
    moe = dims.get("moe", [False] * n)
    if len(windows) != n or len(moe) != n:
        raise ValueError(f"dims give {len(windows)} windows and "
                         f"{len(moe)} MLP kinds for {n} layers")
    count = {}
    for kind in zip(windows, moe):
        count[kind] = count.get(kind, 0) + 1
    return [(w, m, c) for (w, m), c in count.items()]
