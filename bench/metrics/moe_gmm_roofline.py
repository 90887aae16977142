"""Roofline time of the window's grouped expert matmuls (``roofline/
moe_gmm.py``, per step kind from the engine's ``moe_pairs.prefill``,
``moe_pairs.decode``, ``moe_experts_hit.prefill`` and
``moe_experts_hit.decode`` counters) over the device time of the grouped
matmul kernel, ``_moe_gmm`` (``kernels/grouped_matmul``), in percent.
Nothing to read in a model without experts."""

from roofline import moe_gmm

GMM_KERNEL = r"^%_moe_gmm\b"
KINDS = ("prefill", "decode")


def read(run):
    if run.window is None:
        return None
    t = run.kernel_s(GMM_KERNEL)
    if not t:
        return None
    ideal = sum(moe_gmm.roofline_s(
        run.dims, run.peak, run.counters.get(f"moe_pairs.{k}", 0),
        run.counters.get(f"moe_experts_hit.{k}", 0)) for k in KINDS)
    return 100.0 * ideal / t
