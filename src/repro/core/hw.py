"""Target-hardware constant tables.

cf4ocl reads device capabilities through ``clGetDeviceInfo``; JAX reports
no peaks, so the chip's capabilities come from a static table keyed by the
``device_kind`` JAX reports.  The roofline engine (launch/roofline) and
``Kernel.suggest_batching`` read from here — never hard-code these numbers
elsewhere.  A device kind missing from the table is an error, not a
default: a guessed peak would make every roofline share built on it
meaningless.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

from .errors import Code, ReproError


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_bf16_flops: float      # FLOP/s per chip
    hbm_bandwidth: float        # bytes/s per chip
    hbm_bytes: int              # HBM capacity per chip
    ici_link_bandwidth: float   # bytes/s per ICI link
    ici_links: int              # usable ICI links per chip (torus degree)
    vmem_bytes: int             # per-core VMEM
    source: str                 # where the figures come from
    mxu_dim: int = 128          # systolic array edge
    vpu_lanes: int = 128        # vector lanes
    vpu_sublanes: int = 8


TPU_V5E = ChipSpec(
    name="tpu-v5e",
    peak_bf16_flops=197e12,
    hbm_bandwidth=819e9,
    hbm_bytes=16 * 1024**3,
    # 1,600 Gbit/s of chip-to-chip interconnect over 4 links
    ici_link_bandwidth=50e9,
    ici_links=4,
    vmem_bytes=128 * 1024**2,
    source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
           '16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI',
)

# keyed by ``jax.Device.device_kind`` exactly as JAX reports it
SPECS: Dict[str, ChipSpec] = {"TPU v5 lite": TPU_V5E}


def spec_for(device_kind: str) -> ChipSpec:
    """Peaks of the chip JAX names ``device_kind``; raises
    ``DEVICE_NOT_FOUND`` for a kind the table does not hold (the CPU
    among them: a host has no published peaks here)."""
    try:
        return SPECS[device_kind]
    except KeyError:
        raise ReproError(
            Code.DEVICE_NOT_FOUND,
            f"no chip spec for device kind {device_kind!r}; known kinds: "
            f"{sorted(SPECS)}") from None


# the chip this repository is written for (analysis paths size tiles and
# rooflines against it, whatever device the process runs on)
TARGET = TPU_V5E

__all__ = ["ChipSpec", "TPU_V5E", "SPECS", "spec_for", "TARGET"]
