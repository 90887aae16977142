"""Device wrapper — ``CCLDevice`` analogue.

Wraps :class:`jax.Device` one-to-one and answers info queries both about the
*runtime* device (what jax reports) and about its chip's published peaks
(the static :mod:`repro.core.hw` table, keyed by ``device_kind``; a query
fails for a kind the table lacks, such as the CPU).  ``target_spec`` is
the chip the repository is written for, whatever the process runs on.
"""

from __future__ import annotations

from typing import Any, Optional

import jax

from . import hw
from .errors import ErrBox
from .wrapper import Wrapper


class Device(Wrapper):
    def __init__(self, raw: "jax.Device"):
        super().__init__(raw)
        self._info_queries = {
            "NAME": lambda d: f"{d.platform}:{d.id}",
            "PLATFORM": lambda d: d.platform,
            "KIND": lambda d: d.device_kind,
            "ID": lambda d: d.id,
            "PROCESS_INDEX": lambda d: d.process_index,
            "COORDS": lambda d: getattr(d, "coords", None),
            "MEMORY_STATS": Device._mem_stats,
            # the chip's published peaks (roofline constants)
            "PEAK_BF16_FLOPS": lambda d: Device._spec(d).peak_bf16_flops,
            "HBM_BANDWIDTH": lambda d: Device._spec(d).hbm_bandwidth,
            "HBM_BYTES": lambda d: Device._spec(d).hbm_bytes,
            "ICI_LINK_BANDWIDTH": lambda d: Device._spec(d).ici_link_bandwidth,
            "ICI_LINKS": lambda d: Device._spec(d).ici_links,
            "VMEM_BYTES": lambda d: Device._spec(d).vmem_bytes,
            "MXU_DIM": lambda d: Device._spec(d).mxu_dim,
            "VPU_SHAPE": lambda d: (Device._spec(d).vpu_sublanes,
                                    Device._spec(d).vpu_lanes),
        }

    # -- helpers ----------------------------------------------------------
    @staticmethod
    def _spec(d) -> hw.ChipSpec:
        return hw.spec_for(d.device_kind)

    @staticmethod
    def _mem_stats(d) -> Optional[dict]:
        try:
            return d.memory_stats()
        except Exception:  # noqa: BLE001 — not all backends expose stats
            return None

    # -- convenience accessors (most used info keys) -----------------------
    @property
    def name(self) -> str:
        return self.get_info("NAME")

    @property
    def platform(self) -> str:
        return self.get_info("PLATFORM")

    @property
    def kind(self) -> str:
        return self.get_info("KIND")

    @property
    def spec(self) -> hw.ChipSpec:
        return self._spec(self._raw)

    @property
    def target_spec(self) -> hw.ChipSpec:
        """Spec of the deployment target (TPU v5e) regardless of runtime."""
        return hw.TARGET

    def is_accelerator(self) -> bool:
        return self.platform not in ("cpu",)


def all_devices() -> list:
    return [Device.wrap(d) for d in jax.devices()]


__all__ = ["Device", "all_devices"]
