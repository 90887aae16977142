"""Device selector — cf4ocl's filter mechanism for choosing devices.

cf4ocl builds contexts from a chain of *filters*: independent filters accept
or reject a single device; dependent filters operate on the candidate list
as a whole (e.g. "same platform", "first N").  Client code can extend the
mechanism with plug-in filters — here, any callable.

Used mainly by :mod:`repro.core.context` for context creation, but exposed
for workflows that enumerate devices by characteristics (the paper's stated
secondary use).
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence

import jax

from . import hw
from .device import Device
from .errors import Code, ErrBox, raise_or_record

# An independent filter: Device -> bool.
IndepFilter = Callable[[Device], bool]
# A dependent filter: list[Device] -> list[Device].
DepFilter = Callable[[List[Device]], List[Device]]


class Filters:
    """Composable filter chain (``ccl_devsel_add_*_filter`` analogue)."""

    def __init__(self):
        self._indep: List[IndepFilter] = []
        self._dep: List[DepFilter] = []

    # -- built-in independent filters --------------------------------------
    def type(self, platform: str) -> "Filters":
        """Accept devices of a given backend/platform ("tpu", "cpu", ...)."""
        self._indep.append(lambda d: d.platform == platform)
        return self

    def accelerator(self) -> "Filters":
        self._indep.append(lambda d: d.is_accelerator())
        return self

    def kind_contains(self, substr: str) -> "Filters":
        self._indep.append(lambda d: substr.lower() in d.kind.lower())
        return self

    def process_local(self) -> "Filters":
        self._indep.append(
            lambda d: d.unwrap().process_index == jax.process_index())
        return self

    def min_hbm(self, nbytes: int) -> "Filters":
        """Accept chips of a known kind (``hw.SPECS``) with at least
        ``nbytes`` of HBM."""
        self._indep.append(lambda d: d.kind in hw.SPECS and
                           d.spec.hbm_bytes >= nbytes)
        return self

    # -- built-in dependent filters -----------------------------------------
    def same_platform(self) -> "Filters":
        def dep(devs: List[Device]) -> List[Device]:
            if not devs:
                return devs
            plat = devs[0].platform
            return [d for d in devs if d.platform == plat]
        self._dep.append(dep)
        return self

    def first_n(self, n: int) -> "Filters":
        self._dep.append(lambda devs: devs[:n])
        return self

    def count_multiple_of(self, n: int) -> "Filters":
        """Keep the largest prefix whose length is a multiple of ``n`` —
        meshes need rectangular device counts."""
        self._dep.append(lambda devs: devs[: (len(devs) // n) * n])
        return self

    # -- plug-in mechanism ---------------------------------------------------
    def custom(self, fn: IndepFilter) -> "Filters":
        """Plug-in independent filter (cf4ocl's extension point)."""
        self._indep.append(fn)
        return self

    def custom_dep(self, fn: DepFilter) -> "Filters":
        self._dep.append(fn)
        return self

    # -- evaluation ----------------------------------------------------------
    def select(self, pool: Optional[Sequence[Device]] = None,
               err: Optional[ErrBox] = None) -> List[Device]:
        devs = list(pool) if pool is not None else \
            [Device.wrap(d) for d in jax.devices()]
        for f in self._indep:
            devs = [d for d in devs if f(d)]
        for f in self._dep:
            devs = f(devs)
        if not devs:
            raise_or_record(err, Code.DEVICE_NOT_FOUND,
                            "Device filter chain selected zero devices")
            return []
        return devs


def select_default_backend(err: Optional[ErrBox] = None) -> List[Device]:
    """``ccl_context_new_gpu`` device-selection part: the devices of the
    backend JAX was started on.  The backend is ``JAX_PLATFORMS``'s to
    choose; nothing here second-guesses it or falls back to another, so a
    run meant for the chip that came up on the CPU stays visibly on the
    CPU."""
    return Filters().type(jax.default_backend()).select(err=err)


__all__ = ["Filters", "select_default_backend", "IndepFilter",
           "DepFilter"]
