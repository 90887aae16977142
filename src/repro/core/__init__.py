"""repro.core — the cf4ocl wrapper layer adapted to JAX (paper §3–§4).

This is the paper's primary contribution: an object-oriented framework over
a verbose low-level compute API, with integrated profiling, device
selection, error management and offline kernel analysis.

Class map (cf4ocl → repro):

    CCLWrapper    → core.wrapper.Wrapper (+ memcheck)
    CCLErr        → core.errors.ErrBox / ReproError
    CCLPlatform*  → core.platform.Platform
    CCLDevice     → core.device.Device
    CCLContext    → core.context.Context (device set + optional Mesh)
    CCLQueue      → core.queue.DispatchQueue
    CCLEvent      → core.event.Event
    CCLBuffer     → core.buffer.Buffer
    CCLProgram    → core.program.Program (trace/lower/compile + build log)
    CCLKernel     → core.kernel.Kernel (+ suggest_batching)
    device_selector module → core.device_selector.Filters
    errors module → core.errors.err_string
"""

from .buffer import Buffer, swap
from .context import Context
from .device import Device, all_devices
from .device_selector import Filters, select_default_backend
from .errors import Code, ErrBox, ReproError, err_string
from .event import Event
from .kernel import Kernel, suggest_batching, suggest_matmul_tiles
from .platform import Platform, available_platforms, platform_info
from .program import Analysis, Program
from .queue import DispatchQueue
from .wrapper import Wrapper, live_wrappers, memcheck
from . import hw, hlo_analysis

__all__ = [
    "Buffer", "swap", "Context", "Device", "all_devices", "Filters",
    "select_default_backend", "Code", "ErrBox", "ReproError", "err_string",
    "Event", "Kernel", "suggest_batching", "suggest_matmul_tiles",
    "Platform", "available_platforms", "platform_info", "Analysis",
    "Program", "DispatchQueue", "Wrapper", "live_wrappers", "memcheck",
    "hw", "hlo_analysis",
]
