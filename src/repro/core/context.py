"""Context wrapper — ``CCLContext`` analogue.

An OpenCL context is a set of devices sharing objects (programs, buffers,
queues).  On TPU pods the natural unit of coherence is a **mesh**: a context
therefore carries a device list *and* an optional :class:`jax.sharding.Mesh`
over those devices.  Programs built from this context lower against its
mesh; buffers created from it are placed/sharded on it.

Constructors mirror cf4ocl's convenience functions
(``ccl_context_new_gpu``, ``ccl_context_new_from_filters``...).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

from .device import Device
from .device_selector import Filters, select_default_backend
from .errors import Code, ErrBox, guard, raise_or_record
from .wrapper import Wrapper


class Context(Wrapper):
    def __init__(self, devices: Sequence[Device],
                 mesh: Optional[Mesh] = None):
        raw = tuple(d.unwrap() for d in devices)
        self._devices = list(devices)
        self._mesh = mesh
        super().__init__(raw)
        self._info_queries = {
            "NUM_DEVICES": lambda r: len(r),
            "DEVICES": lambda r: list(self._devices),
            "MESH_SHAPE": lambda r: None if self._mesh is None
            else dict(self._mesh.shape),
        }

    # -- constructors -------------------------------------------------------
    @classmethod
    def new_accel(cls, err: Optional[ErrBox] = None) -> Optional["Context"]:
        """``ccl_context_new_gpu`` analogue: the devices of the backend
        JAX was started on (``select_default_backend``)."""
        with guard(err) as g:
            devs = select_default_backend()
            return cls(devs)
        return None

    @classmethod
    def new_from_filters(cls, filters: Filters,
                         err: Optional[ErrBox] = None) -> Optional["Context"]:
        with guard(err) as g:
            return cls(filters.select())
        return None

    @classmethod
    def new_with_mesh(cls, shape: Tuple[int, ...], axis_names: Tuple[str, ...],
                      devices: Optional[Sequence[Device]] = None,
                      err: Optional[ErrBox] = None) -> Optional["Context"]:
        """Context over an explicit mesh (the multi-pod path)."""
        with guard(err) as g:
            pool = [d.unwrap() for d in devices] if devices else jax.devices()
            need = int(np.prod(shape))
            if len(pool) < need:
                raise_or_record(None, Code.INVALID_CONTEXT,
                                f"Mesh {shape} needs {need} devices, have "
                                f"{len(pool)}")
            arr = np.asarray(pool[:need]).reshape(shape)
            mesh = Mesh(arr, axis_names)
            return cls([Device.wrap(d) for d in arr.flat], mesh=mesh)
        return None

    # -- accessors ------------------------------------------------------------
    @property
    def devices(self) -> Sequence[Device]:
        return tuple(self._devices)

    def device(self, index: int = 0,
               err: Optional[ErrBox] = None) -> Optional[Device]:
        """``ccl_context_get_device`` analogue."""
        if not 0 <= index < len(self._devices):
            raise_or_record(err, Code.INVALID_VALUE,
                            f"Device index {index} out of range "
                            f"[0,{len(self._devices)})")
            return None
        return self._devices[index]

    @property
    def num_devices(self) -> int:
        return len(self._devices)

    @property
    def mesh(self) -> Optional[Mesh]:
        return self._mesh

    def require_mesh(self, err: Optional[ErrBox] = None) -> Optional[Mesh]:
        if self._mesh is None:
            raise_or_record(err, Code.INVALID_CONTEXT,
                            "This operation needs a Context with a mesh; "
                            "build one with Context.new_with_mesh()")
            return None
        return self._mesh


__all__ = ["Context"]
