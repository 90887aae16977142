"""Program wrapper — ``CCLProgram`` analogue.

An OpenCL program is source → build → kernels.  The JAX analogue is a
traceable Python callable → ``jax.jit`` (with shardings) → AOT
``.lower()``/``.compile()`` → an executable :class:`~repro.core.kernel.Kernel`.

Mirrored features:

* ``Program.from_source_files`` — loads step functions from Python files
  (cf. ``ccl_program_new_from_source_files``), for the examples that keep
  "device code" in standalone files;
* build log capture — XLA diagnostics are retained and surfaced like
  ``clGetProgramBuildInfo(CL_PROGRAM_BUILD_LOG)``, with hints from
  :func:`repro.core.errors.explain_xla_error`;
* offline analysis — ``analyze()`` returns cost/memory/collective stats from
  the compiled artifact without executing (the ``ccl_c`` analyzer path, and
  the engine behind launch/dryrun and the roofline benchmarks).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax

from . import hlo_analysis
from .context import Context
from .errors import Code, ErrBox, ReproError, explain_xla_error, guard, \
    raise_or_record
from .kernel import Kernel
from .wrapper import Wrapper


@dataclasses.dataclass
class Analysis:
    """Offline analysis of a compiled step (all per-device quantities)."""

    flops: float
    bytes_accessed: float
    argument_bytes: int
    output_bytes: int
    temp_bytes: int
    generated_code_bytes: int
    collectives: hlo_analysis.CollectiveStats
    fusion: Dict[str, int]
    lower_s: float
    compile_s: float
    alias_bytes: int = 0

    @property
    def peak_bytes(self) -> int:
        # donated inputs alias their outputs — count once
        return self.argument_bytes + self.output_bytes + self.temp_bytes \
            - self.alias_bytes

    def to_dict(self) -> Dict[str, Any]:
        return {
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "generated_code_bytes": self.generated_code_bytes,
            "collective_bytes": self.collectives.total_bytes,
            "collective_counts": self.collectives.counts,
            "collective_bytes_by_kind": self.collectives.bytes_by_kind,
            "fusion": self.fusion,
            "lower_s": self.lower_s,
            "compile_s": self.compile_s,
            "peak_bytes": self.peak_bytes,
        }


class Program(Wrapper):
    _counter = 0

    def __init__(self, context: Context, fn: Callable, name: Optional[str] = None):
        Program._counter += 1
        super().__init__(("prog", Program._counter))
        self.context = context
        self.fn = fn
        self.name = name or getattr(fn, "__name__", "program")
        self.build_log: str = ""
        self._jitted = None
        self._lowered = None
        self._compiled = None
        self._jit_kwargs: Dict[str, Any] = {}

    # -- constructors -------------------------------------------------------
    @classmethod
    def from_source_files(cls, context: Context, paths: Sequence[str],
                          entry: str, name: Optional[str] = None,
                          err: Optional[ErrBox] = None) -> Optional["Program"]:
        """Load ``entry`` from the first file defining it (the analogue of
        building a program from .cl source files)."""
        with guard(err) as g:
            ns: Dict[str, Any] = {}
            for i, p in enumerate(paths):
                spec = importlib.util.spec_from_file_location(
                    f"_repro_src_{cls._counter}_{i}", p)
                mod = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(mod)
                ns.update(vars(mod))
            if entry not in ns:
                raise_or_record(None, Code.INVALID_PROGRAM,
                                f"Entry point {entry!r} not found in {paths}")
            return cls(context, ns[entry], name=name or entry)
        return None

    # -- build ---------------------------------------------------------------
    def build(self, in_shardings: Any = None, out_shardings: Any = None,
              static_argnames: Optional[Sequence[str]] = None,
              donate_argnums: Optional[Tuple[int, ...]] = None,
              err: Optional[ErrBox] = None, **jit_kwargs) -> Optional["Program"]:
        """``ccl_program_build`` analogue — stage the function with jit."""
        with guard(err) as g:
            kw: Dict[str, Any] = dict(jit_kwargs)
            if in_shardings is not None:
                kw["in_shardings"] = in_shardings
            if out_shardings is not None:
                kw["out_shardings"] = out_shardings
            if static_argnames:
                kw["static_argnames"] = tuple(static_argnames)
            if donate_argnums:
                kw["donate_argnums"] = tuple(donate_argnums)
            try:
                self._jitted = jax.jit(self.fn, **kw)
            except Exception as e:  # build failure → log, like clBuildProgram
                self.build_log = f"{e}\nhint: {explain_xla_error(str(e))}"
                raise ReproError(Code.BUILD_PROGRAM_FAILURE,
                                 f"jit staging failed for {self.name}", e)
            self._jit_kwargs = kw
            return self
        return None

    def lower(self, *arg_specs, err: Optional[ErrBox] = None, **kw_specs):
        """AOT lower against ShapeDtypeStructs (no allocation)."""
        with guard(err) as g:
            if self._jitted is None:
                self.build()
            mesh = self.context.mesh
            t0 = time.perf_counter()
            try:
                if mesh is not None:
                    with mesh:
                        self._lowered = self._jitted.lower(*arg_specs, **kw_specs)
                else:
                    self._lowered = self._jitted.lower(*arg_specs, **kw_specs)
            except Exception as e:
                self.build_log = f"{e}\nhint: {explain_xla_error(str(e))}"
                raise ReproError(Code.BUILD_PROGRAM_FAILURE,
                                 f"lowering failed for {self.name}", e)
            self._lower_s = time.perf_counter() - t0
            return self._lowered
        return None

    def compile(self, err: Optional[ErrBox] = None):
        with guard(err) as g:
            if self._lowered is None:
                raise_or_record(None, Code.INVALID_PROGRAM,
                                "compile() before lower()")
            t0 = time.perf_counter()
            try:
                self._compiled = self._lowered.compile()
            except Exception as e:
                self.build_log = f"{e}\nhint: {explain_xla_error(str(e))}"
                raise ReproError(Code.COMPILE_FAILURE,
                                 f"XLA compile failed for {self.name}", e)
            self._compile_s = time.perf_counter() - t0
            return self._compiled
        return None

    # -- kernels ---------------------------------------------------------------
    def get_kernel(self, err: Optional[ErrBox] = None) -> Optional[Kernel]:
        """``ccl_program_get_kernel`` analogue: the compiled executable."""
        with guard(err) as g:
            if self._compiled is None:
                if self._lowered is None:
                    raise_or_record(None, Code.INVALID_KERNEL,
                                    "Program has not been lowered; call "
                                    "build()/lower()/compile() or use "
                                    "Kernel.from_jit for eager jit dispatch")
                self.compile()
            return Kernel(self.context, self._compiled, name=self.name,
                          program=self)
        return None

    def get_jit_kernel(self) -> Kernel:
        """Eager-jit kernel (compiles on first call, per-shape), for
        workflows that don't AOT-compile."""
        if self._jitted is None:
            self.build()
        return Kernel(self.context, self._jitted, name=self.name, program=self)

    # -- analysis ----------------------------------------------------------------
    def analyze(self, err: Optional[ErrBox] = None) -> Optional[Analysis]:
        with guard(err) as g:
            if self._compiled is None:
                self.compile()
            c = self._compiled
            ca = c.cost_analysis() or {}
            ma = c.memory_analysis()
            txt = c.as_text()
            return Analysis(
                flops=float(ca.get("flops", 0.0)),
                bytes_accessed=float(ca.get("bytes accessed", 0.0)),
                argument_bytes=int(getattr(ma, "argument_size_in_bytes", 0)),
                output_bytes=int(getattr(ma, "output_size_in_bytes", 0)),
                temp_bytes=int(getattr(ma, "temp_size_in_bytes", 0)),
                generated_code_bytes=int(
                    getattr(ma, "generated_code_size_in_bytes", 0)),
                collectives=hlo_analysis.collective_stats(txt),
                fusion=hlo_analysis.fusion_stats(txt),
                lower_s=getattr(self, "_lower_s", 0.0),
                compile_s=getattr(self, "_compile_s", 0.0),
                alias_bytes=int(getattr(ma, "alias_size_in_bytes", 0)),
            )
        return None

    @property
    def lowered(self):
        return self._lowered

    @property
    def compiled(self):
        return self._compiled

    def hlo_text(self, stage: str = "compiled") -> str:
        if stage == "compiled" and self._compiled is not None:
            return self._compiled.as_text()
        if self._lowered is not None:
            return self._lowered.as_text()
        return ""


__all__ = ["Program", "Analysis"]
