"""Shared infrastructure for the tunable Pallas kernels.

Each kernel package provides:
  ``ref.py``    — pure-jnp oracle,
  ``kernel.py`` — ``pl.pallas_call`` + BlockSpec implementation, parameterized
                  by a config dict drawn from its search space,
  ``ops.py``    — public wrapper with the tuned-config defaults; it always
                  calls the Pallas kernel, in interpret mode only when the
                  caller passes ``interpret=True``,
  ``space.py``  — the :class:`~repro.core.TunableProblem` (search space,
                  constraints, analytical cost-model features).

The landscape/portability studies evaluate configs through the analytical TPU
cost model.  :meth:`KernelProblem.measured` times the compiled kernels on the
device.  CPU correctness tests run the kernels in interpret mode against the
oracles.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Any, Callable

import jax
import numpy as np

from ..core.costmodel import MiB
from ..core.problem import MeasuredProblem, TunableProblem
from ..core.space import Config, SearchSpace
from ..telemetry.trace import span

# Structural VMEM budget for space-level constraints: a config is kept in
# the space if it could run on the LARGEST generation (128 MiB VMEM,
# double-buffered => 2*ws <= 256 MiB).  Per-generation validity on top of
# this comes from the cost model (gen.vmem_bytes overflow => inf), exactly
# the paper's per-architecture "Valid" column mechanism.
PORTABLE_VMEM = 256 * MiB

#: JAX's persistent compilation cache when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset.  A fixed path: a cache under a temp name, a pid or the time would
#: never be found again.
COMPILE_CACHE_DIR = (Path(__file__).resolve().parents[3]
                     / "experiments" / "jax_cache")

#: relative-L2 error a kernel's output may have against its reference:
#: (full-precision configs, configs with a bf16 parameter).  bf16
#: accumulate/compute configs lose ~8 mantissa bits and the references run
#: in f32, so the config-dependent budget is part of each kernel's contract.
REL_L2_TOL = {
    "gemm": (5e-3, 2e-2),
    "conv2d": (5e-3, 3e-2),
    "nbody": (1e-3, 8e-2),      # 1/r^3 amplifies bf16 rounding near pairs
    "hotspot": (5e-3, 3e-2),
    "pnpoly": (0.0, 0.0),       # integer output: exact
    "expdist": (1e-3, 2e-2),
    "dedisp": (1e-3, 2e-2),
    "flash_attention": (5e-3, 2e-2),
}


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is the cache, and JAX reads it
    itself; otherwise the cache goes to :data:`COMPILE_CACHE_DIR`.  JAX
    opens the cache at the first compile after a directory is set.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def rel_l2(got, want) -> float:
    """Relative L2 error of ``got`` against ``want`` (in float64)."""
    g = np.asarray(got, dtype=np.float64)
    w = np.asarray(want, dtype=np.float64)
    if g.shape != w.shape:
        raise ValueError(f"shape {g.shape} != reference shape {w.shape}")
    return float(np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-12))


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def dtype_bytes(dtype) -> int:
    return np.dtype(dtype).itemsize


class KernelProblem(TunableProblem):
    """A tunable kernel bound to a concrete input shape.

    ``shape`` is a dict of problem dimensions (e.g. ``{"m":..,"n":..,"k":..}``)
    so one kernel yields a family of problems (the paper fixes one shape per
    benchmark; we default to the paper-scale shape).
    """

    #: subclasses set these
    default_shape: dict[str, int] = {}
    #: every suite kernel derives features from (config, shape) only — the
    #: TPU generation enters at cost-model-estimate time
    arch_independent_features = True

    def __init__(self, shape: dict[str, int] | None = None):
        self.shape = dict(self.default_shape)
        if shape:
            self.shape.update(shape)
        super().__init__(self.build_space())
        self.name = f"{self.kernel_name}"

    kernel_name: str = "kernel"

    def build_space(self) -> SearchSpace:
        raise NotImplementedError

    # -- correctness hooks (used by tests) ------------------------------- #
    def run_reference(self, config: Config, inputs: dict) -> Any:
        raise NotImplementedError

    def run_kernel(self, config: Config, inputs: dict,
                   *, interpret: bool) -> Any:
        raise NotImplementedError

    def make_inputs(self, key: jax.Array, small: bool = True) -> dict:
        raise NotImplementedError

    def tolerance(self, config: Config) -> float:
        """The :data:`REL_L2_TOL` budget of ``config``'s output."""
        full, low = REL_L2_TOL[self.kernel_name]
        return low if "bf16" in config.values() else full

    # -- the device path ---------------------------------------------------- #
    def lower_kernel(self, config: Config, inputs: dict) -> jax.stages.Lowered:
        """Lower the compiled (never interpreted) kernel on ``inputs``.

        Entries with a ``shape`` (device arrays, or ``ShapeDtypeStruct``s on
        a described device) become the arguments; the others (flags, sweep
        counts, scalars) are baked into the program."""
        arrays, consts = _split_inputs(inputs)
        return jax.jit(lambda a: self.run_kernel(
            config, {**consts, **a}, interpret=False)).lower(arrays)

    def compile_kernel(self, config: Config, inputs: dict) -> Callable[[], Any]:
        """Compile the kernel for ``inputs``, which live on the device, and
        return a zero-argument call of the compiled program.  Raises what
        the chip's compiler raises for a config it refuses."""
        arrays, _ = _split_inputs(inputs)
        # tracing and lowering hold the interpreter lock; the backend
        # compile runs in native code
        with span("kernel.lower", cat="kernel"):
            lowered = self.lower_kernel(config, inputs)
        with span("kernel.compile", cat="kernel"):
            compiled = lowered.compile()
        return lambda: compiled(arrays)

    def measured(self, inputs: dict, **kw) -> MeasuredProblem:
        """This kernel as a :class:`MeasuredProblem` on ``inputs``: each
        config's build is :meth:`compile_kernel`, so a config the compiler
        refuses becomes an invalid trial.  Turns on the compile cache."""
        use_compile_cache()
        return MeasuredProblem(
            self.space, lambda config: self.compile_kernel(config, inputs),
            name=self.name, **kw)


def _split_inputs(inputs: dict) -> tuple[dict, dict]:
    arrays = {k: v for k, v in inputs.items() if hasattr(v, "shape")}
    return arrays, {k: v for k, v in inputs.items() if k not in arrays}
