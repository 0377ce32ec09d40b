"""Problem registry: name -> TunableProblem factory, resolved lazily.

Sessions are pure data, so the orchestrator needs to turn a problem *name*
back into a live :class:`TunableProblem`.  Kernel problems import jax and
their Pallas modules, so factories are referenced by dotted path and
imported only on use — ``repro.orchestrator`` stays importable (CLI
``status``, tests) without pulling in the whole kernel stack.

Two toy problems (``toy_quad``, ``toy_rastrigin``) are registered for
smoke tests and CLI demos; they need nothing beyond the core.
"""

from __future__ import annotations

import importlib
import math
from typing import Callable

from ..core.problem import FunctionProblem, TunableProblem
from ..core.space import Param, SearchSpace

#: problem name -> "module:attr" of a zero-arg (or kwargs) factory
PROBLEM_PATHS: dict[str, str] = {
    "gemm": "repro.kernels.matmul.space:GemmProblem",
    "conv2d": "repro.kernels.conv2d.space:Conv2dProblem",
    "pnpoly": "repro.kernels.pnpoly.space:PnpolyProblem",
    "nbody": "repro.kernels.nbody.space:NbodyProblem",
    "hotspot": "repro.kernels.hotspot.space:HotspotProblem",
    "dedisp": "repro.kernels.dedisp.space:DedispProblem",
    "expdist": "repro.kernels.expdist.space:ExpdistProblem",
    "attention": "repro.kernels.attention.space:AttentionProblem",
    "mla_decode": "repro.kernels.mla.space:MLADecodeProblem",
}


def _toy_quad(n_params: int = 4, k: int = 8) -> TunableProblem:
    space = SearchSpace([Param(f"p{i}", tuple(range(k)))
                         for i in range(n_params)], name="toy_quad")

    def fn(cfg, arch):
        return 1.0 + sum((cfg[f"p{i}"] - 2) ** 2 for i in range(n_params))

    return FunctionProblem(space, fn, name="toy_quad")


def _toy_rastrigin(n_params: int = 4, k: int = 10) -> TunableProblem:
    space = SearchSpace([Param(f"p{i}", tuple(range(k)))
                         for i in range(n_params)], name="toy_rastrigin")

    def fn(cfg, arch):
        tot = 0.0
        for i in range(n_params):
            x = (cfg[f"p{i}"] - 3) * 0.7
            tot += x * x - 3.0 * math.cos(2 * math.pi * x) + 3.0
        return 1.0 + tot

    return FunctionProblem(space, fn, name="toy_rastrigin")


TOY_FACTORIES: dict[str, Callable[..., TunableProblem]] = {
    "toy_quad": _toy_quad,
    "toy_rastrigin": _toy_rastrigin,
}


def problem_names() -> list[str]:
    return sorted([*PROBLEM_PATHS, *TOY_FACTORIES])


def make_problem(name: str, **kwargs) -> TunableProblem:
    """Instantiate a registered problem by name (lazy import)."""
    if name in TOY_FACTORIES:
        return TOY_FACTORIES[name](**kwargs)
    if name not in PROBLEM_PATHS:
        raise KeyError(f"unknown problem {name!r}; "
                       f"registered: {', '.join(problem_names())}")
    mod_name, attr = PROBLEM_PATHS[name].split(":")
    factory = getattr(importlib.import_module(mod_name), attr)
    return factory(**kwargs)
