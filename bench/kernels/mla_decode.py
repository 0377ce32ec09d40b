"""The program's MLA decode, as the benchmark drives it.

``deploy`` is the public op (``kernels/mla/ops.py:mla_decode``) with its
``DEFAULT_CONFIG``; ``problem`` is the tunable problem whose measured
objective a campaign runs; ``TRACE_NAMES`` are substrings of the names the
kernel's device operations carry in a profiler trace.
"""

from __future__ import annotations

TRACE_NAMES = ("mla_decode",)

#: the operands of the op, in its order
OPERANDS = ("q_lat", "q_pe", "ckv", "k_pe", "lengths")


def program_inputs(x: dict, shape: dict) -> dict:
    return {**{k: x[k] for k in OPERANDS}, "scale": float(shape["scale"])}


def deploy(x: dict, shape: dict, *, interpret: bool = False):
    """A zero-argument call of the deployed op on ``x``."""
    import jax
    from repro.kernels.mla import ops

    scale = float(shape["scale"])
    op = jax.jit(lambda *a: ops.mla_decode(*a, scale=scale,
                                           interpret=interpret))
    return lambda: op(*(x[k] for k in OPERANDS))


def problem(shape: dict):
    from repro.kernels.mla.space import MLADecodeProblem

    return MLADecodeProblem({**{k: shape[k] for k in ("b", "h", "c", "r",
                                                     "t_max", "scale")},
                             "lengths": tuple(shape["lengths"])})
