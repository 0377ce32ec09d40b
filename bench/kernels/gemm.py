"""The program's GEMM, as the benchmark drives it.

``deploy`` is the public op (``kernels/matmul/ops.py:gemm``) with its
``DEFAULT_CONFIG``; ``problem`` is the tunable problem whose measured
objective a campaign runs; ``TRACE_NAMES`` are substrings of the names the
kernel's device operations carry in a profiler trace.
"""

from __future__ import annotations

TRACE_NAMES = ("gemm", "_gemm_kernel")


def program_inputs(x: dict, shape: dict) -> dict:
    return {"a": x["a"], "b": x["b"], "c": x["c"],
            "alpha": float(shape["alpha"]), "beta": float(shape["beta"])}


def deploy(x: dict, shape: dict, *, interpret: bool = False):
    """A zero-argument call of the deployed op on ``x``."""
    import jax
    from repro.kernels.matmul import ops

    alpha, beta = float(shape["alpha"]), float(shape["beta"])
    op = jax.jit(lambda a, b, c: ops.gemm(a, b, c, alpha=alpha, beta=beta,
                                          interpret=interpret))
    return lambda: op(x["a"], x["b"], x["c"])


def problem(shape: dict):
    from repro.kernels.matmul.space import GemmProblem

    return GemmProblem({k: shape[k] for k in ("m", "n", "k")})
