"""The program's flash attention, as the benchmark drives it.

``deploy`` is the public op (``kernels/attention/ops.py:attention``) with
its ``DEFAULT_CONFIG``; ``problem`` is the tunable problem whose measured
objective a campaign runs; ``TRACE_NAMES`` are substrings of the names the
kernel's device operations carry in a profiler trace.
"""

from __future__ import annotations

TRACE_NAMES = ("flash_attention", "_flash_kernel")


def program_inputs(x: dict, shape: dict) -> dict:
    return {"q": x["q"], "k": x["k"], "v": x["v"], "causal": shape["causal"]}


def deploy(x: dict, shape: dict, *, interpret: bool = False):
    """A zero-argument call of the deployed op on ``x``."""
    import jax
    from repro.kernels.attention import ops

    op = jax.jit(lambda q, k, v: ops.attention(
        q, k, v, causal=shape["causal"], interpret=interpret))
    return lambda: op(x["q"], x["k"], x["v"])


def problem(shape: dict):
    from repro.kernels.attention.space import AttentionProblem

    return AttentionProblem({k: shape[k] for k in ("hq", "hkv", "tq", "tk",
                                                   "d")})
