"""Plain reference for out = alpha * a @ b + beta * c, with its inputs and
its lower-precision control.

Imports nothing of the program under test.  ``reference`` computes in
float32 at the highest matmul precision, one block of columns at a time;
``control`` rounds a and b to float8 (e4m3), the precision below the bf16
the configuration states, and accumulates in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
#: columns of the output computed at once
BLOCK_N = 2048


def inputs(key: jax.Array, shape: dict) -> dict:
    return _inputs(key, shape["m"], shape["n"], shape["k"])


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _inputs(key, m, n, k):
    ka, kb, kc = jax.random.split(key, 3)
    return {"a": jax.random.normal(ka, (m, k), jnp.bfloat16),
            "b": jax.random.normal(kb, (k, n), jnp.bfloat16),
            "c": jax.random.normal(kc, (m, n), jnp.bfloat16)}


@functools.partial(jax.jit, static_argnames=("alpha", "beta", "low"))
def _gemm(a, b, c, *, alpha: float, beta: float, low=None):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if low is not None:
        a = a.astype(low).astype(jnp.float32)
        b = b.astype(low).astype(jnp.float32)
    n = b.shape[1]
    cols = []
    for j in range(0, n, BLOCK_N):
        part = alpha * jnp.dot(a, b[:, j:j + BLOCK_N], precision=HIGHEST)
        if beta:
            part = part + beta * c[:, j:j + BLOCK_N].astype(jnp.float32)
        cols.append(part)
    return jnp.concatenate(cols, axis=1)


def reference(x: dict, shape: dict) -> jax.Array:
    """float32 (m, n)."""
    return _gemm(x["a"], x["b"], x["c"], alpha=float(shape["alpha"]),
                 beta=float(shape["beta"]))


def control(x: dict, shape: dict) -> jax.Array:
    """The reference in float8 e4m3, returned in the op's bf16."""
    return _gemm(x["a"], x["b"], x["c"], alpha=float(shape["alpha"]),
                 beta=float(shape["beta"]),
                 low=jnp.float8_e4m3fn).astype(jnp.bfloat16)
