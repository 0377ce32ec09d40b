"""Plain reference for causal grouped-query attention, with its inputs and
its lower-precision control.

Imports nothing of the program under test.  ``inputs`` makes q, k and v on
the device from a key; ``reference`` computes softmax(q k^T * d^-1/2) v in
float32 at the highest matmul precision, one query head at a time so that
the (tq, tk) scores of a single head are all that is ever held; ``control``
is the same computation with q, k, v and the probabilities rounded to
float8 (e4m3), the precision below the bf16 the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
NEG_INF = -1e30


def inputs(key: jax.Array, shape: dict) -> dict:
    return _inputs(key, shape["hq"], shape["hkv"], shape["tq"], shape["tk"],
                   shape["d"])


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _inputs(key, hq, hkv, tq, tk, d):
    kq, kk, kv = jax.random.split(key, 3)
    return {"q": jax.random.normal(kq, (hq, tq, d), jnp.bfloat16),
            "k": jax.random.normal(kk, (hkv, tk, d), jnp.bfloat16),
            "v": jax.random.normal(kv, (hkv, tk, d), jnp.bfloat16)}


def _round(x, dtype):
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("causal", "low"))
def _attend(q, k, v, *, causal: bool, low=None):
    hq, tq, d = q.shape
    hkv, tk, _ = k.shape
    group = hq // hkv
    scale = d ** -0.5
    if causal:
        mask = (jnp.arange(tq)[:, None] + (tk - tq)) >= jnp.arange(tk)[None, :]

    def head(h):
        qh = _round(q[h].astype(jnp.float32), low)
        kh = _round(k[h // group].astype(jnp.float32), low)
        vh = _round(v[h // group].astype(jnp.float32), low)
        s = jnp.dot(qh, kh.T, precision=HIGHEST) * scale
        if causal:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - s.max(axis=1, keepdims=True))
        p = p / p.sum(axis=1, keepdims=True)
        return jnp.dot(_round(p, low), vh, precision=HIGHEST)

    return jax.lax.map(head, jnp.arange(hq))


def reference(x: dict, shape: dict) -> jax.Array:
    """float32 (hq, tq, d)."""
    return _attend(x["q"], x["k"], x["v"], causal=shape["causal"])


def control(x: dict, shape: dict) -> jax.Array:
    """The reference in float8 e4m3, returned in the op's bf16."""
    return _attend(x["q"], x["k"], x["v"], causal=shape["causal"],
                   low=jnp.float8_e4m3fn).astype(jnp.bfloat16)
