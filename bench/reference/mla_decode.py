"""Plain reference for absorbed MLA decode, with its inputs and its
lower-precision control.

Imports nothing of the program under test.  ``inputs`` makes the queries
and the padded latent cache on the device from a key (the cache one
sequence at a time, so that no float32 copy of it is ever held), and takes
the lengths from the shape; ``reference`` computes, for each sequence
alone,

    softmax(scale * (q_lat c_kv^T + q_pe k_pe^T)) c_kv

over the sequence's first ``lengths[b]`` cached tokens, in float32 at the
highest matmul precision; ``control`` is the same computation with the
operands and the probabilities rounded to float8 (e4m3), the precision
below the bf16 the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def inputs(key: jax.Array, shape: dict) -> dict:
    x = _inputs(key, shape["b"], shape["h"], shape["c"], shape["r"],
                shape["t_max"])
    x["lengths"] = jnp.asarray(shape["lengths"], jnp.int32)
    return x


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _inputs(key, b, h, c, r, t):
    kq, kp, kc, kk = jax.random.split(key, 4)

    def rows(k, width):
        return jax.lax.map(
            lambda kb: jax.random.normal(kb, (t, width), jnp.bfloat16),
            jax.random.split(k, b))

    return {"q_lat": jax.random.normal(kq, (b, h, c), jnp.bfloat16),
            "q_pe": jax.random.normal(kp, (b, h, r), jnp.bfloat16),
            "ckv": rows(kc, c), "k_pe": rows(kk, r)}


def _round(x, dtype):
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("scale", "low"))
def _attend(q_lat, q_pe, ckv, k_pe, lengths, *, scale: float, low=None):
    t = ckv.shape[1]

    def one(args):
        ql, qp, cv, kp, n = args
        f32 = jnp.float32
        ql, qp = _round(ql.astype(f32), low), _round(qp.astype(f32), low)
        cv, kp = _round(cv.astype(f32), low), _round(kp.astype(f32), low)
        s = (jnp.dot(ql, cv.T, precision=HIGHEST)
             + jnp.dot(qp, kp.T, precision=HIGHEST)) * scale    # (h, t)
        s = jnp.where(jnp.arange(t)[None, :] < n, s, -jnp.inf)
        p = jnp.exp(s - s.max(axis=1, keepdims=True))
        p = p / p.sum(axis=1, keepdims=True)
        return jnp.dot(_round(p, low), cv, precision=HIGHEST)

    return jax.lax.map(one, (q_lat, q_pe, ckv, k_pe, lengths))


def reference(x: dict, shape: dict) -> jax.Array:
    """float32 (b, h, c)."""
    return _attend(x["q_lat"], x["q_pe"], x["ckv"], x["k_pe"], x["lengths"],
                   scale=float(shape["scale"]))


def control(x: dict, shape: dict) -> jax.Array:
    """The reference in float8 e4m3, returned in the op's bf16."""
    return _attend(x["q_lat"], x["q_pe"], x["ckv"], x["k_pe"], x["lengths"],
                   scale=float(shape["scale"]),
                   low=jnp.float8_e4m3fn).astype(jnp.bfloat16)
