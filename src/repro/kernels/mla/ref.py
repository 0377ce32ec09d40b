"""Pure-jnp oracle for absorbed MLA decode."""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def _one(q_lat, q_pe, ckv, k_pe, length, scale):
    f32 = jnp.float32
    s = (q_lat.astype(f32) @ ckv.astype(f32).T
         + q_pe.astype(f32) @ k_pe.astype(f32).T) * scale      # (H, T)
    s = jnp.where(jnp.arange(ckv.shape[0]) < length, s, -jnp.inf)
    return jax.nn.softmax(s, axis=-1) @ ckv.astype(f32)


def mla_decode_reference(q_lat, q_pe, ckv, k_pe, lengths, *, scale):
    """``q_lat``: (B, H, C); ``q_pe``: (B, H, R); ``ckv``: (B, T, C);
    ``k_pe``: (B, T, R); ``lengths``: (B,).  Returns float32 (B, H, C):
    ``softmax(scale * (q_lat ckv^T + q_pe k_pe^T)) ckv`` over each
    sequence's first ``lengths[b]`` cached tokens, one sequence at a time,
    at the highest matmul precision."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            _one(q_lat[b], q_pe[b], ckv[b], k_pe[b], lengths[b], scale)
            for b in range(q_lat.shape[0])])
