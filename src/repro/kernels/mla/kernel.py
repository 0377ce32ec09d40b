"""Tunable Pallas TPU decode kernel for absorbed multi-head latent attention
(DeepSeek-V2's MLA).

One query token per sequence attends over the sequence's latent cache: each
cached token is a ``C``-wide latent ``c_kv`` and an ``R``-wide rope key
``k_pe``.  With ``W_uk`` absorbed into the query, every head scores

    s = scale * (q_lat . c_kv^T + q_pe . k_pe^T)

and its output is ``softmax(s) . c_kv`` (``C`` wide; ``W_uv`` is applied
outside).  All heads share the one cache, so it is attention with a single
KV head whose keys and values are the same bytes.

A program holds ``block_h`` heads' queries as the rows of its tiles and
streams ``(block_kv, C)`` tiles of its sequence's ``c_kv`` and ``(R,
block_kv)`` tiles of its ``k_pe``; each tile is read once for those heads,
and the ``c_kv`` tile serves as both K and V.  ``k_pe`` is read as its
transpose ``(B, R, T)``: the TPU lays out a ``(B, T, 64)`` bf16 array with
``T`` minor, so the transpose is the array as it lies in memory, where the
kernel's row-major ``(B, T, R)`` would cost a relayout of the whole cache
on every call.

``lengths`` (one per sequence) reaches the kernel as a scalar-prefetch
operand: columns at or past a sequence's length are masked, a KV step that
lies wholly past it computes nothing, and its index map repeats the last
live block, so the pipeline starts no DMA for it.

Tunables:

  block_kv  — cache tokens per tile (grid steps and dead steps past the
              lengths vs tile residency),
  block_h   — heads per program: the cache is streamed H / block_h times,
  acc_dtype — f32 or bf16 accumulator.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attention.kernel import NEG_INF, online_softmax_update
from ..common import cdiv


def _mla_kernel(lengths_ref, q_ref, qpe_ref, ckv_ref, kpe_ref, o_ref,
                m_ref, l_ref, acc_ref, *, scale, block_kv, nkv_grid):
    b = pl.program_id(0)
    j = pl.program_id(2)
    length = lengths_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block_kv < length)
    def _body():
        ckv = ckv_ref[0]                              # (bkv, C): K and V
        s = jax.lax.dot_general(q_ref[0], ckv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        # k_pe's tile is (R, bkv): the cache's own layout
        s = s + jnp.dot(qpe_ref[0], kpe_ref[0],
                        preferred_element_type=jnp.float32)
        s = s * scale                                 # (bh, bkv)
        cols = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < length, s, NEG_INF)
        online_softmax_update(s, ckv, m_ref, l_ref, acc_ref, ...)

    @pl.when(j == nkv_grid - 1)
    def _finish():
        o_ref[0] = (acc_ref[...].astype(jnp.float32)
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_kv", "block_h", "acc_dtype",
                              "interpret"))
def mla_decode(q_lat, q_pe, ckv, k_pe, lengths, *, scale, block_kv=512,
               block_h=128, acc_dtype="f32", interpret=False):
    """``q_lat``: (B, H, C); ``q_pe``: (B, H, R); ``ckv``: (B, T, C);
    ``k_pe``: (B, T, R); ``lengths``: (B,) int32, each sequence's live cache
    length, at most T.  Returns ``o_lat``: (B, H, C) in ``q_lat``'s dtype.
    ``min(block_h, H)`` must divide H."""
    bsz, h, c = q_lat.shape
    r = q_pe.shape[-1]
    t = ckv.shape[1]
    bh = min(block_h, h)
    if h % bh:
        raise ValueError(f"block_h {block_h} does not divide {h} heads")
    bkv = min(block_kv, t)
    nkv = cdiv(t, bkv)
    acc_jnp = jnp.float32 if acc_dtype == "f32" else jnp.bfloat16

    def head_map(b, hb, j, lens):
        return (b, hb, 0)

    def kv_block(b, j, lens):
        # past the length, stay on the last live block: no new DMA
        return jnp.minimum(j, jnp.maximum((lens[b] + bkv - 1) // bkv - 1, 0))

    def ckv_map(b, hb, j, lens):
        return (b, kv_block(b, j, lens), 0)

    def kpe_map(b, hb, j, lens):
        return (b, 0, kv_block(b, j, lens))

    kern = functools.partial(_mla_kernel, scale=float(scale), block_kv=bkv,
                             nkv_grid=nkv)
    return pl.pallas_call(
        kern,
        name="mla_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, h // bh, nkv),
            in_specs=[
                pl.BlockSpec((1, bh, c), head_map),
                pl.BlockSpec((1, bh, r), head_map),
                pl.BlockSpec((1, bkv, c), ckv_map),
                pl.BlockSpec((1, r, bkv), kpe_map),
            ],
            out_specs=pl.BlockSpec((1, bh, c), head_map),
            scratch_shapes=[
                pltpu.VMEM((bh, 1), jnp.float32),
                pltpu.VMEM((bh, 1), jnp.float32),
                pltpu.VMEM((bh, c), acc_jnp),
            ]),
        out_shape=jax.ShapeDtypeStruct((bsz, h, c), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q_lat, q_pe, ckv, jnp.swapaxes(k_pe, 1, 2))
