"""Tunable Pallas TPU flash attention (GQA-aware, causal-capable).

Online-softmax tiling (FlashAttention adapted to the TPU memory hierarchy):
(block_q × d) query tiles stay VMEM-resident while (block_kv × d) key/value
tiles stream; running max/denominator in VMEM scratch.  GQA is expressed in
the BlockSpec index maps (kv head = q head // group), so no KV replication
ever materializes.

Tunables (the TPU vocabulary for attention):

  block_q / block_kv — VMEM tile shape (arithmetic-intensity vs residency),
  block_h            — q heads per program; GQA heads sharing a kv head can
                       amortize each streamed K/V tile (requires block_h | g),
  skip_masked        — causal block skipping: fully-masked kv tiles do no
                       compute (grid still visits them; on hardware this
                       halves the MXU work of causal attention),
  acc_dtype          — f32 (exact) or bf16 accumulators (halves scratch).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import cdiv

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale, causal, block_q, block_kv, block_h, tq, tk,
                  nkv_grid, skip_masked):
    j = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body():
        k = k_ref[0].astype(jnp.float32)              # (bkv, d)
        v = v_ref[0].astype(jnp.float32)
        if causal:
            rows0 = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0) + (tk - tq)
            cols0 = j * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
        for hh in range(block_h):                     # GQA: amortize K/V tile
            q = q_ref[hh].astype(jnp.float32)         # (bq, d)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if causal:
                s = jnp.where(rows0 >= cols0, s, NEG_INF)
            m_prev = m_ref[hh].astype(jnp.float32)    # (bq, 1)
            m_cur = s.max(axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[hh] = (alpha * l_ref[hh].astype(jnp.float32)
                         + p.sum(axis=1, keepdims=True)).astype(l_ref.dtype)
            pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc_ref[hh] = (acc_ref[hh].astype(jnp.float32) * alpha
                           + pv).astype(acc_ref.dtype)
            m_ref[hh] = m_new.astype(m_ref.dtype)

    if causal and skip_masked:
        # last row of this q tile vs first col of this kv tile: if even that
        # pair is masked, the whole tile is dead — skip all compute.
        alive = (qi * block_q + block_q - 1 + (tk - tq)) >= j * block_kv
        pl.when(alive)(body)
    else:
        body()

    @pl.when(j == nkv_grid - 1)
    def _finish():
        for hh in range(block_h):
            o_ref[hh] = (acc_ref[hh].astype(jnp.float32)
                         / jnp.maximum(l_ref[hh].astype(jnp.float32), 1e-30)
                         ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_kv", "block_h",
                     "skip_masked", "acc_dtype", "interpret"))
def flash_attention(q, k, v, *, causal=True, block_q=256, block_kv=512,
                    block_h=1, skip_masked=1, acc_dtype="f32", scale=None,
                    interpret=False):
    """``q``: (Hq, Tq, D); ``k``/``v``: (Hkv, Tk, D).  Returns (Hq, Tq, D).
    ``block_h`` must divide the GQA group size Hq // Hkv."""
    hq, tq, d = q.shape
    hkv, tk, _ = k.shape
    g = hq // hkv
    bh = max(1, min(block_h, g))
    while g % bh:
        bh -= 1
    bq = min(block_q, tq)
    bkv = min(block_kv, tk)
    scale = float(scale) if scale is not None else float(d) ** -0.5
    acc_jnp = jnp.float32 if acc_dtype == "f32" else jnp.bfloat16

    kern = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=bq, block_kv=bkv,
        block_h=bh, tq=tq, tk=tk, nkv_grid=cdiv(tk, bkv),
        skip_masked=skip_masked)
    return pl.pallas_call(
        kern,
        name="flash_attention",
        grid=(hq // bh, cdiv(tq, bq), cdiv(tk, bkv)),
        in_specs=[
            pl.BlockSpec((bh, bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda h, i, j, bh=bh, g=g:
                         ((h * bh) // g, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda h, i, j, bh=bh, g=g:
                         ((h * bh) // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((bh, bq, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((hq, tq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bh, bq, 1), jnp.float32),
            pltpu.VMEM((bh, bq, 1), jnp.float32),
            pltpu.VMEM((bh, bq, d), acc_jnp),
        ],
        interpret=interpret,
    )(q, k, v)
