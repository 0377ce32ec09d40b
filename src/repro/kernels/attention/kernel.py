"""Tunable Pallas TPU flash attention (GQA-aware, causal-capable).

Online-softmax tiling (FlashAttention adapted to the TPU memory hierarchy):
a program's (block_h · block_q × d) query rows — block_q rows of each of
block_h heads that share one kv head — stay VMEM-resident while (block_kv ×
d) key/value tiles stream; running max/denominator per row in VMEM scratch.
GQA is expressed in the BlockSpec index maps (kv head = q head // group), so
no KV replication ever materializes.

Tunables (the TPU vocabulary for attention):

  block_q / block_kv — VMEM tile shape (arithmetic-intensity vs residency),
  block_h            — q heads per program, all of one GQA group: their
                       queries are the rows of one MXU tile, so each streamed
                       K/V tile serves block_h heads at once (the kernel takes
                       the largest divisor of g not above block_h; the
                       deployed op asks for 8, so it takes the whole group
                       where g <= 8),
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


def online_softmax_update(s, v, m_ref, l_ref, acc_ref, idx):
    """One key/value tile of the online softmax.

    ``s``: the tile's f32 scores, rows by keys; ``v``: its values, keys by
    width.  ``m_ref``, ``l_ref`` and ``acc_ref`` hold the running row max,
    denominator and weighted sum; ``idx`` picks the rows' slot in them (a
    head index, or ``...``).  The old sum and denominator are rescaled by
    ``exp(m_prev - m_new)`` and the tile's ``p = exp(s - m_new)`` is added;
    ``p`` enters the MXU in ``v``'s dtype, with f32 accumulation."""
    m_prev = m_ref[idx].astype(jnp.float32)           # (rows, 1)
    m_cur = s.max(axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[idx] = (alpha * l_ref[idx].astype(jnp.float32)
                  + p.sum(axis=1, keepdims=True)).astype(l_ref.dtype)
    pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[idx] = (acc_ref[idx].astype(jnp.float32) * alpha
                    + pv).astype(acc_ref.dtype)
    m_ref[idx] = m_new.astype(m_ref.dtype)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale, causal, block_q, block_kv, block_h, tq, tk,
                  nkv_grid, skip_masked):
    j = pl.program_id(2)
    qi = pl.program_id(1)
    rows = block_h * block_q
    d = q_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body():
        # the block_h heads' queries are the rows of one tile: one dot
        # against the K tile and one against the V tile serve them all
        q = q_ref[...].astype(jnp.float32).reshape(rows, d)
        k = k_ref[0].astype(jnp.float32)              # (bkv, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            # a row's query position is its place among its head's rows
            pos = jax.lax.broadcasted_iota(
                jnp.int32, (block_h, block_q, block_kv), 1).reshape(
                    rows, block_kv)
            cols = j * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (rows, block_kv), 1)
            s = jnp.where(qi * block_q + pos + (tk - tq) >= cols, s, NEG_INF)
        online_softmax_update(s, v, m_ref, l_ref, acc_ref, ...)

    if causal and skip_masked:
        # last row of this q tile vs first col of this kv tile: if even that
        # pair is masked, the whole tile is dead — skip all compute.
        alive = (qi * block_q + block_q - 1 + (tk - tq)) >= j * block_kv
        pl.when(alive)(body)
    else:
        body()

    @pl.when(j == nkv_grid - 1)
    def _finish():
        o = (acc_ref[...].astype(jnp.float32)
             / jnp.maximum(l_ref[...], 1e-30))
        o_ref[...] = o.reshape(block_h, block_q, d).astype(o_ref.dtype)


def tiling(q_shape, k_shape, *, block_q, block_kv, block_h):
    """The blocks and the grid the kernel runs for ``q`` and ``k`` of these
    shapes: ``((bh, bq, bkv), grid)``.  ``bh`` is the largest divisor of the
    GQA group ``Hq // Hkv`` not above ``block_h``; the grid is ``(Hq / bh,
    Tq / bq, Tk / bkv)``, one step per (head block, q tile, kv tile)."""
    hq, tq, _ = q_shape
    hkv, tk, _ = k_shape
    g = hq // hkv
    bh = max(1, min(block_h, g))
    while g % bh:
        bh -= 1
    bq = min(block_q, tq)
    bkv = min(block_kv, tk)
    return (bh, bq, bkv), (hq // bh, cdiv(tq, bq), cdiv(tk, bkv))


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_kv", "block_h",
                     "skip_masked", "acc_dtype", "interpret"))
def flash_attention(q, k, v, *, causal=True, block_q=256, block_kv=512,
                    block_h=1, skip_masked=1, acc_dtype="f32", scale=None,
                    interpret=False):
    """``q``: (Hq, Tq, D); ``k``/``v``: (Hkv, Tk, D).  Returns (Hq, Tq, D).
    A program takes the largest divisor of the GQA group Hq // Hkv not above
    ``block_h`` (:func:`tiling`)."""
    hq, tq, d = q.shape
    hkv, tk, _ = k.shape
    g = hq // hkv
    (bh, bq, bkv), grid = tiling(q.shape, k.shape, block_q=block_q,
                                 block_kv=block_kv, block_h=block_h)
    scale = float(scale) if scale is not None else float(d) ** -0.5
    acc_jnp = jnp.float32 if acc_dtype == "f32" else jnp.bfloat16

    kern = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=bq, block_kv=bkv,
        block_h=bh, tq=tq, tk=tk, nkv_grid=grid[2], skip_masked=skip_masked)
    return pl.pallas_call(
        kern,
        name="flash_attention",
        grid=grid,
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
            pltpu.VMEM((bh * bq, 1), jnp.float32),
            pltpu.VMEM((bh * bq, 1), jnp.float32),
            pltpu.VMEM((bh * bq, d), acc_jnp),
        ],
        interpret=interpret,
    )(q, k, v)
