"""Tunable Pallas TPU 2D convolution (single-channel, shift-and-accumulate).

TPU adaptation of the van Werkhoven GPU conv parameters: thread-block dims →
output tile (block_h × block_w); work-per-thread → row_chunk (VREG-pressure
control); shared-memory staging → halo-materialized VMEM tiles (overlapping
reads are staged by a gather outside the kernel — the TPU-idiomatic
replacement for CUDA's shared-memory halo loads); bank-conflict padding →
dropped (no TPU analogue); read-only cache → filter residency in SMEM vs VMEM.

Single-channel shift-multiply convolution is VPU work (no MXU contraction
dimension) — the tunables trade lane/sublane utilization, VMEM footprint and
issue overhead, not MXU tiling.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import cdiv


def _conv_kernel(filt_ref, tile_ref, out_ref, *, fh, fw, block_h, block_w,
                 unroll_fh, unroll_fw, row_chunk, acc_dtype, filter_smem):
    acc_jnp = jnp.float32 if acc_dtype == "f32" else jnp.bfloat16
    tile = tile_ref[0]

    def accum_rows(r0, rows):
        """Partial unrolling is structural: the un-unrolled residue runs as a
        rolled ``fori_loop`` (dynamic filter-tap indices), the unrolled part
        as straight-line code — exactly the CUDA partial-unroll trade."""
        acc0 = jnp.zeros((rows, block_w), acc_jnp)
        n_io, n_jo = fh // unroll_fh, fw // unroll_fw

        def tap(acc, i, j):
            win = lax.dynamic_slice(tile, (r0 + i, j), (rows, block_w))
            return acc + win.astype(acc_jnp) * filt_ref[i, j].astype(acc_jnp)

        def jo_body(jo, acc, i):
            for ju in range(unroll_fw):
                acc = tap(acc, i, jo * unroll_fw + ju)
            return acc

        def io_body(io, acc):
            for iu in range(unroll_fh):
                i = io * unroll_fh + iu
                if n_jo > 1:
                    acc = lax.fori_loop(
                        0, n_jo, lambda jo, a, _i=i: jo_body(jo, a, _i), acc)
                else:
                    acc = jo_body(0, acc, i)
            return acc

        if n_io > 1:
            return lax.fori_loop(0, n_io, io_body, acc0)
        return io_body(0, acc0)

    if row_chunk == 0 or row_chunk >= block_h:
        out_ref[0] = accum_rows(0, block_h).astype(out_ref.dtype)
    else:
        for r0 in range(0, block_h, row_chunk):      # static; handles remainder
            rows = min(row_chunk, block_h - r0)
            out_ref[0, r0:r0 + rows, :] = \
                accum_rows(r0, rows).astype(out_ref.dtype)


def _make_tiles(padded, gh, gw, th, tw, bh, bw):
    """Materialize overlapping halo tiles: (gh*gw, th, tw)."""
    def slice_at(r, c):
        return lax.dynamic_slice(padded, (r, c), (th, tw))
    rows = jnp.arange(gh) * bh
    cols = jnp.arange(gw) * bw
    tiles = jax.vmap(lambda r: jax.vmap(lambda c: slice_at(r, c))(cols))(rows)
    return tiles.reshape(gh * gw, th, tw)


@functools.partial(
    jax.jit,
    static_argnames=("block_h", "block_w", "unroll_fh", "unroll_fw",
                     "row_chunk", "acc_dtype", "filter_smem", "interpret"))
def conv2d(image, filt, *, block_h=32, block_w=512, unroll_fh=1, unroll_fw=1,
           row_chunk=0, acc_dtype="f32", filter_smem=False, interpret=False):
    h, w = image.shape
    fh, fw = filt.shape
    oh, ow = h - fh + 1, w - fw + 1
    bh, bw = min(block_h, oh), min(block_w, ow)
    gh, gw = cdiv(oh, bh), cdiv(ow, bw)
    th, tw = bh + fh - 1, bw + fw - 1
    # pad so every tile is full-size (edge values never reach valid output)
    padded = jnp.pad(image, ((0, gh * bh + fh - 1 - h), (0, gw * bw + fw - 1 - w)))
    tiles = _make_tiles(padded, gh, gw, th, tw, bh, bw)

    def snap_unroll(u, extent):        # largest divisor of extent <= u
        u = min(u, extent)
        while extent % u:
            u -= 1
        return u

    kern = functools.partial(
        _conv_kernel, fh=fh, fw=fw, block_h=bh, block_w=bw,
        unroll_fh=snap_unroll(unroll_fh, fh), unroll_fw=snap_unroll(unroll_fw, fw),
        row_chunk=row_chunk, acc_dtype=acc_dtype, filter_smem=filter_smem)

    filt_spec = pl.BlockSpec(
        (fh, fw), lambda g: (0, 0),
        memory_space=pltpu.SMEM if filter_smem else pltpu.VMEM)

    out = pl.pallas_call(
        kern,
        name="conv2d",
        grid=(gh * gw,),
        in_specs=[filt_spec,
                  pl.BlockSpec((1, th, tw), lambda g: (g, 0, 0))],
        out_specs=pl.BlockSpec((1, bh, bw), lambda g: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((gh * gw, bh, bw), image.dtype),
        interpret=interpret,
    )(filt, tiles)
    out = out.reshape(gh, gw, bh, bw).transpose(0, 2, 1, 3)
    return out.reshape(gh * bh, gw * bw)[:oh, :ow]
