"""Tunable Pallas TPU point-in-polygon kernel.

TPU adaptation of the BAT Pnpoly kernel: thread-block size → points per grid
program; the paper's algorithm-variant parameters are kept verbatim as
*branch-free vectorized* variants (all compute the same inside/outside
answer, at different VPU cost):

  between_method 0  xor of strict comparisons
                 1  sign-product (y1-py)*(y2-py) < 0
                 2  |int(y1>py) - int(y2>py)| == 1
                 3  min/max interval test
  use_method     0  boolean xor-parity accumulator
                 1  integer crossing count, parity at the end
                 2  multiplicative sign flip (+1/-1 product)

``precompute_slope`` hoists (x2-x1)/(y2-y1) out of the point loop (VMEM vs
flops trade); ``coord_layout`` contrasts (2,N) SoA lane-contiguity against
(N,2) AoS (2/128 lane utilization — the TPU re-reading of ``use_soa``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..common import cdiv


def _edge_data(poly_ref, v, precompute_slope, slopes):
    x1 = poly_ref[0, v]
    y1 = poly_ref[1, v]
    x2 = poly_ref[2, v]
    y2 = poly_ref[3, v]
    if precompute_slope:
        return x1, y1, x2, y2, slopes[0, v]
    den = y2 - y1
    safe = jnp.where(den == 0, 1.0, den)
    return x1, y1, x2, y2, (x2 - x1) / safe


def _pnpoly_kernel(poly_ref, pts_ref, out_ref, *, n_vertices, unroll_v,
                   between_method, use_method, precompute_slope,
                   coord_layout, block_pts):
    if coord_layout == "soa":
        px = pts_ref[0:1, :]                   # (1, bp)
        py = pts_ref[1:2, :]
    else:
        px = pts_ref[:, 0:1].T
        py = pts_ref[:, 1:2].T

    slopes = None
    if precompute_slope:
        x1 = poly_ref[0:1, :]
        y1 = poly_ref[1:2, :]
        x2 = poly_ref[2:3, :]
        y2 = poly_ref[3:4, :]
        den = y2 - y1
        safe = jnp.where(den == 0.0, 1.0, den)
        slopes = (x2 - x1) / safe              # (1, V)

    if use_method == 0:
        acc0 = jnp.zeros(px.shape, jnp.bool_)
    elif use_method == 1:
        acc0 = jnp.zeros(px.shape, jnp.int32)
    else:
        acc0 = jnp.ones(px.shape, jnp.float32)

    def edge_update(acc, v):
        x1, y1, x2, y2, slope = _edge_data(poly_ref, v, precompute_slope,
                                           slopes)
        gt1 = y1 > py
        gt2 = y2 > py
        if between_method == 0:
            between = gt1 != gt2
        elif between_method == 1:
            between = (y1 - py) * (y2 - py) < 0.0
        elif between_method == 2:
            between = jnp.abs(gt1.astype(jnp.int32)
                              - gt2.astype(jnp.int32)) == 1
        else:
            between = (jnp.minimum(y1, y2) <= py) & (py < jnp.maximum(y1, y2))
        xint = slope * (py - y1) + x1
        cross = jnp.where(between, px < xint, False)
        if use_method == 0:
            return acc ^ cross
        if use_method == 1:
            return acc + cross.astype(jnp.int32)
        return acc * jnp.where(cross, -1.0, 1.0)

    n_chunks = n_vertices // unroll_v

    def chunk(c, acc):
        for u in range(unroll_v):
            acc = edge_update(acc, c * unroll_v + u)
        return acc

    if n_chunks > 1:
        acc = lax.fori_loop(0, n_chunks, chunk, acc0)
    else:
        acc = chunk(0, acc0)
    for v in range(n_chunks * unroll_v, n_vertices):   # remainder edges
        acc = edge_update(acc, v)

    if use_method == 0:
        inside = acc.astype(jnp.int32)
    elif use_method == 1:
        inside = (acc % 2).astype(jnp.int32)
    else:
        inside = (acc < 0.0).astype(jnp.int32)
    out_ref[...] = inside


@functools.partial(
    jax.jit,
    static_argnames=("block_points", "unroll_v", "between_method",
                     "use_method", "precompute_slope", "coord_layout",
                     "interpret"))
def pnpoly(points, poly, *, block_points=1024, unroll_v=4, between_method=0,
           use_method=0, precompute_slope=0, coord_layout="soa",
           interpret=False):
    """``points``: (2, N); ``poly``: (2, V).  Returns int32 (1, N)."""
    n = points.shape[1]
    v = poly.shape[1]
    bp = min(block_points, n)
    grid = (cdiv(n, bp),)
    # edges as rows: [x1; y1; x2; y2] so the kernel reads contiguous lanes
    poly_edges = jnp.concatenate([poly, jnp.roll(poly, -1, axis=1)], axis=0)

    if coord_layout == "soa":
        pts_in = points
        pts_spec = pl.BlockSpec((2, bp), lambda g: (0, g))
    else:
        pts_in = points.T
        pts_spec = pl.BlockSpec((bp, 2), lambda g: (g, 0))

    kern = functools.partial(
        _pnpoly_kernel, n_vertices=v, unroll_v=max(1, min(unroll_v, v)),
        between_method=between_method, use_method=use_method,
        precompute_slope=precompute_slope, coord_layout=coord_layout,
        block_pts=bp)
    out = pl.pallas_call(
        kern,
        name="pnpoly",
        grid=grid,
        in_specs=[pl.BlockSpec((4, v), lambda g: (0, 0)), pts_spec],
        out_specs=pl.BlockSpec((1, bp), lambda g: (0, g)),
        out_shape=jax.ShapeDtypeStruct((1, cdiv(n, bp) * bp), jnp.int32),
        interpret=interpret,
    )(poly_edges, pts_in)
    return out[:, :n]
