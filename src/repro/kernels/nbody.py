"""Pallas TPU tiled O(N^2) gravity kernel (the paper's N-body example app).

Grid: (i-tiles, j-tiles).  Each step loads a [bi, 3] block of target bodies
and a [3, bj] block of sources (the positions transposed, one body per lane)
into VMEM and accumulates forces in an f32 VMEM scratch tile; the all-pairs
structure is the same "stream the second operand" pattern as flash
attention, so VMEM stays O(tile).  The pair math runs per coordinate on
[bi, bj] tiles: a target coordinate is a column, a source coordinate a row.

Positions are padded to tile multiples; padded sources get zero mass via an
index mask.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(pi_ref, pj_ref, o_ref, acc_ref, *, soft: float, bj: int, N: int):
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pi = pi_ref[...].astype(jnp.float32)            # [bi, 3]
    pj = pj_ref[...].astype(jnp.float32)            # [3, bj]
    d = [pj[c:c + 1, :] - pi[:, c:c + 1] for c in range(3)]   # 3 x [bi, bj]
    r2 = d[0] * d[0] + d[1] * d[1] + d[2] * d[2] + soft
    inv = jax.lax.rsqrt(r2)
    w = inv * inv * inv                             # 1 / r^3
    jpos = j * bj + jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
    w = jnp.where(jpos < N, w, 0.0)                 # mask padded sources
    for c in range(3):
        acc_ref[:, c:c + 1] += jnp.sum(d[c] * w, axis=1, keepdims=True)

    @pl.when(j == nj - 1)
    def _fin():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_i", "tile_j", "soft",
                                             "interpret"))
def nbody_forces_tpu(p_all, *, tile_i: int = 256, tile_j: int = 256,
                     soft: float = 1e-3, interpret: bool = False):
    """p_all: [N,3] -> forces [N,3]."""
    N = p_all.shape[0]
    ti, tj = min(tile_i, N), min(tile_j, N)
    Np_i = -(-N // ti) * ti
    Np_j = -(-N // tj) * tj
    Np = max(Np_i, Np_j)
    pp = jnp.pad(p_all, ((0, Np - N), (0, 0)))
    grid = (Np // ti, Np // tj)
    out = pl.pallas_call(
        functools.partial(_kernel, soft=soft, bj=tj, N=N),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ti, 3), lambda i, j: (i, 0)),
            pl.BlockSpec((3, tj), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((ti, 3), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Np, 3), p_all.dtype),
        scratch_shapes=[pltpu.VMEM((ti, 3), jnp.float32)],
        interpret=interpret,
    )(pp, pp.T)
    return out[:N]
