"""Pallas TPU SSD (state-space duality) chunk kernel — Mamba2's compute core.

Grid: (batch*heads, chunks) with the chunk dimension sequential
("arbitrary"): each step computes the intra-chunk quadratic term plus the
contribution of the carried state, and updates the running [p, n] state in
f32 VMEM scratch — the cross-chunk recurrence lives entirely in scratch, so
the kernel is one pass over the sequence.

Per grid step (one head, one chunk of q timesteps):
    L[i,j]   = exp(cumsum(a)[i] - cumsum(a)[j]) for j<=i      (decay matrix)
    y_intra  = ((C B^T) * L) x
    y_inter  = diag(exp(cumsum(a))) C h_prev
    h_new    = exp(total) h_prev + sum_j decay_to_end[j] B_j x_j^T

The chunk-local cumsum of ``a`` is taken outside the kernel and laid out
[heads, chunks, q], one block per head (a [1, q] block would break the TPU's
(8, 128) tiling); each step reads its chunk's row.  B and C are shared by
the heads of a batch row and are indexed, not broadcast.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dot(lhs, rhs, dims):
    # f32 operands at f32 precision: Mosaic's default contracts f32 in a
    # single bf16 pass, which misses the f32 reference by percents
    return jax.lax.dot_general(lhs, rhs, dims,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(x_ref, cs_ref, b_ref, c_ref, y_ref, state_ref, h_ref, *,
            q: int, p: int, n: int):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)          # [q, p]
    cs_row = cs_ref[0, pl.ds(ci, 1), :]       # [1, q] chunk-local cumsum(a)
    cs = cs_row.T                             # [q, 1]
    B = b_ref[0].astype(jnp.float32)          # [q, n]
    C = c_ref[0].astype(jnp.float32)          # [q, n]

    seg = cs - cs_row                         # [q, q]
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    Lmat = jnp.where(jj <= ii, jnp.exp(seg), 0.0)

    scores = _dot(C, B, (((1,), (1,)), ((), ()))) * Lmat
    y = _dot(scores, x, (((1,), (0,)), ((), ())))

    h_prev = h_ref[...]                       # [p, n]
    y += jnp.exp(cs) * _dot(C, h_prev, (((1,), (1,)), ((), ())))

    # cumsum at the chunk's end, [1, 1]; a lane reduction rather than a
    # slice at lane q-1, which Mosaic cannot broadcast back over [p, n]
    total = jnp.sum(jnp.where(jj[:1] == q - 1, cs_row, 0.0), axis=1,
                    keepdims=True)
    state_upd = _dot(x * jnp.exp(total - cs), B, (((0,), (0,)), ((), ())))
    h_ref[...] = jnp.exp(total) * h_prev + state_upd

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _fin():
        state_ref[0] = h_ref[...].astype(state_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_tpu(x, a, B, C, *, chunk: int = 64, interpret: bool = False):
    """SSD over full sequences.

    x: [b,s,h,p], a: [b,s,h] (log-decay), B/C: [b,s,n].
    Returns (y [b,s,h,p], final state [b,h,p,n]).  s % chunk == 0 required
    (callers pad, same as models.mamba2.ssd_chunked).
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0
    nc = s // chunk
    # fold (batch, head)
    xf = x.transpose(0, 2, 1, 3).reshape(b * h, s, p)
    cs = jnp.cumsum(a.astype(jnp.float32).transpose(0, 2, 1)
                    .reshape(b * h, nc, chunk), axis=-1)

    grid = (b * h, nc)
    y, state = pl.pallas_call(
        functools.partial(_kernel, q=chunk, p=p, n=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, nc, chunk), lambda g, c: (g, 0, 0)),
            pl.BlockSpec((1, chunk, n), lambda g, c: (g // h, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda g, c: (g // h, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, p, n), lambda g, c: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, p), x.dtype),
            jax.ShapeDtypeStruct((b * h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xf, cs, B, C)
    y = y.reshape(b, h, s, p).transpose(0, 2, 1, 3)
    state = state.reshape(b, h, p, n)
    return y, state
