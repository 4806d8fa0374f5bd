"""Pallas TPU 5-point wave-propagation stencil (WaveSim).

Grid over row tiles.  Pallas block index maps are in whole-block units, so
overlapping halo windows are not directly expressible; instead the +-1-row
neighbours are provided as two pre-shifted, tile-aligned input arrays (XLA
fuses the shifts into cheap copies) and each grid step works entirely on
[tile, W] VMEM blocks.  Column neighbours are in-block rolls.  A block
spans the full width, so the row tile shrinks as the field widens.

Boundary rows/columns are clamped to zero (Dirichlet), matching
``ref.wave_step_ref``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(um_ref, u_ref, up_ref, dn_ref, o_ref, *, c: float, tile: int,
            H: int):
    i = pl.program_id(0)
    um = um_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    up = up_ref[...].astype(jnp.float32)    # u shifted: row r holds u[r-1]
    dn = dn_ref[...].astype(jnp.float32)    # u shifted: row r holds u[r+1]
    left = jnp.roll(u, 1, axis=1)
    right = jnp.roll(u, -1, axis=1)
    lap = up + dn + left + right - 4.0 * u
    un = 2.0 * u - um + c * lap
    row = i * tile + jax.lax.broadcasted_iota(jnp.int32, un.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, un.shape, 1)
    interior = ((row > 0) & (row < H - 1)
                & (col > 0) & (col < un.shape[1] - 1))
    o_ref[...] = jnp.where(interior, un, 0.0).astype(o_ref.dtype)


VMEM_BUDGET = 12 * 2**20
BLOCKS_AND_TEMPS = 16          # 5 blocks x 2 buffers + ~6 [tile, W] temps


def _row_tile(W: int) -> int:
    """Largest row tile (a multiple of 8, at most 128) whose five
    double-buffered [tile, W] f32 blocks and the kernel's temporaries fit
    the 16 MiB of VMEM a kernel may use by default."""
    return max(8, min(128, VMEM_BUDGET // (W * 4 * BLOCKS_AND_TEMPS) // 8 * 8))


@functools.partial(jax.jit, static_argnames=("c", "tile", "interpret"))
def wave_step_tpu(um, u, *, c: float = 0.25, tile=None,
                  interpret: bool = False):
    """One wave step: um/u [H,W] -> next field [H,W].  ``tile`` rows per grid
    step; by default the largest that fits VMEM at this width."""
    H, W = u.shape
    tile = min(tile or _row_tile(W), H)
    Hp = -(-H // tile) * tile
    pad = ((0, Hp - H), (0, 0))
    umpad = jnp.pad(um, pad)
    upad = jnp.pad(u, pad)
    up = jnp.pad(u, ((1, Hp - H), (0, 0)))[:Hp]        # row r -> u[r-1]
    dn = jnp.pad(u, ((0, Hp - H + 1), (0, 0)))[1:Hp + 1]  # row r -> u[r+1]
    grid = (Hp // tile,)
    spec = pl.BlockSpec((tile, W), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, c=c, tile=tile, H=H),
        grid=grid,
        in_specs=[spec, spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((Hp, W), u.dtype),
        interpret=interpret,
    )(umpad, upad, up, dn)
    return out[:H]
