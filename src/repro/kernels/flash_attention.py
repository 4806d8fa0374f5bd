"""Fused causal self-attention on the TPU, forward and backward.

JAX's splash attention (Pallas TPU kernels, ``custom_vjp``): within each
(query block, key block) tile the running max, normaliser and accumulator
stay in VMEM; the forward saves only each row's log-sum-exp, the backward
runs as a dk/dv kernel and a dq kernel that recompute the tile's
probabilities from it, and key blocks above the diagonal (or outside a
sliding window) are skipped without being fetched.  No [heads, S, S]
tensor reaches HBM.  Products take bf16 operands with f32 accumulation.

Layout: q [B, S, K, G, hd] (G query heads per KV head), k/v [B, S, K, hd].
Each batch row is folded to [K*G, S, hd], so query head k*G + g reads KV
head k, and the kernel is vmapped over the batch.  The 1/sqrt(hd) scale is
folded into q (exact where hd is a power of four, as at hd 64).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as splash, splash_attention_mask as masks)

LANES = 128
# query and key rows of one tile in the forward, the dk/dv and the dq
# kernels; each is cut to S where S is shorter.  MaxText's sizes, not yet
# timed against other tilings on the chip
BLOCKS = dict(block_q=512, block_kv=1024, block_kv_compute=512,
              block_q_dkv=512, block_kv_dkv=1024, block_kv_dkv_compute=512,
              block_q_dq=512, block_kv_dq=1024)


def fits(S: int) -> bool:
    """Whether a sequence of S rows is a whole number of every tile."""
    return S % LANES == 0 and all(S % min(b, S) == 0 for b in BLOCKS.values())


@functools.lru_cache(maxsize=32)
def _kernel(S: int, heads: int, window, interpret: bool):
    if window is None:
        mask = masks.CausalMask((S, S))
    else:
        mask = masks.LocalMask((S, S), window_size=(window - 1, 0), offset=0)
    blocks = splash.BlockSizes(**{n: min(b, S) for n, b in BLOCKS.items()})
    with jax.ensure_compile_time_eval():      # its block tables, concrete
        return splash.make_splash_mha(
            masks.MultiHeadMask([mask] * heads), block_sizes=blocks,
            head_shards=1, q_seq_shards=1, interpret=interpret)


def causal_attention(q, k, v, *, window=None, interpret: bool = False):
    """Causal (optionally sliding-window) self-attention over q [B,S,K,G,hd]
    and k, v [B,S,K,hd] -> [B,S,K,G,hd], differentiable.  A sequence that
    is not a whole number of tiles is padded at its end, where causality
    hides the padded keys from every real query."""
    B, S, K, G, hd = q.shape
    Sp = -(-S // LANES) * LANES
    while not fits(Sp):
        Sp += LANES
    pad = ((0, 0), (0, Sp - S)) + ((0, 0),) * (q.ndim - 2)
    qf = jnp.pad(q * (1.0 / math.sqrt(hd)), pad)
    qf = qf.transpose(0, 2, 3, 1, 4).reshape(B, K * G, Sp, hd)
    kf, vf = (jnp.pad(x, pad[:-1]).transpose(0, 2, 1, 3) for x in (k, v))
    out = jax.vmap(_kernel(Sp, K * G, window, interpret))(qf, kf, vf)
    return out.reshape(B, K, G, Sp, hd).transpose(0, 3, 1, 2, 4)[:, :S]
