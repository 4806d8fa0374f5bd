"""Pallas TPU fused attention (flash) kernel.

TPU-native adaptation: the kernel tiles Q into ``q_block`` rows held in VMEM,
streams K/V blocks through VMEM, and keeps the running-softmax state
(m, l, acc) in f32 VMEM scratch so nothing of size O(S*T) ever exists.  The
MXU sees [q_block, hd] x [hd, kv_block] and [q_block, kv_block] x
[kv_block, hd] matmuls — both dims multiples of 128 for the standard configs.

Layout: q [BH, S, hd] (batch x query-head folded), k/v [BK, T, hd] with
``group`` query heads per kv head (GQA: kv index = head index // group).

Causal and sliding-window masking are applied from global block indices;
fully-masked blocks are skipped via pl.when.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window, group: int,
            q_block: int, kv_block: int, T: int, q_offset: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q0 = qi * q_block + q_offset
    k0 = kj * kv_block

    # skip key blocks entirely above the causal diagonal / outside window
    live = jnp.array(True)
    if causal:
        live &= k0 <= q0 + q_block - 1
    if window is not None:
        live &= k0 + kv_block - 1 > q0 - window

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # [qb, hd]
        k = k_ref[0].astype(jnp.float32)          # [kb, hd]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 0)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 1)
        mask = kpos < T
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                              preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_block",
                                             "kv_block", "interpret",
                                             "q_offset"))
def flash_attention_tpu(q, k, v, *, causal: bool = True, window=None,
                        q_block: int = 512, kv_block: int = 512,
                        interpret: bool = False, q_offset: int = 0):
    """q: [B,S,K,G,hd], k/v: [B,T,K,hd] -> [B,S,K,G,hd]."""
    B, S, K, G, hd = q.shape
    T = k.shape[1]
    q_block = min(q_block, S)
    kv_block = min(kv_block, T)
    Sp = -(-S // q_block) * q_block
    Tp = -(-T // kv_block) * kv_block
    qf = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0), (0, 0), (0, 0)))
    kf = jnp.pad(k, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    vf = jnp.pad(v, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    # fold heads: q -> [B*K*G, Sp, hd]; kv -> [B*K, Tp, hd]
    qf = qf.transpose(0, 2, 3, 1, 4).reshape(B * K * G, Sp, hd)
    kf = kf.transpose(0, 2, 1, 3).reshape(B * K, Tp, hd)
    vf = vf.transpose(0, 2, 1, 3).reshape(B * K, Tp, hd)

    grid = (B * K * G, Sp // q_block, Tp // kv_block)
    kernel = functools.partial(
        _kernel, scale=1.0 / math.sqrt(hd), causal=causal, window=window,
        group=G, q_block=q_block, kv_block=kv_block, T=T, q_offset=q_offset)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_block, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, kv_block, hd), lambda b, i, j, G=G: (b // G, j, 0)),
            pl.BlockSpec((1, kv_block, hd), lambda b, i, j, G=G: (b // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * K * G, Sp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block,), jnp.float32),      # running max  m
            pltpu.VMEM((q_block,), jnp.float32),      # running norm l
            pltpu.VMEM((q_block, hd), jnp.float32),   # accumulator
        ],
        interpret=interpret,
    )(qf, kf, vf)
    out = out.reshape(B, K, G, Sp, hd).transpose(0, 3, 1, 2, 4)
    return out[:, :S]

