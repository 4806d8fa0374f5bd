"""Core layers: norms, RoPE, GQA attention (+SWA, QKV bias), MLPs, MoE.

Pure-functional: ``init_*`` builds parameter pytrees, ``apply``-style
functions consume them.  All matmul dims are kept multiples of 128 where the
configs allow, activations run in ``cfg.dtype`` with fp32 softmax/norm
accumulation — the TPU-native layout expected by the MXU.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.tracing import flight_recorder
from repro.kernels import ops as kops

# ---------------------------------------------------------------------------
# initializers


def _normal(key, shape, dtype, scale):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def init_linear(key, d_in, d_out, dtype, bias=False, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": _normal(key, (d_in, d_out), dtype, scale)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p, x):
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def init_norm(d, dtype):
    return {"g": jnp.ones((d,), dtype)}


def rms_norm(p, x, eps=1e-5):
    h = x.astype(jnp.float32)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    return (h * p["g"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE


def rope_freqs(hd: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [..., S, H, hd]; positions: broadcastable to [..., S]."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)                       # [hd/2]
    ang = positions[..., None].astype(jnp.float32) * inv  # [..., S, hd/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention


def init_attention(key, cfg):
    ks = jax.random.split(key, 4)
    d, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    return {
        "wq": init_linear(ks[0], d, H * hd, cfg.pdt, bias=cfg.qkv_bias),
        "wk": init_linear(ks[1], d, K * hd, cfg.pdt, bias=cfg.qkv_bias),
        "wv": init_linear(ks[2], d, K * hd, cfg.pdt, bias=cfg.qkv_bias),
        "wo": init_linear(ks[3], H * hd, d, cfg.pdt,
                          scale=1.0 / math.sqrt(H * hd * 2 * cfg.num_layers)),
    }


FLASH_THRESHOLD = 4096 * 4096   # S*T above which blockwise attention is used


def _sdpa(q, k, v, mask, *, causal: bool = False,
          window: Optional[int] = None):
    """Grouped scaled-dot-product attention.

    q: [B,S,K,G,hd] (G = query groups per kv head), k/v: [B,T,K,hd],
    mask: [B,1,S,T] or broadcastable boolean (True = attend).

    On the TPU, causal self-attention (``causal``, S == T > 1) takes the
    fused op of ``kops.flash_attention`` at any S.  Otherwise large S*T
    (long-context prefill) takes the blockwise jnp path so O(S*T) logits are
    never materialized, and the rest the einsum-softmax-einsum path.  Each
    trace records ``attention.kernel`` in the flight recorder: 1 where the
    site is built with the fused Pallas op, 0 otherwise.
    """
    S, T = q.shape[1], k.shape[1]
    fused = causal and S == T > 1 and kops.on_tpu()
    flight_recorder().counter("attention.kernel", int(fused))
    if fused:
        return kops.flash_attention(q, k, v, window=window)
    if causal and S > 1 and S * T > FLASH_THRESHOLD:
        from ..kernels.ref import flash_attention_ref
        return flash_attention_ref(q, k, v, causal=True, window=window)
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bskgh,btkh->bkgst", q, k).astype(jnp.float32) * scale
    logits = jnp.where(mask[:, None, None] if mask.ndim == 3 else mask,
                       logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", w, v)
    return out


def scale_queries(cfg, q):
    """Queries for a softmax scale of ``cfg.attention_multiplier``: every
    attention path scales by 1/sqrt(hd), so the queries take the rest
    (Granite's 1/64 at hd 64 is 0.125 here, exact in bf16)."""
    if cfg.attention_multiplier is None:
        return q
    return q * (cfg.attention_multiplier * math.sqrt(q.shape[-1]))


def causal_mask(S: int, T: int, offset: int = 0,
                window: Optional[int] = None) -> jnp.ndarray:
    """[S,T] boolean mask; query i attends key j iff j <= i+offset (and
    within the sliding window if given)."""
    qpos = jnp.arange(S)[:, None] + offset
    kpos = jnp.arange(T)[None, :]
    m = kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    return m


def attention(p, cfg, x, positions, mask, kv=None, *, causal=False):
    """kv: optional (k, v) override for cross-attention / cached decode."""
    B, S, d = x.shape
    if getattr(cfg, "ablate_attention", False) and kv is None:
        # measurement-only path (§Perf): QKV/O projections run, the O(S*T)
        # mixing is skipped — isolates attention-mixing HBM traffic
        H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
        qa = linear(p["wq"], x)
        ka = linear(p["wk"], x).reshape(B, S, K, hd)
        va = linear(p["wv"], x).reshape(B, S, K, hd)
        return linear(p["wo"], qa * 0.001), (ka, va)
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    G = H // K
    q = linear(p["wq"], x).reshape(B, S, H, hd)
    q = apply_rope(q, positions, cfg.rope_theta) if cfg.rope_theta else q
    if kv is None:
        k = linear(p["wk"], x).reshape(B, S, K, hd)
        v = linear(p["wv"], x).reshape(B, S, K, hd)
        k = apply_rope(k, positions, cfg.rope_theta) if cfg.rope_theta else k
    else:
        k, v = kv
    qg = scale_queries(cfg, q).reshape(B, S, K, G, hd)
    out = _sdpa(qg, k, v, mask, causal=causal and kv is None,
                window=cfg.sliding_window)
    out = out.reshape(B, S, H * hd)
    return linear(p["wo"], out), (k, v)


# ---------------------------------------------------------------------------
# MLPs


def init_mlp(key, cfg, d_ff=None):
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    d = cfg.d_model
    out_scale = 1.0 / math.sqrt(d_ff * 2 * cfg.num_layers)
    if cfg.mlp == "swiglu":
        return {"wi": init_linear(ks[0], d, d_ff, cfg.pdt),
                "wg": init_linear(ks[1], d, d_ff, cfg.pdt),
                "wo": init_linear(ks[2], d_ff, d, cfg.pdt, scale=out_scale)}
    return {"wi": init_linear(ks[0], d, d_ff, cfg.pdt),
            "wo": init_linear(ks[2], d_ff, d, cfg.pdt, scale=out_scale)}


def mlp(p, cfg, x):
    if cfg.mlp == "swiglu":
        h = jax.nn.silu(linear(p["wg"], x)) * linear(p["wi"], x)
    else:
        h = jax.nn.gelu(linear(p["wi"], x))
    return linear(p["wo"], h)


# ---------------------------------------------------------------------------
# Mixture of Experts (dropless top-k, grouped expert products)


def init_moe(key, cfg):
    ks = jax.random.split(key, 4)
    d, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(F * 2 * cfg.num_layers)
    return {
        "router": init_linear(ks[0], d, E, jnp.float32),
        "wi": _normal(ks[1], (E, d, F), cfg.pdt, s_in),
        "wg": _normal(ks[2], (E, d, F), cfg.pdt, s_in),
        "wo": _normal(ks[3], (E, F, d), cfg.pdt, s_out),
    }


@jax.custom_vjp
def permute_rows(x, idx, inv):
    """``x[idx]`` for a permutation ``idx`` of x's rows whose inverse is
    ``inv``: a gather, and its gradient the gather by ``inv`` (where
    autodiff would scatter)."""
    return x.at[idx].get(mode="promise_in_bounds", unique_indices=True)


def _permute_rows_fwd(x, idx, inv):
    return permute_rows(x, idx, inv), (idx, inv)


def _permute_rows_bwd(res, g):
    idx, inv = res
    return permute_rows(g, inv, idx), None, None


permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def moe(p, cfg, x):
    """Dropless top-k MoE, routed as Granite's ``GraniteMoeTopKGating``:
    router logits in f32, each token's top ``top_k`` experts, its gates a
    softmax over those k logits.  The token's k (token, slot) rows are
    sorted by expert, the SwiGLU experts run as grouped products over each
    expert's rows, and the k outputs are put back and summed under their
    gates.  Sorting and putting back are gathers both ways
    (``permute_rows``).  Every token reaches all k of its experts: there
    is no capacity."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.top_k
    tokens = x.reshape(-1, D)
    with jax.named_scope("router"):
        logits = jnp.dot(tokens.astype(jnp.float32), p["router"]["w"],
                         precision=jax.lax.Precision.HIGHEST)      # [T,E]
        top, idx = jax.lax.top_k(logits, K)                        # [T,K]
        gates = jax.nn.softmax(top, axis=-1)
    with jax.named_scope("moe_dispatch"):
        expert = idx.reshape(-1)                                   # [T*K]
        n = expert.shape[0]
        order = jnp.argsort(expert, stable=True)
        back = jnp.zeros((n,), jnp.int32).at[order].set(
            jnp.arange(n, dtype=jnp.int32))
        rows = permute_rows(jnp.repeat(tokens, K, axis=0), order, back)
        # a sum of one-hots: bincount's scatter-add serializes on repeated
        # experts, so its time would follow the routing
        group_sizes = jnp.sum(jax.nn.one_hot(expert, E, dtype=jnp.int32), 0)
    with jax.named_scope("experts"):
        y = kops.grouped_swiglu(rows, p["wg"].astype(x.dtype),
                                p["wi"].astype(x.dtype),
                                p["wo"].astype(x.dtype), group_sizes)
    with jax.named_scope("moe_combine"):
        y = permute_rows(y, back, order).reshape(-1, K, D)
        out = jnp.einsum("tkd,tk->td", y.astype(jnp.float32), gates)
    return out.astype(x.dtype).reshape(B, S, D)


# ---------------------------------------------------------------------------
# embedding / head


def init_embedding(key, vocab, d, dtype):
    return {"e": _normal(key, (vocab, d), dtype, 0.02)}


def embed(p, ids):
    return p["e"][ids]


def unembed(p, x, dtype=jnp.float32):
    return (x @ p["e"].T.astype(x.dtype)).astype(dtype)


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0] - lse
    loss = -ll
    if mask is not None:
        return jnp.sum(loss * mask) / jnp.maximum(jnp.sum(mask), 1)
    return jnp.mean(loss)
