"""Plain float32 reference of Granite-3.0-1B-A400M (model type
``granitemoe``, ibm-granite/granite-3.0-1b-a400m-base) for training.

The embedding (tied) is scaled by ``embedding_multiplier``.  Each block:
RMSNorm; GQA attention (16 query heads over 8 KV heads of width 64, RoPE
with theta 1e4 in the rotate-half form, causal, softmax scale
``attention_multiplier``), its output scaled by ``residual_multiplier`` and
added to the residual; RMSNorm; the router's logits, each token's top 8
of 32 experts and its gates, a softmax over those 8 logits; SwiGLU experts
silu(x Wg) * (x Wi) Wo; their gate-weighted sum scaled by
``residual_multiplier`` and added.  A final RMSNorm, logits over the tied
embedding divided by ``logits_scaling``, next-token cross-entropy.  The
loss has no load-balancing term (the source's ``output_router_logits`` is
false).

The MoE is written densely, as the plain form: every expert runs on every
token, and the output is the sum over experts of gate x expert output, the
gate 0 outside the token's top 8.  That is 4x the routed form's expert
work, and what the routed form must equal.

Two departures change no arithmetic and let the reference fit one chip
beside its state: each layer runs under ``jax.checkpoint``, and attention
is computed for blocks of 1024 queries at a time (each block's softmax
over all its keys), each block under ``jax.checkpoint``.

The initializer reproduces the program's from the same key: the same
splits, shapes and scales.
"""

from __future__ import annotations

import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from reference import next_token_loss, normal, rms_norm, silu  # noqa: E402

QUERY_BLOCK = 1024


def sizes(cfg: dict) -> dict:
    d, H = cfg["d_model"], cfg["num_heads"]
    return dict(d=d, H=H, K=cfg["num_kv_heads"], hd=d // H, F=cfg["d_ff"],
                E=cfg["num_experts"], k=cfg["top_k"], L=cfg["num_layers"],
                V=cfg["vocab_size"])


def init(key, cfg: dict) -> dict:
    s = sizes(cfg)
    d, H, K, hd, F, E, L = (s[n] for n in ("d", "H", "K", "hd", "F", "E",
                                           "L"))
    ke, _, _, *kl = jax.random.split(key, 3 + L)
    layers = []
    for k in kl:
        ka, km, _, _ = jax.random.split(k, 4)
        kq, kk, kv, ko = jax.random.split(ka, 4)
        kr, ki, kg, kw = jax.random.split(km, 4)
        layers.append({
            "ln1": {"g": jnp.ones((d,))},
            "ln2": {"g": jnp.ones((d,))},
            "attn": {
                "wq": {"w": normal(kq, (d, H * hd), 1 / math.sqrt(d))},
                "wk": {"w": normal(kk, (d, K * hd), 1 / math.sqrt(d))},
                "wv": {"w": normal(kv, (d, K * hd), 1 / math.sqrt(d))},
                "wo": {"w": normal(ko, (H * hd, d),
                                   1 / math.sqrt(H * hd * 2 * L))},
            },
            "moe": {
                "router": {"w": normal(kr, (d, E), 1 / math.sqrt(d))},
                "wi": normal(ki, (E, d, F), 1 / math.sqrt(d)),
                "wg": normal(kg, (E, d, F), 1 / math.sqrt(d)),
                "wo": normal(kw, (E, F, d), 1 / math.sqrt(F * 2 * L)),
            },
        })
    return {"embed": {"e": normal(ke, (s["V"], d), 0.02)},
            "ln_f": {"g": jnp.ones((d,))},
            "layers": jax.tree.map(lambda *xs: jnp.stack(xs), *layers)}


def rope(x, theta):
    """x [b, s, ..., hd]: the first and second halves of each head rotated
    by position x frequency (rotate-half form)."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv        # [s, hd/2]
    ang = ang.reshape((1, S) + (1,) * (x.ndim - 3) + (hd // 2,))
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def attention(q, k, v, scale, dot):
    """q [b, s, K, G, hd], k, v [b, s, K, hd]: causal softmax attention,
    a block of queries at a time."""
    S = q.shape[1]
    nq = min(QUERY_BLOCK, S)

    @jax.checkpoint
    def block(qb, start):
        sc = dot("bqkgh,btkh->bkgqt", qb, k) * scale
        qpos = start + jnp.arange(qb.shape[1])[:, None]
        sc = jnp.where(jnp.arange(S)[None, :] <= qpos, sc, -jnp.inf)
        return dot("bkgqt,btkh->bqkgh", jax.nn.softmax(sc, axis=-1), v)

    return jnp.concatenate([block(q[:, i:i + nq], i)
                            for i in range(0, S, nq)], axis=1)


def block(lp, x, cfg, dot):
    s = sizes(cfg)
    eps, rm = cfg["norm_eps"], cfg["residual_multiplier"]
    B, S, d = x.shape
    K, G, hd = s["K"], s["H"] // s["K"], s["hd"]
    a = lp["attn"]
    h = rms_norm(x, lp["ln1"]["g"], eps)
    q = dot("bsd,de->bse", h, a["wq"]["w"]).reshape(B, S, K, G, hd)
    kk = dot("bsd,de->bse", h, a["wk"]["w"]).reshape(B, S, K, hd)
    v = dot("bsd,de->bse", h, a["wv"]["w"]).reshape(B, S, K, hd)
    q, kk = rope(q, cfg["rope_theta"]), rope(kk, cfg["rope_theta"])
    o = attention(q, kk, v, cfg["attention_multiplier"], dot)
    x = x + rm * dot("bse,ed->bsd", o.reshape(B, S, -1), a["wo"]["w"])

    m = lp["moe"]
    h = rms_norm(x, lp["ln2"]["g"], eps)
    logits = dot("bsd,de->bse", h, m["router"]["w"])              # [b,s,E]
    _, top = jax.lax.top_k(logits, s["k"])
    chosen = jnp.sum(jax.nn.one_hot(top, s["E"]), axis=-2) > 0
    gates = jax.nn.softmax(jnp.where(chosen, logits, -jnp.inf), axis=-1)
    g = dot("bsd,edf->bsef", h, m["wg"])
    i = dot("bsd,edf->bsef", h, m["wi"])
    y = dot("bsef,efd->bsed", silu(g) * i, m["wo"])               # [b,s,E,d]
    return x + rm * jnp.sum(gates[..., None] * y, axis=2)


def loss(params, tokens, cfg: dict, dot):
    e = params["embed"]["e"]
    x = e[tokens] * cfg["embedding_multiplier"]

    def body(x, lp):
        return block(lp, x, cfg, dot), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["layers"])
    x = rms_norm(x, params["ln_f"]["g"], cfg["norm_eps"])
    logits = dot("bsd,vd->bsv", x, e) / cfg["logits_scaling"]
    return next_token_loss(logits, tokens)


def model_flops_per_token(cfg: dict, seq: int) -> float:
    """Operations one trained token needs, forward and backward once
    (backward = 2 x forward): 2 per active weight of every matrix product
    (the attention projections, the router, the token's 8 SwiGLU experts,
    the tied unembedding), and attention's two products over the whole
    sequence, 4 x seq x (heads x head width) forward, the causal mask
    ignored.  Routing, dispatch and combine, norms and gates are left out,
    and so is the recompute of ``remat``."""
    s = sizes(cfg)
    d, H, K, hd = s["d"], s["H"], s["K"], s["hd"]
    attn = d * H * hd + 2 * d * K * hd + H * hd * d
    experts = s["k"] * 3 * d * s["F"]
    fwd = (s["L"] * (2 * (attn + d * s["E"] + experts) + 4 * seq * H * hd)
           + 2 * s["V"] * d)
    return 3.0 * fwd


# grouped products an MoE layer runs in a training step: 3 in the forward
# (x Wg, x Wi, h Wo), 3 again in the backward's recompute of the layer
# (remat), and for each of the 3 its input gradient and its weight gradient
PRODUCTS_PER_LAYER = 12


def expert_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    """Operations of the expert products in one training step: each of
    the 12 grouped products of a layer is 2 x rows x d x F, over the
    batch x seq x top_k (token, expert) rows."""
    s = sizes(cfg)
    rows = batch * seq * s["k"]
    return float(s["L"] * PRODUCTS_PER_LAYER * 2 * rows * s["d"] * s["F"])


def expert_bytes_per_step(cfg: dict, batch: int, seq: int) -> float:
    """The least HBM traffic of those products in bf16: each reads its two
    operands and writes its result once.  A product of rows [r, a] and
    experts [E, a, b] into [r, b], and either of its gradients, moves
    r x a + r x b + E x a x b elements; (a, b) is (d, F) or (F, d)."""
    s = sizes(cfg)
    rows = batch * seq * s["k"]
    d, F = s["d"], s["F"]
    return float(s["L"] * PRODUCTS_PER_LAYER * 2
                 * (rows * (d + F) + s["E"] * d * F))
