"""Plain float32 reference of Mamba-2 (arXiv:2405.21060) for training.

Each block: RMSNorm, in_proj to (z, x, B, C, dt), depthwise causal conv
of width 4 over (x, B, C) with SiLU, dt = softplus(dt + dt_bias),
A = -exp(A_log), the SSD scan over heads of width 64 with one group of
B and C, the D skip, a gated RMSNorm y * silu(z), out_proj, residual.
Embeddings are tied.  The scan is the chunked "minimal SSD" listing of
the paper (section 6), here with chunks of 128 whatever the program
uses, so a fault at the program's chunk boundaries cannot cancel out.

The initializer reproduces the program's from the same key: the same
splits, shapes and scales, so the reference starts where the program
starts without taking anything the program made.
"""

from __future__ import annotations

import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from reference import (next_token_loss, normal, rms_norm, silu,  # noqa: E402
                       softplus)

REF_CHUNK = 128


def sizes(cfg: dict) -> dict:
    d = cfg["d_model"]
    di = cfg["ssm_expand"] * d
    n = cfg["ssm_state"]
    h = di // cfg["ssm_headdim"]
    return dict(d=d, di=di, n=n, h=h, p=cfg["ssm_headdim"],
                conv=di + 2 * n, proj=2 * di + 2 * n + h,
                w=cfg["conv_width"], L=cfg["num_layers"], V=cfg["vocab_size"])


def init(key, cfg: dict) -> dict:
    s = sizes(cfg)
    ke, _, *kl = jax.random.split(key, 2 + s["L"])
    layers = []
    for k in kl:
        k1, k2, k3 = jax.random.split(k, 3)
        layers.append({
            "ln": {"g": jnp.ones((s["d"],))},
            "in_proj": {"w": normal(k1, (s["d"], s["proj"]),
                                    1 / math.sqrt(s["d"]))},
            "conv_w": normal(k2, (s["w"], s["conv"]), 1 / math.sqrt(s["w"])),
            "conv_b": jnp.zeros((s["conv"],)),
            "A_log": jnp.log(jnp.linspace(1.0, 16.0, s["h"])),
            "D": jnp.ones((s["h"],)),
            "dt_bias": jnp.log(jnp.expm1(jnp.full((s["h"],), 0.01))),
            "norm": {"g": jnp.ones((s["di"],))},
            "out_proj": {"w": normal(k3, (s["di"], s["d"]),
                                     1 / math.sqrt(s["di"] * 2 * s["L"]))},
        })
    return {"embed": {"e": normal(ke, (s["V"], s["d"]), 0.02)},
            "ln_f": {"g": jnp.ones((s["d"],))},
            "layers": jax.tree.map(lambda *xs: jnp.stack(xs), *layers)}


def segsum(x):
    """out[..., i, j] = x[..., j+1] + ... + x[..., i] for j <= i, else
    -inf (the paper's stable form: a masked cumulative sum)."""
    T = x.shape[-1]
    xx = jnp.broadcast_to(x[..., None], x.shape + (T,))      # [..., i, j]
    below = jnp.tril(jnp.ones((T, T), bool), -1)
    xx = jnp.where(below, xx, 0.0)
    out = jnp.cumsum(xx, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), out, -jnp.inf)


def ssd(x, a, B, C, dot, chunk=REF_CHUNK):
    """x [b,s,h,p], a [b,s,h] (log decay), B, C [b,s,n] -> y [b,s,h,p]."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0, f"sequence {s} is not a multiple of {chunk}"
    c, q = s // chunk, chunk
    x = x.reshape(b, c, q, h, p)
    B = B.reshape(b, c, q, n)
    C = C.reshape(b, c, q, n)
    a = a.reshape(b, c, q, h).transpose(0, 3, 1, 2)           # [b,h,c,q]
    acum = jnp.cumsum(a, axis=-1)
    # within each chunk
    Lm = jnp.exp(segsum(a))                                   # [b,h,c,i,j]
    cb = dot("bcin,bcjn->bcij", C, B)
    w = cb[:, None] * Lm                                      # [b,h,c,i,j]
    y_diag = dot("bhcij,bcjhp->bcihp", w, x)
    # each chunk's final state
    decay_states = jnp.exp(acum[..., -1:] - acum)             # [b,h,c,q]
    xd = x * decay_states.transpose(0, 2, 3, 1)[..., None]    # [b,c,q,h,p]
    states = dot("bcqn,bcqhp->bchpn", B, xd)
    # states passed between chunks
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    decay_chunk = jnp.exp(segsum(jnp.pad(acum[..., -1], ((0, 0), (0, 0),
                                                         (1, 0)))))
    new_states = dot("bhzc,bchpn->bzhpn", decay_chunk, states)
    states = new_states[:, :-1]                               # [b,c,h,p,n]
    # state to output
    y_off = dot("bcqn,bchpn->bcqhp", C, states)
    y_off = y_off * jnp.exp(acum).transpose(0, 2, 3, 1)[..., None]
    return (y_diag + y_off).reshape(b, s, h, p)


def block(lp, x, cfg, dot):
    s = sizes(cfg)
    eps = cfg["norm_eps"]
    Bsz, S, _ = x.shape
    u = rms_norm(x, lp["ln"]["g"], eps)
    zxbcdt = dot("bsd,de->bse", u, lp["in_proj"]["w"])
    z = zxbcdt[..., :s["di"]]
    xbc = zxbcdt[..., s["di"]:s["di"] + s["conv"]]
    dt = zxbcdt[..., s["di"] + s["conv"]:]
    pad = jnp.pad(xbc, ((0, 0), (s["w"] - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + S] * lp["conv_w"][i] for i in range(s["w"]))
    xbc = silu(conv + lp["conv_b"])
    xs = xbc[..., :s["di"]].reshape(Bsz, S, s["h"], s["p"])
    Bm = xbc[..., s["di"]:s["di"] + s["n"]]
    Cm = xbc[..., s["di"] + s["n"]:]
    dt = softplus(dt + lp["dt_bias"])                          # [b,s,h]
    a = dt * -jnp.exp(lp["A_log"])
    y = ssd(xs * dt[..., None], a, Bm, Cm, dot)
    y = y + xs * lp["D"][:, None]
    y = rms_norm(y.reshape(Bsz, S, s["di"]) * silu(z), lp["norm"]["g"], eps)
    return x + dot("bse,ed->bsd", y, lp["out_proj"]["w"])


def loss(params, tokens, cfg: dict, dot):
    x = params["embed"]["e"][tokens]

    def body(x, lp):
        return block(lp, x, cfg, dot), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["layers"])
    x = rms_norm(x, params["ln_f"]["g"], cfg["norm_eps"])
    logits = dot("bsd,vd->bsv", x, params["embed"]["e"])
    return next_token_loss(logits, tokens)


def model_flops_per_token(cfg: dict, seq: int, chunk=None) -> float:
    """Operations one trained token needs, forward and backward once
    (backward = 2 x forward): 2 per weight of every matrix product
    (in_proj, out_proj, the tied unembedding), and the SSD's products at
    the source's chunk q (``chunk_size``; ``chunk`` counts at another):
    C.B^T within a chunk (2 q n), its product with x (2 q d_inner), the
    chunk states (2 n d_inner) and their read-out (2 n d_inner).  Counted
    in full per chunk, as attention's S x S is counted in full; the conv,
    norms and gates are left out.  The count does not follow the chunk
    the program runs at, so a change of the program's tiling moves the
    time and not the operations."""
    s = sizes(cfg)
    q = cfg["chunk_size"] if chunk is None else chunk
    proj = s["d"] * s["proj"] + s["di"] * s["d"]
    ssd_ops = 2 * q * s["n"] + 2 * q * s["di"] + 4 * s["n"] * s["di"]
    fwd = s["L"] * (2 * proj + ssd_ops) + 2 * s["V"] * s["d"]
    return 3.0 * fwd
