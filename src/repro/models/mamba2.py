"""Mamba2 — state-space duality (SSD) blocks, arXiv:2405.21060.

Training/prefill uses the chunked matmul-friendly SSD algorithm (quadratic
within a chunk, linear state passing between chunks) — the formulation that
maps onto the MXU.  Decode is the O(1) recurrent state update, which is what
makes ``long_500k`` tractable for this family.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels import ops

from . import layers as L
from .config import ArchConfig

CONV_WIDTH = 4


def segsum(a):
    """log-space segment-sum: out[..., i, j] = sum_{k=j+1..i} a[..., k]."""
    T = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), bool), 0)
    return jnp.where(mask, out, -jnp.inf)


@jax.named_scope("ssd")
def ssd_chunked(x, a, B, C, chunk: int):
    """SSD scan (discrete) — x:[b,s,h,p] a:[b,s,h] B,C:[b,s,n] (1 group).

    a is the per-step log-decay (log a_t = -dt*A). Returns y:[b,s,h,p] and
    the final state [b,h,p,n].
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    if s % chunk:
        # pad to a chunk multiple: x=0, a=0 (decay 1) steps are identities
        pad = chunk - s % chunk
        y, hlast = ssd_chunked(
            jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))),
            jnp.pad(a, ((0, 0), (0, pad), (0, 0))),
            jnp.pad(B, ((0, 0), (0, pad), (0, 0))),
            jnp.pad(C, ((0, 0), (0, pad), (0, 0))), chunk)
        return y[:, :s], hlast
    c = s // chunk
    xc = x.reshape(b, c, chunk, h, p)
    ac = a.reshape(b, c, chunk, h).transpose(0, 1, 3, 2)        # [b,c,h,q]
    Bc = B.reshape(b, c, chunk, n)
    Cc = C.reshape(b, c, chunk, n)

    # 1. intra-chunk (quadratic, causal-decay-masked "attention")
    Lmat = jnp.exp(segsum(ac))                                   # [b,c,h,q,q]
    scores = jnp.einsum("bcin,bcjn,bchij->bchij", Cc, Bc, Lmat)
    y_intra = jnp.einsum("bchij,bcjhp->bcihp", scores, xc)

    # 2. chunk states: decay-weighted sum of B x^T within each chunk
    # (state recurrence runs in f32 regardless of activation dtype)
    a_cum = jnp.cumsum(ac, axis=-1)                              # [b,c,h,q]
    decay_to_end = jnp.exp(a_cum[..., -1:] - a_cum)             # [b,c,h,q]
    states = jnp.einsum("bchq,bcqn,bcqhp->bchpn", decay_to_end, Bc,
                        xc).astype(jnp.float32)

    # 3. inter-chunk recurrence over c (scan)
    chunk_decay = jnp.exp(a_cum[..., -1]).astype(jnp.float32)    # [b,c,h]

    def step(hprev, inp):
        dec, st = inp
        hnew = dec[..., None, None] * hprev + st
        return hnew, hprev

    h0 = jnp.zeros((b, h, p, n), jnp.float32)
    hlast, hprevs = jax.lax.scan(
        step, h0, (chunk_decay.transpose(1, 0, 2), states.transpose(1, 0, 2, 3, 4)))
    hprevs = hprevs.transpose(1, 0, 2, 3, 4)                     # [b,c,h,p,n]

    # 4. inter-chunk output: C_t · (decay from chunk start) · h_prev
    decay_from_start = jnp.exp(a_cum)                            # [b,c,h,q]
    y_inter = jnp.einsum("bcqn,bchq,bchpn->bcqhp", Cc, decay_from_start,
                         hprevs.astype(x.dtype))

    # both terms accumulate in f32 (Lmat/decay are f32); emit in input dtype
    y = (y_intra + y_inter).astype(x.dtype).reshape(b, s, h, p)
    return y, hlast


@jax.named_scope("conv")
def causal_conv(x, w, b):
    """Depthwise causal conv, width W: x [B,S,C], w [W,C], b [C]."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(W))
    return out + b


class Mamba2LM:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.d_inner = cfg.ssm_expand * cfg.d_model
        self.headdim = 64
        self.nheads = cfg.ssm_heads or self.d_inner // self.headdim
        self.headdim = self.d_inner // self.nheads
        self.conv_dim = self.d_inner + 2 * cfg.ssm_state

    # -- params ------------------------------------------------------------
    def init_layer(self, key):
        cfg = self.cfg
        d, di, n, h = cfg.d_model, self.d_inner, cfg.ssm_state, self.nheads
        k1, k2, k3 = jax.random.split(key, 3)
        d_in_proj = 2 * di + 2 * n + h
        return {
            "ln": L.init_norm(d, cfg.pdt),
            "in_proj": L.init_linear(k1, d, d_in_proj, cfg.pdt),
            "conv_w": L._normal(k2, (CONV_WIDTH, self.conv_dim), cfg.pdt,
                                1.0 / math.sqrt(CONV_WIDTH)),
            "conv_b": jnp.zeros((self.conv_dim,), cfg.pdt),
            "A_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32),
            "D": jnp.ones((h,), jnp.float32),
            "dt_bias": jnp.log(jnp.expm1(jnp.full((h,), 0.01))).astype(jnp.float32),
            "norm": L.init_norm(di, cfg.pdt),
            "out_proj": L.init_linear(
                k3, di, d, cfg.pdt, scale=1.0 / math.sqrt(di * 2 * cfg.num_layers)),
        }

    def init(self, key):
        cfg = self.cfg
        from .transformer import stack_layer_params
        ke, kh, *kl = jax.random.split(key, 2 + cfg.num_layers)
        p = {"embed": L.init_embedding(ke, cfg.vocab_size, cfg.d_model, cfg.pdt),
             "ln_f": L.init_norm(cfg.d_model, cfg.pdt),
             "layers": stack_layer_params([self.init_layer(k) for k in kl])}
        if not cfg.tie_embeddings:
            p["head"] = L.init_linear(kh, cfg.d_model, cfg.vocab_size, cfg.pdt)
        return p

    # -- block --------------------------------------------------------------
    def _mix_in(self, lp, x):
        """in_proj + split + conv; returns z, xs, B, C, dt."""
        cfg = self.cfg
        di, n, h = self.d_inner, cfg.ssm_state, self.nheads
        with jax.named_scope("in_proj"):
            zxbcdt = L.linear(lp["in_proj"], x)
        z, xBC, dt = jnp.split(zxbcdt, [di, di + self.conv_dim], axis=-1)
        return z, xBC, dt

    def _block_seq(self, lp, x, ssd=None):
        """One block over whole sequences.  ``ssd`` is the SSD scan, by
        default ``kernels.ops.ssd_scan``: the Pallas op on the TPU, the jnp
        scan elsewhere."""
        cfg = self.cfg
        Bsz, S, _ = x.shape
        di, n, h = self.d_inner, cfg.ssm_state, self.nheads
        with jax.named_scope("norm"):
            hin = L.rms_norm(lp["ln"], x, cfg.norm_eps)
        z, xBC, dt = self._mix_in(lp, hin)
        xBC = jax.nn.silu(causal_conv(xBC, lp["conv_w"].astype(x.dtype),
                                      lp["conv_b"].astype(x.dtype)))
        xs, Bm, Cm = jnp.split(xBC, [di, di + n], axis=-1)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])  # [B,S,h]
        A = -jnp.exp(lp["A_log"])                                     # [h]
        a = (dt * A).astype(jnp.float32)                              # log-decay
        xh = xs.reshape(Bsz, S, h, self.headdim)
        xin = xh * dt.astype(x.dtype)[..., None]
        y, _ = (ssd or ops.ssd_scan)(xin, a, Bm.astype(x.dtype),
                                     Cm.astype(x.dtype), chunk=cfg.ssm_chunk)
        y = y + xh * lp["D"].astype(x.dtype)[:, None]
        y = y.reshape(Bsz, S, di)
        with jax.named_scope("norm"):
            y = L.rms_norm(lp["norm"], y * jax.nn.silu(z), cfg.norm_eps)
        with jax.named_scope("out_proj"):
            return x + L.linear(lp["out_proj"], y)

    # -- forward / loss --------------------------------------------------------
    def forward(self, params, ids):
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = L.embed(params["embed"], ids).astype(cfg.adt)

        def body(x, lp):
            return self._block_seq(lp, x), None

        body_fn = jax.checkpoint(body) if cfg.remat else body
        x, _ = jax.lax.scan(body_fn, x, params["layers"])
        with jax.named_scope("norm"):
            x = L.rms_norm(params["ln_f"], x, cfg.norm_eps)
        with jax.named_scope("unembed"):
            if cfg.tie_embeddings:
                return L.unembed(params["embed"], x), 0.0
            return L.linear(params["head"], x).astype(jnp.float32), 0.0

    def loss(self, params, batch):
        logits, _ = self.forward(params, batch["tokens"])
        with jax.named_scope("cross_entropy"):
            return L.cross_entropy(logits[:, :-1], batch["labels"][:, 1:],
                                   batch.get("mask", None))

    # -- decode (recurrent; O(1) in sequence length) ------------------------------
    def init_cache(self, B: int, max_len: int) -> dict:
        cfg = self.cfg
        Lr, h, p, n = cfg.num_layers, self.nheads, self.headdim, cfg.ssm_state
        return {
            "conv": jnp.zeros((Lr, B, CONV_WIDTH - 1, self.conv_dim), cfg.adt),
            "ssm": jnp.zeros((Lr, B, h, p, n), cfg.adt),
            "pos": jnp.zeros((), jnp.int32),
        }

    def prefill(self, params, ids, max_len: int):
        """Simple prefill: full forward for logits + recurrent state replay
        is avoided by running the chunked scan and capturing final states."""
        cfg = self.cfg
        x = L.embed(params["embed"], ids).astype(cfg.adt)
        B, S = ids.shape
        convs, ssms = [], []

        def run_layer(lp, x):
            Bsz, S, _ = x.shape
            di, n, h = self.d_inner, cfg.ssm_state, self.nheads
            hin = L.rms_norm(lp["ln"], x, cfg.norm_eps)
            z, xBC, dt = self._mix_in(lp, hin)
            conv_tail = xBC[:, -(CONV_WIDTH - 1):, :]
            xBC = jax.nn.silu(causal_conv(xBC, lp["conv_w"].astype(x.dtype),
                                          lp["conv_b"].astype(x.dtype)))
            xs, Bm, Cm = jnp.split(xBC, [di, di + n], axis=-1)
            dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
            A = -jnp.exp(lp["A_log"])
            a = (dt * A).astype(jnp.float32)
            xh = xs.reshape(Bsz, S, h, self.headdim)
            y, hlast = ssd_chunked(xh * dt.astype(x.dtype)[..., None], a,
                                   Bm.astype(x.dtype), Cm.astype(x.dtype),
                                   cfg.ssm_chunk)
            y = y + xh * lp["D"].astype(x.dtype)[:, None]
            y = y.reshape(Bsz, S, di)
            y = L.rms_norm(lp["norm"], y * jax.nn.silu(z), cfg.norm_eps)
            return x + L.linear(lp["out_proj"], y), conv_tail, hlast

        def body(x, lp):
            xo, conv_tail, hlast = run_layer(lp, x)
            return xo, (conv_tail, hlast)

        x, (convs, ssms) = jax.lax.scan(body, x, params["layers"])
        x = L.rms_norm(params["ln_f"], x, cfg.norm_eps)
        logits = (L.unembed(params["embed"], x) if cfg.tie_embeddings
                  else L.linear(params["head"], x).astype(jnp.float32))
        cache = {"conv": convs.astype(cfg.adt), "ssm": ssms.astype(cfg.adt),
                 "pos": jnp.array(S, jnp.int32)}
        return logits[:, -1], cache

    def decode_step(self, params, cache, ids):
        cfg = self.cfg
        B = ids.shape[0]
        di, n, h = self.d_inner, cfg.ssm_state, self.nheads
        x = L.embed(params["embed"], ids).astype(cfg.adt)   # [B,1,D]

        def body(x, lp_cache):
            lp, conv_st, ssm_st = lp_cache
            hin = L.rms_norm(lp["ln"], x, cfg.norm_eps)
            z, xBC, dt = self._mix_in(lp, hin)              # [B,1,*]
            hist = jnp.concatenate([conv_st, xBC], axis=1)  # [B,W,convdim]
            w = lp["conv_w"].astype(x.dtype)
            conv_out = jnp.einsum("bwc,wc->bc", hist, w) + lp["conv_b"].astype(x.dtype)
            xBC1 = jax.nn.silu(conv_out)[:, None]
            xs, Bm, Cm = jnp.split(xBC1, [di, di + n], axis=-1)
            dtv = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + lp["dt_bias"])  # [B,h]
            A = -jnp.exp(lp["A_log"])
            a = jnp.exp(dtv * A)                            # [B,h]
            xh = xs[:, 0].reshape(B, h, self.headdim)
            dx = xh * dtv.astype(x.dtype)[..., None]        # [B,h,p]
            ssm_new = (a.astype(x.dtype)[..., None, None] * ssm_st
                       + jnp.einsum("bhp,bn->bhpn", dx, Bm[:, 0]))
            y = jnp.einsum("bhpn,bn->bhp", ssm_new, Cm[:, 0])
            y = y + xh * lp["D"].astype(x.dtype)[:, None]
            y = y.reshape(B, 1, di)
            y = L.rms_norm(lp["norm"], y * jax.nn.silu(z), cfg.norm_eps)
            return x + L.linear(lp["out_proj"], y), (hist[:, 1:], ssm_new)

        x, (conv_new, ssm_new) = jax.lax.scan(
            body, x, (params["layers"], cache["conv"], cache["ssm"]))
        x = L.rms_norm(params["ln_f"], x, cfg.norm_eps)
        logits = (L.unembed(params["embed"], x) if cfg.tie_embeddings
                  else L.linear(params["head"], x).astype(jnp.float32))
        return logits[:, 0], {"conv": conv_new, "ssm": ssm_new,
                              "pos": cache["pos"] + 1}
