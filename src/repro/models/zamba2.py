"""Zamba2-style hybrid: Mamba2 backbone with a *shared* attention block
(arXiv:2411.15242).  One full attention+MLP block's parameters are reused at
every group boundary; each invocation keeps its own KV cache at decode time.

The group size is ``cfg.attn_every`` (must divide ``num_layers``); the
forward pass is a two-level scan: outer over groups (shared attention +
inner scan over that group's Mamba2 layers).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import layers as L
from .config import ArchConfig
from .mamba2 import CONV_WIDTH, Mamba2LM, causal_conv, ssd_chunked
from .transformer import stack_layer_params


class Zamba2LM:
    def __init__(self, cfg: ArchConfig):
        assert cfg.attn_every and cfg.num_layers % cfg.attn_every == 0, \
            f"attn_every {cfg.attn_every} must divide num_layers {cfg.num_layers}"
        self.cfg = cfg
        self.mamba = Mamba2LM(cfg)
        self.groups = cfg.num_layers // cfg.attn_every

    # -- params ------------------------------------------------------------
    def init(self, key):
        cfg = self.cfg
        ke, kh, ka, km, *kl = jax.random.split(key, 4 + cfg.num_layers)
        p = {"embed": L.init_embedding(ke, cfg.vocab_size, cfg.d_model, cfg.pdt),
             "ln_f": L.init_norm(cfg.d_model, cfg.pdt),
             "shared": {"ln1": L.init_norm(cfg.d_model, cfg.pdt),
                        "ln2": L.init_norm(cfg.d_model, cfg.pdt),
                        "attn": L.init_attention(ka, cfg),
                        "mlp": L.init_mlp(km, cfg)},
             "layers": stack_layer_params(
                 [self.mamba.init_layer(k) for k in kl])}
        if not cfg.tie_embeddings:
            p["head"] = L.init_linear(kh, cfg.d_model, cfg.vocab_size, cfg.pdt)
        return p

    def _group_params(self, params):
        """Reshape stacked layer params [L,...] -> [G, g, ...]."""
        G, g = self.groups, self.cfg.attn_every
        return jax.tree.map(lambda v: v.reshape((G, g) + v.shape[1:]),
                            params["layers"])

    def _shared_block(self, sp, x, positions, mask, kv=None):
        cfg = self.cfg
        a, new_kv = L.attention(sp["attn"], cfg,
                                L.rms_norm(sp["ln1"], x, cfg.norm_eps),
                                positions, mask, kv=kv, causal=(kv is None))
        x = x + a
        x = x + L.mlp(sp["mlp"], cfg, L.rms_norm(sp["ln2"], x, cfg.norm_eps))
        return x, new_kv

    # -- forward / loss -------------------------------------------------------
    def forward(self, params, ids):
        cfg = self.cfg
        B, S = ids.shape
        x = L.embed(params["embed"], ids).astype(cfg.adt)
        positions = jnp.arange(S)
        mask = L.causal_mask(S, S)
        gp = self._group_params(params)
        sp = params["shared"]

        def inner(x, lp):
            # the jnp scan on every backend: the Pallas op is tuned and
            # compiled for mamba2-370m's widths only
            return self.mamba._block_seq(lp, x, ssd=ssd_chunked), None

        inner_fn = jax.checkpoint(inner) if cfg.remat else inner

        def outer(x, glp):
            x, _ = self._shared_block(sp, x, positions, mask)
            x, _ = jax.lax.scan(inner_fn, x, glp)
            return x, None

        x, _ = jax.lax.scan(outer, x, gp)
        x = L.rms_norm(params["ln_f"], x, cfg.norm_eps)
        if cfg.tie_embeddings:
            return L.unembed(params["embed"], x), 0.0
        return L.linear(params["head"], x).astype(jnp.float32), 0.0

    def loss(self, params, batch):
        logits, _ = self.forward(params, batch["tokens"])
        return L.cross_entropy(logits[:, :-1], batch["labels"][:, 1:],
                               batch.get("mask", None))

    # -- decode -----------------------------------------------------------------
    def init_cache(self, B: int, max_len: int) -> dict:
        cfg = self.cfg
        m = self.mamba
        G, K, hd = self.groups, cfg.num_kv_heads, cfg.hd
        return {
            "conv": jnp.zeros((cfg.num_layers, B, CONV_WIDTH - 1, m.conv_dim),
                              cfg.adt),
            "ssm": jnp.zeros((cfg.num_layers, B, m.nheads, m.headdim,
                              cfg.ssm_state), cfg.adt),
            "k": jnp.zeros((G, B, max_len, K, hd), cfg.adt),
            "v": jnp.zeros((G, B, max_len, K, hd), cfg.adt),
            "kpos": jnp.full((max_len,), -1, jnp.int32),
            "pos": jnp.zeros((), jnp.int32),
        }

    def prefill(self, params, ids, max_len: int):
        cfg = self.cfg
        B, S = ids.shape
        x = L.embed(params["embed"], ids).astype(cfg.adt)
        positions = jnp.arange(S)
        mask = L.causal_mask(S, S)
        gp = self._group_params(params)
        sp = params["shared"]
        cache = self.init_cache(B, max_len)
        ks, vs, convs, ssms = [], [], [], []

        def inner(x, lp):
            # reuse the mamba prefill body to capture states
            xo, conv_tail, hlast = None, None, None
            xo, (conv_tail, hlast) = self._mamba_prefill_layer(lp, x)
            return xo, (conv_tail, hlast)

        x_cur = x
        for gi in range(self.groups):
            x_cur, (k, v) = self._shared_block(sp, x_cur, positions, mask)
            ks.append(k)
            vs.append(v)
            glp = jax.tree.map(lambda a: a[gi], gp)
            x_cur, (ct, hl) = jax.lax.scan(inner, x_cur, glp)
            convs.append(ct)
            ssms.append(hl)
        x_cur = L.rms_norm(params["ln_f"], x_cur, cfg.norm_eps)
        logits = (L.unembed(params["embed"], x_cur) if cfg.tie_embeddings else
                  L.linear(params["head"], x_cur).astype(jnp.float32))
        cache["k"] = cache["k"].at[:, :, :S].set(jnp.stack(ks))
        cache["v"] = cache["v"].at[:, :, :S].set(jnp.stack(vs))
        cache["kpos"] = cache["kpos"].at[:S].set(jnp.arange(S))
        cache["conv"] = jnp.concatenate(convs).astype(cfg.adt)
        cache["ssm"] = jnp.concatenate(ssms).astype(cfg.adt)
        cache["pos"] = jnp.array(S, jnp.int32)
        return logits[:, -1], cache

    def _mamba_prefill_layer(self, lp, x):
        """One mamba layer forward capturing (conv tail, final ssm state)."""
        cfg = self.cfg
        m = self.mamba
        Bsz, S, _ = x.shape
        di, n, h = m.d_inner, cfg.ssm_state, m.nheads
        hin = L.rms_norm(lp["ln"], x, cfg.norm_eps)
        z, xBC, dt = m._mix_in(lp, hin)
        conv_tail = xBC[:, -(CONV_WIDTH - 1):, :]
        xBC = jax.nn.silu(causal_conv(xBC, lp["conv_w"].astype(x.dtype),
                                      lp["conv_b"].astype(x.dtype)))
        xs, Bm, Cm = jnp.split(xBC, [di, di + n], axis=-1)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
        A = -jnp.exp(lp["A_log"])
        a = (dt * A).astype(jnp.float32)
        xh = xs.reshape(Bsz, S, h, m.headdim)
        y, hlast = ssd_chunked(xh * dt.astype(x.dtype)[..., None], a,
                               Bm.astype(x.dtype), Cm.astype(x.dtype),
                               cfg.ssm_chunk)
        y = y + xh * lp["D"].astype(x.dtype)[:, None]
        y = y.reshape(Bsz, S, di)
        y = L.rms_norm(lp["norm"], y * jax.nn.silu(z), cfg.norm_eps)
        return x + L.linear(lp["out_proj"], y), (conv_tail, hlast)

    def decode_step(self, params, cache, ids):
        cfg = self.cfg
        B = ids.shape[0]
        pos = cache["pos"]
        T = cache["k"].shape[2]
        x = L.embed(params["embed"], ids).astype(cfg.adt)
        positions = pos[None].astype(jnp.int32)
        kpos = cache["kpos"].at[pos].set(pos)
        mask = (kpos >= 0)[None, :]                     # [1,T]
        gp = self._group_params(params)
        sp = params["shared"]
        K, hd = cfg.num_kv_heads, cfg.hd

        def mamba_step(x, lp_cache):
            lp, conv_st, ssm_st = lp_cache
            return self._mamba_decode_layer(lp, x, conv_st, ssm_st)

        ks_new, vs_new, convs, ssms = [], [], [], []
        x_cur = x
        for gi in range(self.groups):
            h = L.rms_norm(sp["ln1"], x_cur, cfg.norm_eps)
            q = L.linear(sp["attn"]["wq"], h).reshape(B, 1, cfg.num_heads, hd)
            q = L.apply_rope(q, positions, cfg.rope_theta)
            kn = L.linear(sp["attn"]["wk"], h).reshape(B, 1, K, hd)
            vn = L.linear(sp["attn"]["wv"], h).reshape(B, 1, K, hd)
            kn = L.apply_rope(kn, positions, cfg.rope_theta)
            k_g = jax.lax.dynamic_update_slice_in_dim(cache["k"][gi], kn, pos,
                                                      axis=1)
            v_g = jax.lax.dynamic_update_slice_in_dim(cache["v"][gi], vn, pos,
                                                      axis=1)
            qg = q.reshape(B, 1, K, cfg.num_heads // K, hd)
            o = L._sdpa(qg, k_g, v_g, mask)
            x_cur = x_cur + L.linear(sp["attn"]["wo"],
                                     o.reshape(B, 1, cfg.num_heads * hd))
            x_cur = x_cur + L.mlp(sp["mlp"], cfg,
                                  L.rms_norm(sp["ln2"], x_cur, cfg.norm_eps))
            ks_new.append(k_g)
            vs_new.append(v_g)
            lo, hi = gi * cfg.attn_every, (gi + 1) * cfg.attn_every
            glp = jax.tree.map(lambda a: a[gi], gp)
            x_cur, (cs, ss) = jax.lax.scan(
                mamba_step, x_cur,
                (glp, cache["conv"][lo:hi], cache["ssm"][lo:hi]))
            convs.append(cs)
            ssms.append(ss)
        x_cur = L.rms_norm(params["ln_f"], x_cur, cfg.norm_eps)
        logits = (L.unembed(params["embed"], x_cur) if cfg.tie_embeddings else
                  L.linear(params["head"], x_cur).astype(jnp.float32))
        new_cache = {"k": jnp.stack(ks_new), "v": jnp.stack(vs_new),
                     "kpos": kpos, "pos": pos + 1,
                     "conv": jnp.concatenate(convs),
                     "ssm": jnp.concatenate(ssms)}
        return logits[:, 0], new_cache

    def _mamba_decode_layer(self, lp, x, conv_st, ssm_st):
        cfg = self.cfg
        m = self.mamba
        B = x.shape[0]
        di, n = m.d_inner, cfg.ssm_state
        hin = L.rms_norm(lp["ln"], x, cfg.norm_eps)
        z, xBC, dt = m._mix_in(lp, hin)
        hist = jnp.concatenate([conv_st, xBC], axis=1)
        w = lp["conv_w"].astype(x.dtype)
        conv_out = jnp.einsum("bwc,wc->bc", hist, w) + lp["conv_b"].astype(x.dtype)
        xBC1 = jax.nn.silu(conv_out)[:, None]
        xs, Bm, Cm = jnp.split(xBC1, [di, di + n], axis=-1)
        dtv = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + lp["dt_bias"])
        A = -jnp.exp(lp["A_log"])
        a = jnp.exp(dtv * A)
        xh = xs[:, 0].reshape(B, m.nheads, m.headdim)
        dx = xh * dtv.astype(x.dtype)[..., None]
        ssm_new = (a.astype(x.dtype)[..., None, None] * ssm_st
                   + jnp.einsum("bhp,bn->bhpn", dx, Bm[:, 0]))
        y = jnp.einsum("bhpn,bn->bhp", ssm_new, Cm[:, 0])
        y = y + xh * lp["D"].astype(x.dtype)[:, None]
        y = y.reshape(B, 1, di)
        y = L.rms_norm(lp["norm"], y * jax.nn.silu(z), cfg.norm_eps)
        return x + L.linear(lp["out_proj"], y), (hist[:, 1:], ssm_new)
