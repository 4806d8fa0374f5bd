"""Decoder-only LM (dense and MoE) with scan-over-layers, GQA/RoPE/SWA,
KV-cached decode (ring buffer for sliding-window), and remat policies.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import layers as L
from .config import ArchConfig


def stack_layer_params(per_layer: list) -> dict:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)


class DecoderLM:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg

    # -- params ---------------------------------------------------------------
    def init_layer(self, key) -> dict:
        cfg = self.cfg
        ka, km, k1, k2 = jax.random.split(key, 4)
        p = {"ln1": L.init_norm(cfg.d_model, cfg.pdt),
             "ln2": L.init_norm(cfg.d_model, cfg.pdt),
             "attn": L.init_attention(ka, cfg)}
        if cfg.family == "moe":
            p["moe"] = L.init_moe(km, cfg)
        else:
            p["mlp"] = L.init_mlp(km, cfg)
        return p

    def init(self, key) -> dict:
        cfg = self.cfg
        ke, kh, kf, *kl = jax.random.split(key, 3 + cfg.num_layers)
        params = {
            "embed": L.init_embedding(ke, cfg.vocab_size, cfg.d_model, cfg.pdt),
            "ln_f": L.init_norm(cfg.d_model, cfg.pdt),
            "layers": stack_layer_params([self.init_layer(k) for k in kl]),
        }
        if not cfg.tie_embeddings:
            params["head"] = L.init_linear(kh, cfg.d_model, cfg.vocab_size,
                                           cfg.pdt)
        return params

    # -- blocks -----------------------------------------------------------------
    def _residual(self, x, y):
        m = self.cfg.residual_multiplier
        return x + (y if m is None else y * m)

    def _block(self, p, x, positions, mask, kv=None, *, causal=False):
        cfg = self.cfg
        with jax.named_scope("norm"):
            h = L.rms_norm(p["ln1"], x, cfg.norm_eps)
        with jax.named_scope("attention"):
            a, new_kv = L.attention(p["attn"], cfg, h, positions, mask, kv=kv,
                                    causal=causal)
        x = self._residual(x, a)
        with jax.named_scope("norm"):
            h = L.rms_norm(p["ln2"], x, cfg.norm_eps)
        if cfg.family == "moe":
            y = L.moe(p["moe"], cfg, h)
        else:
            y = L.mlp(p["mlp"], cfg, h)
        return self._residual(x, y), new_kv

    def _embed(self, params, ids):
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = L.embed(params["embed"], ids)
            if cfg.embedding_multiplier is not None:
                x = x * cfg.embedding_multiplier
            return x.astype(cfg.adt)

    def _logits(self, params, x):
        cfg = self.cfg
        with jax.named_scope("norm"):
            x = L.rms_norm(params["ln_f"], x, cfg.norm_eps)
        with jax.named_scope("unembed"):
            if cfg.tie_embeddings:
                logits = L.unembed(params["embed"], x)
            else:
                logits = L.linear(params["head"], x).astype(jnp.float32)
            if cfg.logits_scaling is not None:
                logits = logits / cfg.logits_scaling
            return logits

    # -- full forward (train / prefill) -------------------------------------------
    def forward(self, params, ids, *, return_cache: bool = False,
                last_only: bool = False):
        cfg = self.cfg
        B, S = ids.shape
        x = self._embed(params, ids)
        positions = jnp.arange(S)
        mask = L.causal_mask(S, S, window=cfg.sliding_window)
        return self.forward_embedded(params, x, positions, mask,
                                     return_cache=return_cache,
                                     last_only=last_only)

    def forward_embedded(self, params, x, positions, mask, *,
                         return_cache: bool = False, last_only: bool = False):
        """``last_only`` computes logits for the final position only —
        prefill never needs the full [B,S,V] logits tensor (or the head
        matmul + vocab-axis collective behind it)."""
        cfg = self.cfg

        def body(x, lp):
            x, kv = self._block(lp, x, positions, mask, causal=True)
            return x, (kv if return_cache else 0)

        body_fn = jax.checkpoint(body) if cfg.remat else body
        if cfg.scan_layers:
            x, kvs = jax.lax.scan(body_fn, x, params["layers"])
        else:
            kvs_list = []
            for i in range(cfg.num_layers):
                lp = jax.tree.map(lambda v: v[i], params["layers"])
                x, kv = body_fn(x, lp)
                kvs_list.append(kv)
            kvs = kvs_list if return_cache else None
        logits = self._logits(params, x[:, -1:] if last_only else x)
        if return_cache:
            return logits, 0.0, kvs
        return logits, 0.0

    def loss(self, params, batch):
        logits, _ = self.forward(params, batch["tokens"])
        with jax.named_scope("cross_entropy"):
            return L.cross_entropy(logits[:, :-1], batch["labels"][:, 1:],
                                   batch.get("mask", None))

    # -- cached decode --------------------------------------------------------------
    def cache_len(self, max_len: int) -> int:
        w = self.cfg.sliding_window
        return min(w, max_len) if w else max_len

    def init_cache(self, B: int, max_len: int) -> dict:
        cfg = self.cfg
        W = self.cache_len(max_len)
        K, hd, Lr = cfg.num_kv_heads, cfg.hd, cfg.num_layers
        return {
            "k": jnp.zeros((Lr, B, W, K, hd), cfg.adt),
            "v": jnp.zeros((Lr, B, W, K, hd), cfg.adt),
            "kpos": jnp.full((W,), -1, jnp.int32),     # global pos per slot
            "pos": jnp.zeros((), jnp.int32),
        }

    def prefill(self, params, ids, max_len: int):
        """Run the full prompt, return (last-token logits, primed cache)."""
        cfg = self.cfg
        B, S = ids.shape
        logits, _, kvs = self.forward(params, ids, return_cache=True,
                                      last_only=True)
        cache = self.init_cache(B, max_len)
        W = cache["k"].shape[2]
        take = min(S, W)
        # kvs: (k, v) stacked over layers: [L,B,S,K,hd].  Position p lives in
        # ring slot p % W — the same invariant decode_step maintains.
        k_all, v_all = kvs
        keep_pos = jnp.arange(S - take, S)
        slots = keep_pos % W
        cache["k"] = cache["k"].at[:, :, slots].set(k_all[:, :, S - take:])
        cache["v"] = cache["v"].at[:, :, slots].set(v_all[:, :, S - take:])
        cache["kpos"] = cache["kpos"].at[slots].set(keep_pos)
        cache["pos"] = jnp.array(S, jnp.int32)
        return logits[:, -1], cache

    def decode_step(self, params, cache, ids):
        """ids: [B,1] next token; returns (logits [B,V], new cache)."""
        cfg = self.cfg
        B = ids.shape[0]
        pos = cache["pos"]
        W = cache["k"].shape[2]
        slot = pos % W
        x = self._embed(params, ids)
        positions = pos[None].astype(jnp.int32)

        kpos = cache["kpos"].at[slot].set(pos)
        # mask: valid slots, causal, within window
        valid = kpos >= 0
        if cfg.sliding_window:
            valid &= kpos > pos - cfg.sliding_window
        mask = valid[None, :]                          # [S=1, T=W]

        def body(carry, lp_kc):
            x, _ = carry
            lp, k_l, v_l = lp_kc
            h = L.rms_norm(lp["ln1"], x, cfg.norm_eps)
            K, hd = cfg.num_kv_heads, cfg.hd
            q = L.linear(lp["attn"]["wq"], h).reshape(B, 1, cfg.num_heads, hd)
            q = L.apply_rope(q, positions, cfg.rope_theta) if cfg.rope_theta else q
            kn = L.linear(lp["attn"]["wk"], h).reshape(B, 1, K, hd)
            vn = L.linear(lp["attn"]["wv"], h).reshape(B, 1, K, hd)
            kn = L.apply_rope(kn, positions, cfg.rope_theta) if cfg.rope_theta else kn
            k_l = jax.lax.dynamic_update_slice_in_dim(k_l, kn, slot, axis=1)
            v_l = jax.lax.dynamic_update_slice_in_dim(v_l, vn, slot, axis=1)
            G = cfg.num_heads // K
            qg = L.scale_queries(cfg, q).reshape(B, 1, K, G, hd)
            o = L._sdpa(qg, k_l, v_l, mask)
            x = self._residual(x, L.linear(lp["attn"]["wo"],
                                           o.reshape(B, 1, cfg.num_heads * hd)))
            h2 = L.rms_norm(lp["ln2"], x, cfg.norm_eps)
            if cfg.family == "moe":
                y = L.moe(lp["moe"], cfg, h2)
            else:
                y = L.mlp(lp["mlp"], cfg, h2)
            return (self._residual(x, y), 0.0), (k_l, v_l)

        (x, _), (k_new, v_new) = jax.lax.scan(
            body, (x, 0.0), (params["layers"], cache["k"], cache["v"]))
        logits = self._logits(params, x)[:, 0]
        new_cache = {"k": k_new, "v": v_new, "kpos": kpos, "pos": pos + 1}
        return logits, new_cache
