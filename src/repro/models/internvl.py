"""InternVL2-style VLM backbone (arXiv:2404.16821).

The InternViT frontend is a STUB per the assignment: ``input_specs`` supplies
precomputed patch embeddings [B, vis_tokens, d_vis]; a 2-layer MLP projector
maps them into the LM's embedding space and they are prepended to the text
tokens.  The language backbone (InternLM2-20B geometry) is the standard
``DecoderLM``; labels cover only the text positions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import layers as L
from .config import ArchConfig
from .transformer import DecoderLM

D_VIS = 1024   # stub InternViT output width (projector input)


class InternVLModel:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.lm = DecoderLM(cfg)

    def init(self, key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "lm": self.lm.init(k1),
            "proj": {"w1": L.init_linear(k2, D_VIS, self.cfg.d_model, self.cfg.pdt),
                     "w2": L.init_linear(k3, self.cfg.d_model, self.cfg.d_model,
                                         self.cfg.pdt)},
        }

    def _embed_multimodal(self, params, vis, ids):
        cfg = self.cfg
        v = L.linear(params["proj"]["w2"],
                     jax.nn.gelu(L.linear(params["proj"]["w1"],
                                          vis.astype(cfg.adt))))
        t = L.embed(params["lm"]["embed"], ids).astype(cfg.adt)
        return jnp.concatenate([v, t], axis=1)

    def forward(self, params, batch):
        """batch: {vis: [B,Tv,D_VIS], tokens: [B,S]}; logits over text part."""
        cfg = self.cfg
        x = self._embed_multimodal(params, batch["vis"], batch["tokens"])
        S = x.shape[1]
        positions = jnp.arange(S)
        mask = L.causal_mask(S, S)
        logits, aux = self.lm.forward_embedded(params["lm"], x, positions, mask)
        Tv = batch["vis"].shape[1]
        return logits[:, Tv:], aux

    def loss(self, params, batch):
        logits, _ = self.forward(params, batch)
        return L.cross_entropy(logits[:, :-1], batch["labels"][:, 1:],
                               batch.get("mask", None))

    # -- decode: delegate to the LM with a multimodal prefill ---------------------
    def prefill(self, params, vis, ids, max_len: int):
        cfg = self.cfg
        x = self._embed_multimodal(params, vis, ids)
        B, S = x.shape[0], x.shape[1]
        positions = jnp.arange(S)
        mask = L.causal_mask(S, S)
        logits, _, kvs = self.lm.forward_embedded(params["lm"], x, positions,
                                                  mask, return_cache=True,
                                                  last_only=True)
        cache = self.lm.init_cache(B, max_len)
        W = cache["k"].shape[2]
        take = min(S, W)
        k_all, v_all = kvs
        cache["k"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k_all[:, :, S - take:], 0, axis=2)
        cache["v"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v_all[:, :, S - take:], 0, axis=2)
        cache["kpos"] = cache["kpos"].at[:take].set(jnp.arange(S - take, S))
        cache["pos"] = jnp.array(S, jnp.int32)
        return logits[:, -1], cache

    def init_cache(self, B, max_len):
        return self.lm.init_cache(B, max_len)

    def decode_step(self, params, cache, ids):
        return self.lm.decode_step(params["lm"], cache, ids)
