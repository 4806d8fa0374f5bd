"""Model correctness: SSD vs brute-force recurrence, cached decode vs full
forward, MoE routing invariants, per-family loss sanity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ArchConfig, build_model
from repro.models import layers as L
from repro.models.internvl import D_VIS
from repro.models.mamba2 import ssd_chunked

jax.config.update("jax_enable_x64", False)


# -- SSD algorithm vs O(S) recurrence oracle ---------------------------------
def ssd_recurrent_oracle(x, a, B, C):
    b, s, h, p = x.shape
    n = B.shape[-1]
    hstate = np.zeros((b, h, p, n), np.float64)
    ys = np.zeros((b, s, h, p), np.float64)
    xn, an, Bn, Cn = map(lambda t: np.asarray(t, np.float64), (x, a, B, C))
    for t in range(s):
        hstate = (np.exp(an[:, t])[:, :, None, None] * hstate
                  + np.einsum("bhp,bn->bhpn", xn[:, t], Bn[:, t]))
        ys[:, t] = np.einsum("bhpn,bn->bhp", hstate, Cn[:, t])
    return ys, hstate


@pytest.mark.parametrize("s,chunk", [(8, 4), (32, 8), (64, 64), (48, 16)])
def test_ssd_chunked_matches_recurrence(s, chunk):
    key = jax.random.PRNGKey(0)
    b, h, p, n = 2, 3, 4, 5
    k1, k2, k3, k4 = jax.random.split(key, 4)
    x = jax.random.normal(k1, (b, s, h, p))
    a = -jax.nn.softplus(jax.random.normal(k2, (b, s, h)))  # log-decay < 0
    B = jax.random.normal(k3, (b, s, n))
    C = jax.random.normal(k4, (b, s, n))
    y, hlast = ssd_chunked(x, a, B, C, chunk)
    ye, he = ssd_recurrent_oracle(x, a, B, C)
    np.testing.assert_allclose(np.asarray(y), ye, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(hlast), he, rtol=2e-4, atol=2e-4)


# -- configs for decode consistency ------------------------------------------
def tiny(family, **kw):
    base = dict(num_layers=30, d_model=256, num_heads=8, num_kv_heads=2,
                d_ff=512, vocab_size=512)
    cfg = ArchConfig(name=f"tiny-{family}", family=family, **base)
    from dataclasses import replace
    return replace(cfg.reduced(), **kw)


@pytest.mark.parametrize("family,kw", [
    ("dense", {}),
    ("dense", {"sliding_window": 8}),
    ("dense", {"qkv_bias": True}),
    ("moe", {"num_experts": 4, "top_k": 2}),
    ("ssm", {"num_heads": 0, "num_kv_heads": 0, "d_ff": 0,
             "ssm_state": 16, "tie_embeddings": True}),
    ("hybrid", {"ssm_state": 16, "attn_every": 2, "num_layers": 4}),
    # Granite's scalars (softmax scale 1/32 at head width 32, not 1/sqrt)
    ("moe", {"num_experts": 4, "top_k": 2, "embedding_multiplier": 12.0,
             "attention_multiplier": 1 / 32, "residual_multiplier": 0.22,
             "logits_scaling": 6.0}),
])
def test_decode_matches_forward(family, kw):
    """prefill + N decode steps must reproduce teacher-forced logits."""
    cfg = tiny(family, **kw)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    B, S, S0 = 1, 16, 8
    ids = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    full_logits, _ = m.forward(params, ids)

    logits, cache = m.prefill(params, ids[:, :S0], max_len=S)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full_logits[:, S0 - 1]),
                               rtol=2e-3, atol=2e-3)
    for t in range(S0, S):
        logits, cache = m.decode_step(params, cache, ids[:, t:t + 1])
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full_logits[:, t]),
            rtol=2e-3, atol=2e-3,
            err_msg=f"{family}{kw} decode step t={t}")


def test_sliding_window_decode_ring_buffer():
    """With window < prompt length the ring cache must still be exact."""
    cfg = tiny("dense", sliding_window=6)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    B, S, S0 = 1, 20, 10
    ids = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    full_logits, _ = m.forward(params, ids)
    logits, cache = m.prefill(params, ids[:, :S0], max_len=S)
    assert cache["k"].shape[2] == 6     # O(window) cache, not O(S)
    for t in range(S0, S):
        logits, cache = m.decode_step(params, cache, ids[:, t:t + 1])
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full_logits[:, t]),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"t={t}")


# -- MoE invariants -------------------------------------------------------------
def test_moe_routing_weights_normalized():
    """With every expert the same, each token's gated sum over its top-k
    experts is that one expert's output: the gates sum to 1."""
    cfg = tiny("moe", num_experts=8, top_k=2, d_model=64, d_ff=32)
    key = jax.random.PRNGKey(0)
    p = L.init_moe(key, cfg)
    p = dict(p, **{k: jnp.broadcast_to(p[k][:1], p[k].shape)
                   for k in ("wg", "wi", "wo")})
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64))
    out = L.moe(p, cfg, x)
    assert out.shape == x.shape
    assert not bool(jnp.isnan(out).any())
    xt = x.reshape(-1, 64)
    one = (jax.nn.silu(xt @ p["wg"][0]) * (xt @ p["wi"][0])) @ p["wo"][0]
    np.testing.assert_allclose(np.asarray(out).reshape(one.shape),
                               np.asarray(one), rtol=1e-4, atol=1e-5)


def test_moe_is_dropless_when_every_token_picks_one_expert():
    """A router that sends every token to the same first expert: each
    token still gets all of its top-k experts' outputs, as if routed
    alone (there is no capacity to overflow)."""
    cfg = tiny("moe", num_experts=4, top_k=2, d_model=64, d_ff=32)
    p = L.init_moe(jax.random.PRNGKey(0), cfg)
    w = jnp.zeros_like(p["router"]["w"]).at[:, 0].set(1.0).at[:, 1].set(0.5)
    p = dict(p, router={"w": w})
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (1, 32, 64)))
    out = L.moe(p, cfg, x)
    alone = jnp.concatenate([L.moe(p, cfg, x[:, t:t + 1])
                             for t in range(32)], axis=1)
    assert not bool(jnp.isnan(out).any())
    assert bool(jnp.all(jnp.abs(out).sum(-1) > 0))
    np.testing.assert_allclose(np.asarray(out), np.asarray(alone),
                               rtol=1e-5, atol=1e-6)


# -- attention variants -----------------------------------------------------------
def test_gqa_equals_mha_when_groups_1():
    """num_kv_heads == num_heads degenerates to standard MHA."""
    cfg = tiny("dense", num_heads=4, num_kv_heads=4)
    m = build_model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
    logits, _ = m.forward(p, ids)
    assert logits.shape == (2, 8, cfg.vocab_size)


def test_causality():
    """Perturbing a future token must not change past logits."""
    cfg = tiny("dense")
    m = build_model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 12), 0, cfg.vocab_size)
    l1, _ = m.forward(p, ids)
    ids2 = ids.at[0, 8].set((ids[0, 8] + 1) % cfg.vocab_size)
    l2, _ = m.forward(p, ids2)
    np.testing.assert_allclose(np.asarray(l1[0, :8]), np.asarray(l2[0, :8]),
                               rtol=1e-5, atol=1e-5)


def test_ssm_causality():
    cfg = tiny("ssm", num_heads=0, num_kv_heads=0, d_ff=0, ssm_state=16,
               tie_embeddings=True)
    m = build_model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, cfg.vocab_size)
    l1, _ = m.forward(p, ids)
    ids2 = ids.at[0, 12].set((ids[0, 12] + 1) % cfg.vocab_size)
    l2, _ = m.forward(p, ids2)
    np.testing.assert_allclose(np.asarray(l1[0, :12]), np.asarray(l2[0, :12]),
                               rtol=1e-4, atol=1e-4)


# -- grad flow -------------------------------------------------------------------
@pytest.mark.parametrize("family,kw", [
    ("dense", {}), ("moe", {"num_experts": 4, "top_k": 2}),
    ("ssm", {"num_heads": 0, "num_kv_heads": 0, "d_ff": 0, "ssm_state": 16,
             "tie_embeddings": True}),
    ("hybrid", {"ssm_state": 16, "attn_every": 2, "num_layers": 4}),
])
def test_grads_finite(family, kw):
    cfg = tiny(family, **kw)
    m = build_model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    g = jax.grad(lambda p: m.loss(p, {"tokens": ids, "labels": ids}))(p)
    for leaf in jax.tree.leaves(g):
        assert bool(jnp.isfinite(leaf).all())


def test_mamba2_pallas_ssd_matches_jnp(monkeypatch):
    """Mamba2LM's loss and parameter gradients with its SSD through the
    Pallas op (interpret mode) equal the jnp scan's, in f32, on a sequence
    that is not a chunk multiple; ``ssd.kernel`` in the flight recorder
    names the path each trace took."""
    from dataclasses import replace
    from functools import partial

    from repro.configs import get_config
    from repro.core.tracing import flight_recorder
    from repro.kernels import ops

    cfg = replace(get_config("mamba2-370m").reduced(), dtype="float32")
    m = build_model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, cfg.vocab_size)
    batch = {"tokens": ids, "labels": ids}

    def loss_and_grads():
        out = jax.jit(jax.value_and_grad(m.loss))(p, batch)
        return out, flight_recorder().counters["ssd.kernel"][-1][1]

    (l_jnp, g_jnp), engaged = loss_and_grads()
    assert engaged == 0
    monkeypatch.setattr(ops, "ssd_scan", partial(ops.ssd_scan, interpret=True))
    (l_pallas, g_pallas), engaged = loss_and_grads()
    assert engaged == 1
    np.testing.assert_allclose(float(l_pallas), float(l_jnp), rtol=1e-5)
    for got, want in zip(jax.tree.leaves(g_pallas), jax.tree.leaves(g_jnp)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4,
                                   atol=2e-4 * float(jnp.max(jnp.abs(want))))


def test_zamba2_keeps_the_jnp_ssd(monkeypatch):
    """Zamba2's mamba blocks run the jnp scan on every backend: they never
    reach ``kernels.ops.ssd_scan``, which picks the Pallas op on the TPU."""
    from repro.kernels import ops
    from repro.models.zamba2 import Zamba2LM

    def no_kernel(*args, **kwargs):
        raise AssertionError("zamba2 reached kernels.ops.ssd_scan")

    monkeypatch.setattr(ops, "ssd_scan", no_kernel)
    cfg = tiny("hybrid", ssm_state=16, attn_every=2, num_layers=4)
    m = build_model(cfg)
    assert isinstance(m, Zamba2LM)
    p = m.init(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    assert bool(jnp.isfinite(m.loss(p, {"tokens": ids, "labels": ids})))


def test_whisper_loss_and_shapes():
    cfg = ArchConfig("w", "audio", 4, 384, 6, 6, 1536, 51865, rope_theta=0.0,
                     tie_embeddings=True, enc_layers=4).reduced()
    m = build_model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    B, S = 2, 16
    batch = {"frames": jax.random.normal(jax.random.PRNGKey(1),
                                         (B, cfg.enc_frames, cfg.d_model)),
             "tokens": jnp.zeros((B, S), jnp.int32),
             "labels": jnp.zeros((B, S), jnp.int32)}
    logits, _ = m.forward(p, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(m.loss(p, batch)))


def test_internvl_loss_and_shapes():
    cfg = ArchConfig("v", "vlm", 48, 6144, 48, 8, 16384, 92553).reduced()
    m = build_model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    B, S = 2, 16
    batch = {"vis": jax.random.normal(jax.random.PRNGKey(1),
                                      (B, cfg.vis_tokens, D_VIS)),
             "tokens": jnp.zeros((B, S), jnp.int32),
             "labels": jnp.zeros((B, S), jnp.int32)}
    logits, _ = m.forward(p, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(m.loss(p, batch)))


def _granite_shaped():
    from repro.configs import get_config
    return get_config("granite-moe-1b-a400m").reduced(head_dim=64,
                                                      dtype="bfloat16")


def _trace_train(seq):
    m = build_model(_granite_shaped())
    p = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((1, seq), jnp.int32)
    jax.make_jaxpr(jax.grad(m.loss))(p, {"tokens": ids, "labels": ids})


def _trace_decode(seq):
    m = build_model(_granite_shaped())
    p = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: m.init_cache(1, seq))
    jax.make_jaxpr(m.decode_step)(p, cache,
                                  jax.ShapeDtypeStruct((1, 1), jnp.int32))


def _trace_noncausal(seq):
    cfg = _granite_shaped()
    p = jax.eval_shape(lambda: L.init_attention(jax.random.PRNGKey(0), cfg))
    x = jax.ShapeDtypeStruct((1, seq, cfg.d_model), jnp.bfloat16)
    jax.make_jaxpr(lambda p, x: L.attention(
        p, cfg, x, jnp.arange(seq), L.causal_mask(seq, seq))[0])(p, x)


def _trace_mamba2(seq):
    from repro.configs import get_config
    m = build_model(get_config("mamba2-370m").reduced())
    p = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((1, seq), jnp.int32)
    jax.make_jaxpr(jax.grad(m.loss))(p, {"tokens": ids, "labels": ids})


@pytest.mark.parametrize("trace,want", [
    (_trace_train, [1]), (_trace_decode, [0]),
    (_trace_noncausal, [0]), (_trace_mamba2, []),
])
def test_attention_kernel_selection(monkeypatch, trace, want):
    """On a TPU (faked; the programs are traced, not run), at a length that
    is no whole tile: ``DecoderLM``'s training forward at Granite-shaped
    widths (GQA, hd 64, bf16) records ``attention.kernel`` 1 (its layers are
    one scanned body, traced once), cached decode (S = 1) and a non-causal
    call 0, and ``Mamba2LM``, which has no attention, nothing."""
    from repro.core.tracing import flight_recorder
    from repro.kernels import ops

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setitem(flight_recorder().counters, "attention.kernel", [])
    trace(200)
    counters = flight_recorder().counters
    assert [v for _, v in counters.get("attention.kernel", [])] == want
