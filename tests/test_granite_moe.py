"""Granite-3.0-1B-A400M on the program's normal path, held to the
benchmark's plain reference (``bench/configs/granite-moe-1b-a400m-l8.py``)
at a small size on the CPU; the dropless MoE against a per-token loop;
the Pallas grouped matmul (interpret mode) against ``ragged_dot``; the
scalars left out of the trace when unset; and the two readers of the MoE
layer's device time."""

import dataclasses
import json
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.tracing import flight_recorder
from repro.kernels import ops
from repro.models import build_model
from repro.models import layers as L

BENCH = Path(__file__).resolve().parents[1] / "bench"
sys.path.insert(0, str(BENCH))

from devtrace import Reduction  # noqa: E402
from reference import exact_dot  # noqa: E402
from spec import load_cell, load_module  # noqa: E402

CONFIG = "granite-moe-1b-a400m-l8"
CELL = "granite-moe-1b-a400m-l8.train-s4096"
SMALL = dict(num_layers=2, d_model=128, num_heads=4, num_kv_heads=2,
             d_ff=64, num_experts=8, top_k=2, vocab_size=512)


def small_granite():
    """The benchmark's configuration at the small size: the program's
    ArchConfig (f32 activations) and the reference's dict."""
    config = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
    cfg = dataclasses.replace(get_config(config["registry"]), dtype="float32",
                              **SMALL)
    return cfg, dict(config, **SMALL)


@pytest.fixture(scope="module")
def reference():
    return load_module(BENCH / "configs" / f"{CONFIG}.py")


def test_program_matches_the_reference(reference):
    """Loss and every parameter gradient of ``DecoderLM`` equal the plain
    reference's, from the same key.  Both compute in f32 (the reference
    at HIGHEST); they differ in the order of sums only: routed against
    dense experts, fused against split attention.  So the loss agrees to
    1e-5 relative, and each gradient leaf to 1e-4 of its largest element
    (f32 round-off over sums of a few hundred terms)."""
    cfg, config = small_granite()
    model = build_model(cfg)
    key = jax.random.PRNGKey(3)
    params = jax.jit(model.init)(key)
    ref_params = jax.jit(lambda k: reference.init(k, config))(key)
    assert jax.tree.structure(params) == jax.tree.structure(ref_params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ref_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    ids = jax.random.randint(jax.random.PRNGKey(4), (2, 64), 0,
                             cfg.vocab_size)
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(
        params, {"tokens": ids, "labels": ids})
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p: reference.loss(p, ids, config, exact_dot)))(params)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(ref_grads)):
        r = np.asarray(r)
        np.testing.assert_allclose(np.asarray(g), r, rtol=0,
                                   atol=1e-4 * np.abs(r).max(),
                                   err_msg=jax.tree_util.keystr(path))


def per_token_moe(p, cfg, x):
    """The MoE one token at a time: its top-k experts by router logit,
    gates a softmax over their logits, the gated sum of their SwiGLUs."""
    p = jax.tree.map(np.asarray, p)
    out = np.zeros_like(x)
    for t, xt in enumerate(x):
        logits = xt @ p["router"]["w"]
        top = np.argsort(-logits)[:cfg.top_k]
        g = np.exp(logits[top] - logits[top].max())
        g /= g.sum()
        for gate, e in zip(g, top):
            a = xt @ p["wg"][e]
            h = a / (1 + np.exp(-a)) * (xt @ p["wi"][e])
            out[t] += gate * (h @ p["wo"][e])
    return out


def test_dropless_moe_equals_a_per_token_loop():
    cfg = dataclasses.replace(get_config("granite-moe-1b-a400m").reduced(),
                              num_experts=8, top_k=3)
    p = L.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.d_model))
    out = L.moe(p, cfg, x)
    want = per_token_moe(p, cfg, np.asarray(x).reshape(-1, cfg.d_model))
    np.testing.assert_allclose(np.asarray(out).reshape(want.shape), want,
                               rtol=1e-4, atol=1e-5)


def test_gates_sum_to_one_and_no_token_is_dropped():
    """Every token, even where all of them choose the same experts, gets
    its k gates, summing to 1: its output is the gated sum whatever the
    load on its experts."""
    cfg = dataclasses.replace(get_config("granite-moe-1b-a400m").reduced(),
                              num_experts=8, top_k=2)
    p = L.init_moe(jax.random.PRNGKey(0), cfg)
    bias = jnp.zeros((cfg.d_model, cfg.num_experts)).at[:, 5].set(1.0)
    p = dict(p, router={"w": p["router"]["w"] * 1e-3 + bias})
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (1, 40, cfg.d_model)))
    logits = x.reshape(-1, cfg.d_model) @ p["router"]["w"]
    top, idx = jax.lax.top_k(logits, cfg.top_k)
    assert bool(jnp.all(idx[:, 0] == 5))
    np.testing.assert_allclose(np.asarray(jax.nn.softmax(top).sum(-1)), 1.0,
                               rtol=1e-6)
    out = L.moe(p, cfg, x)
    want = per_token_moe(p, cfg, np.asarray(x)[0])
    np.testing.assert_allclose(np.asarray(out)[0], want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("group_sizes", [
    [0, 64, 0, 32, 96, 0, 64, 0],      # empty groups, whole tiles
    [3, 0, 50, 0, 0, 61, 86, 0],       # ragged groups, rows padded to a tile
])
def test_pallas_grouped_matmul_equals_ragged_dot(group_sizes):
    """The Pallas grouped matmul (interpret mode) against ``ragged_dot``,
    forward and gradient, in f32: both sum the same products in f32, so
    they agree to 1e-5 of the largest element.  An empty group's weight
    gradient is zero."""
    E, d, F = len(group_sizes), 128, 64
    m = sum(group_sizes)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    rows = jax.random.normal(ks[0], (m, d))
    wg, wi = (jax.random.normal(k, (E, d, F)) / d ** 0.5 for k in ks[1:3])
    wo = jax.random.normal(ks[3], (E, F, d)) / F ** 0.5
    gs = jnp.array(group_sizes, jnp.int32)

    def loss(interpret, rows, wg, wi, wo):
        y = ops.grouped_swiglu(rows, wg, wi, wo, gs, interpret=interpret)
        return jnp.sum(jnp.sin(y)), y

    grad = partial(jax.value_and_grad, has_aux=True, argnums=(0, 1, 2, 3))
    (_, want), gwant = grad(partial(loss, False))(rows, wg, wi, wo)
    assert flight_recorder().counters["moe.kernel"][-1][1] == 0
    (_, got), ggot = grad(partial(loss, True))(rows, wg, wi, wo)
    assert flight_recorder().counters["moe.kernel"][-1][1] == 1
    for a, b in zip((got,) + ggot, (want,) + gwant):
        b = np.asarray(b)
        np.testing.assert_allclose(np.asarray(a), b, rtol=0,
                                   atol=1e-5 * np.abs(b).max())
    empty = [e for e, n in enumerate(group_sizes) if n == 0]
    for g in ggot[1:]:
        assert not np.asarray(g)[empty].any()


def test_model_with_the_pallas_grouped_matmul(monkeypatch):
    """The whole model's loss and gradients are the same through the
    Pallas kernel (interpret mode) as through ``ragged_dot``."""
    cfg, _ = small_granite()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 64), 0, cfg.vocab_size)
    batch = {"tokens": ids, "labels": ids}
    loss, grads = jax.value_and_grad(model.loss)(params, batch)
    monkeypatch.setattr(ops, "grouped_swiglu",
                        partial(ops.grouped_swiglu, interpret=True))
    kloss, kgrads = jax.value_and_grad(model.loss)(params, batch)
    np.testing.assert_allclose(float(kloss), float(loss), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(kgrads), jax.tree.leaves(grads)):
        b = np.asarray(b)
        np.testing.assert_allclose(np.asarray(a), b, rtol=0,
                                   atol=1e-5 * np.abs(b).max())


def test_unset_scalars_leave_the_block_unscaled():
    """With Granite's scalars unset the model is the plain block: equal to
    the same model with each scalar at its neutral value (multipliers 1,
    the softmax scale 1/sqrt(hd)), and traced with fewer operations, as
    an unset scalar adds none."""
    cfg, _ = small_granite()
    unset = dataclasses.replace(cfg, embedding_multiplier=None,
                                attention_multiplier=None,
                                residual_multiplier=None, logits_scaling=None)
    neutral = dataclasses.replace(cfg, embedding_multiplier=1.0,
                                  attention_multiplier=cfg.hd ** -0.5,
                                  residual_multiplier=1.0, logits_scaling=1.0)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0, cfg.vocab_size)
    params = build_model(unset).init(jax.random.PRNGKey(0))
    outs, eqns = [], []
    for c in (unset, neutral):
        fwd = partial(build_model(c).forward, ids=ids)
        outs.append(fwd(params)[0])
        eqns.append(len(str(jax.make_jaxpr(fwd)(params)).splitlines()))
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(outs[1]),
                               rtol=1e-5, atol=1e-6)
    assert eqns[0] < eqns[1]


def _reduction(top_ops):
    return Reduction(window_s=1.0, busy_s=1.0, gaps=[], program_gaps_s={},
                     program_runs={}, top_ops=top_ops)


PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_moe_readers_on_a_synthetic_trace(monkeypatch):
    """10 steps with 0.5 s of ``gmm`` and 0.25 s of ``tgmm``: 75 ms a
    step.  The cell's expert work a step, by hand: 8 layers x 12 products
    x 2 x 32768 rows x 1024 x 512 = 3,298,534,883,328 operations, 16.744 ms
    at 197 TFLOP/s; 8 x 12 x 2 bytes x (32768 x 1536 + 32 x 1024 x 512) =
    12,884,901,888 bytes, 15.732 ms at 819 GB/s.  So compute bound, and
    16.744 / 75 = 22.33 % of the roofline."""
    gmm_ms = load_module(BENCH / "metrics" / "moe_gmm_ms.py").read
    roofline = load_module(BENCH / "metrics" / "moe_gmm_roofline.py")
    ctx = {"steps": 10, "peak": PEAK, "trace": _reduction([
        ("fusion.1 f32[4096,1024]", 1.0),
        ("gmm.3 bf16[32768,512]", 0.375), ("tgmm.1 bf16[32,1024,512]", 0.25),
        ("gmm bf16[32768,1024]", 0.125), ("rgmm.2 f32[8]", 7.0)])}
    assert gmm_ms(ctx) == pytest.approx(75.0)
    least_ms = 1e3 * 3_298_534_883_328 / 197e12
    assert roofline.share(ctx, load_cell(CELL)) == pytest.approx(
        100 * least_ms / 75.0)
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", CELL])
    assert roofline.read(ctx) == pytest.approx(22.3251, abs=1e-4)


def test_moe_readers_find_nothing_without_the_kernel(monkeypatch):
    ctx = {"steps": 10, "peak": PEAK, "trace": _reduction([
        ("fusion.1 f32[4096,1024]", 1.0), ("ragged-dot.2 bf16[32768,512]", 2.0)])}
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", CELL])
    assert load_module(BENCH / "metrics" / "moe_gmm_ms.py").read(ctx) is None
    assert load_module(BENCH / "metrics" / "moe_gmm_roofline.py").read(ctx) \
        is None
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload",
                                      "mamba2-370m.train-s2048"])
    ctx["trace"] = _reduction([("gmm.3 bf16[32768,512]", 1.0)])
    assert load_module(BENCH / "metrics" / "moe_gmm_roofline.py").read(ctx) \
        is None


def test_expert_work_counts_twelve_products_a_layer(reference):
    """Per layer and step: forward, remat recompute, input and weight
    gradients of the three expert products; the model's own count per
    token is 6 x active parameters plus attention, 1.46 GFLOP."""
    config = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
    rows = 4096 * 8
    assert reference.expert_flops_per_step(config, 1, 4096) == \
        8 * 12 * 2 * rows * 1024 * 512
    assert reference.model_flops_per_token(config, 4096) == pytest.approx(
        1_461_209_088)
