"""Ahead-of-time compiles for one TPU v5e chip, with no chip attached.

The TPU compiler is installed with JAX and compiles for a described
topology.  These tests catch what interpret mode cannot: Mosaic refusing a
block layout or an in-kernel op, a kernel asking for more VMEM than it may
use, and a train step that does not fit the chip's 16 GiB of HBM.  The
kernels compile at the sizes ``chip_smoke.py`` runs them at.

The topology is described inside a module fixture, never at import: the
TPU library may be loaded by one process at a time, and only the worker
that runs this file loads it.  The persistent compilation cache is off
around these compiles, since what they write cannot be read back without a
chip.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import causal_attention
from repro.kernels.nbody import nbody_forces_tpu
from repro.kernels import ops
from repro.kernels.ssd_scan import ssd
from repro.kernels.stencil5 import wave_step_tpu
from repro.launch.steps import make_train_step
from repro.models import build_model
from repro.optim import adamw_init

HBM_BYTES = 16 * 2**30          # one v5e chip
SSD_CHUNK = get_config("mamba2-370m").ssm_chunk


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(fn, *args):
    exe = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in exe.as_text()
    return exe


def test_nbody_compiles_at_65536_bodies(one_chip):
    _compile_kernel(nbody_forces_tpu, _spec(one_chip, (65536, 3)))


def test_wave_step_compiles_at_8192_square(one_chip):
    f = _spec(one_chip, (8192, 8192))
    _compile_kernel(wave_step_tpu, f, f)


def _ssd_specs(sharding, dtype):
    b, s, h, p, n = 2, 2048, 32, 64, 128
    return (_spec(sharding, (b, s, h, p), dtype), _spec(sharding, (b, s, h)),
            _spec(sharding, (b, s, n), dtype), _spec(sharding, (b, s, n), dtype))


def test_ssd_scan_compiles_at_mamba2_370m_widths(one_chip):
    _compile_kernel(lambda x, a, B, C: ssd(x, a, B, C, chunk=SSD_CHUNK),
                    *_ssd_specs(one_chip, jnp.float32))


def test_ssd_backward_compiles_at_mamba2_370m_widths(one_chip):
    def loss(x, a, B, C):
        y, hlast = ssd(x, a, B, C, chunk=SSD_CHUNK)
        return jnp.sum(y.astype(jnp.float32)) + jnp.sum(hlast)

    exe = _compile_kernel(jax.grad(loss, argnums=(0, 1, 2, 3)),
                          *_ssd_specs(one_chip, jnp.bfloat16))
    assert "ssd_bwd" in exe.as_text()


def test_causal_attention_compiles_at_qwen2_1_5b_widths(one_chip):
    """The fused attention, forward and gradient, at qwen2-1.5b's widths
    (12 query heads in 2 KV groups, hd 128)."""
    b, s, k, g, hd = 2, 2048, 2, 6, 128

    def loss(q, kk, v):
        return jnp.sum(causal_attention(q, kk, v).astype(jnp.float32))

    bf = jnp.bfloat16
    exe = _compile_kernel(jax.grad(loss, argnums=(0, 1, 2)),
                          _spec(one_chip, (b, s, k, g, hd), bf),
                          _spec(one_chip, (b, s, k, hd), bf),
                          _spec(one_chip, (b, s, k, hd), bf))
    for kernel in ("splash_mha_fwd", "splash_mha_dkv", "splash_mha_dq"):
        assert kernel in exe.as_text()


def test_grouped_swiglu_compiles_at_granite_widths(one_chip, monkeypatch):
    """One granite-moe-1b-a400m layer's expert products at the benchmark
    cell's shape (4096 tokens x top-8 rows, d 1024, F 512, 32 experts),
    forward and gradient, through the Pallas grouped matmul."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    m, d, F, E = 4096 * 8, 1024, 512, 32

    def loss(rows, wg, wi, wo, gs):
        y = ops.grouped_swiglu(rows, wg, wi, wo, gs)
        return jnp.sum(y.astype(jnp.float32))

    bf = jnp.bfloat16
    exe = _compile_kernel(jax.grad(loss, argnums=(0, 1, 2, 3)),
                          _spec(one_chip, (m, d), bf),
                          _spec(one_chip, (E, d, F), bf),
                          _spec(one_chip, (E, d, F), bf),
                          _spec(one_chip, (E, F, d), bf),
                          _spec(one_chip, (E,), jnp.int32))
    assert "tgmm" in exe.as_text()


def _train_step_on_chip(sharding, cfg, batch, seq):
    """The train step of ``cfg`` compiled for one chip, with its arguments'
    and temporaries' bytes."""
    model = build_model(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda s: _spec(sharding, s.shape, s.dtype), tree)

    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(adamw_init, params)
    toks = _spec(sharding, (batch, seq), jnp.int32)
    step = jax.jit(make_train_step(model), donate_argnums=(0, 1))
    exe = step.lower(on_chip(params), on_chip(opt),
                     {"tokens": toks, "labels": toks}).compile()
    ma = exe.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    return exe, used


def test_mamba2_370m_train_step_fits_one_chip(one_chip, monkeypatch):
    """Full widths, depth cut to 2 layers to keep the compile short; the
    layers are a scan, so depth scales the parameter bytes and not the
    program.  The SSD takes the chip's path, the Pallas op."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("mamba2-370m"), num_layers=2)
    exe, used = _train_step_on_chip(one_chip, cfg, 4, 1024)
    assert "ssd_bwd" in exe.as_text()
    assert 0 < used < HBM_BYTES


def test_granite_moe_train_step_attention_is_fused(one_chip, monkeypatch):
    """granite-moe-1b-a400m at full widths, depth cut to 2 layers, batch
    1 x 4096: the train step compiles with the fused attention's forward,
    dk/dv and dq kernels, fits the chip, and holds no [..., 4096, 4096]
    buffer of scores or probabilities."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("granite-moe-1b-a400m"), num_layers=2)
    exe, used = _train_step_on_chip(one_chip, cfg, 1, 4096)
    hlo = exe.as_text()
    for kernel in ("splash_mha_fwd", "splash_mha_dkv", "splash_mha_dq"):
        assert kernel in hlo
    assert not re.findall(r"(?:bf16|f32)\[[\d,]*4096,4096[\d,]*\]", hlo)
    assert 0 < used < HBM_BYTES
