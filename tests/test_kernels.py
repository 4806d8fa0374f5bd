"""Pallas kernel validation: shape/dtype sweeps in interpret=True against the
pure-jnp oracles in kernels/ref.py."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.tracing import flight_recorder
from repro.kernels import flash_attention as fused
from repro.kernels import ops, ref
from repro.kernels.nbody import nbody_forces_tpu
from repro.kernels.ssd_scan import ssd
from repro.kernels.stencil5 import wave_step_tpu
from repro.models.layers import _sdpa, causal_mask
from repro.models.mamba2 import ssd_chunked


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
           dict(atol=2e-5, rtol=2e-5)


# -- fused causal attention --------------------------------------------------
def _attention_inputs(S, K, G, hd, dtype, seed=0):
    """q with Granite's query scale (0.125 at hd 64, a power of two), k, v,
    and the weights of a loss over the output."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (2, S, K, G, hd)) * 0.125 * 4
    k = jax.random.normal(ks[1], (2, S, K, hd))
    v = jax.random.normal(ks[2], (2, S, K, hd))
    w = jax.random.normal(ks[3], (2, S, K, G, hd))
    return [x.astype(dtype) for x in (q, k, v)] + [w]


def _unfused(S, window=None):
    """``_sdpa``'s einsum-softmax-einsum path under the causal mask."""
    mask = causal_mask(S, S, window=window)
    return lambda q, k, v: _sdpa(q, k, v, mask)


def _out_and_grads(fn, q, k, v, w):
    def loss(q, k, v):
        out = fn(q, k, v)
        return jnp.sum(out.astype(jnp.float32) * w), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return (out,) + grads


ATTENTION_CASES = [             # (S, K, G, window): 4 over 2 and 16 over 8
    (256, 2, 2, None), (512, 2, 2, None), (256, 8, 2, None),
    (512, 8, 2, None), (512, 2, 2, 200), (256, 8, 2, 100),
    (200, 2, 2, None),          # no whole number of tiles: padded
]


@pytest.mark.parametrize("S,K,G,window", ATTENTION_CASES)
def test_fused_attention_matches_unfused(monkeypatch, S, K, G, window):
    """Causal self-attention through ``_sdpa`` where the TPU is faked and
    the fused op runs in interpret mode, against the unfused path, in bf16
    at hd 64: the output and the gradients of q, k and v miss the f32
    unfused path's by no more than the bf16 unfused path's do (norms over
    each tensor), also where S is padded to whole tiles.  The site records
    ``attention.kernel`` 1."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(ops, "flash_attention", functools.partial(
        fused.causal_attention, interpret=True))
    q, k, v, w = _attention_inputs(S, K, G, 64, jnp.bfloat16)
    mask = causal_mask(S, S, window=window)

    def sdpa(q, k, v):
        return _sdpa(q, k, v, mask, causal=True, window=window)

    got = _out_and_grads(sdpa, q, k, v, w)
    assert flight_recorder().counters["attention.kernel"][-1][1] == 1
    bf16 = _out_and_grads(_unfused(S, window), q, k, v, w)
    f32 = _out_and_grads(_unfused(S, window),
                         *(x.astype(jnp.float32) for x in (q, k, v)), w)
    for name, g, b, f in zip(("out", "dq", "dk", "dv"), got, bf16, f32):
        assert _rel_norm_err(g, f) <= _rel_norm_err(b, f), name


@pytest.mark.parametrize("S,K,G,hd", [
    (64, 2, 3, 32), (128, 1, 4, 64), (48, 2, 1, 16), (256, 4, 2, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 32])
def test_flash_attention_any_length(S, K, G, hd, dtype, window):
    """The fused op of ``ops.flash_attention`` at any S (padded to whole
    tiles): its output against the blockwise f32 reference."""
    q, k, v, _ = _attention_inputs(S, K, G, hd, dtype)
    out = ops.flash_attention(q, k, v, window=window, interpret=True)
    exp = ref.flash_attention_ref(*(x.astype(jnp.float32) for x in (q, k, v)),
                                  causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(exp),
                               **tol(dtype))


def test_flash_attention_grads_any_length():
    """The fused op's gradients at a length that is no whole tile equal
    the blockwise reference's, in f32."""
    q, k, v, w = _attention_inputs(200, 2, 2, 64, jnp.float32)
    got = _out_and_grads(
        functools.partial(ops.flash_attention, interpret=True), q, k, v, w)
    want = _out_and_grads(ref.flash_attention_ref, q, k, v, w)
    for g, e in zip(got, want):
        assert _rel_err(g, e) < 2e-5


# -- nbody ----------------------------------------------------------------------
@pytest.mark.parametrize("N,tile", [(64, 32), (100, 32), (256, 128), (33, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_nbody(N, tile, dtype):
    p = jax.random.normal(jax.random.PRNGKey(0), (N, 3), dtype)
    out = nbody_forces_tpu(p, tile_i=tile, tile_j=tile, interpret=True)
    exp = ref.nbody_forces_ref(p, p)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)


# -- stencil ----------------------------------------------------------------------
@pytest.mark.parametrize("H,W,tile", [(64, 32, 16), (100, 24, 32), (32, 16, 32)])
def test_wave_step(H, W, tile):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    um = jax.random.normal(k1, (H, W))
    u = jax.random.normal(k2, (H, W))
    out = wave_step_tpu(um, u, tile=tile, interpret=True)
    exp = ref.wave_step_ref(um, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-5)


# -- ssd ---------------------------------------------------------------------------
@pytest.mark.parametrize("s,chunk,h,p,n", [
    (64, 16, 2, 8, 4), (128, 64, 4, 64, 16), (96, 32, 1, 16, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_ssd_scan(s, chunk, h, p, n, dtype):
    b = 2
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (b, s, h, p), dtype)
    a = -jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    B = jax.random.normal(ks[2], (b, s, n), dtype)
    C = jax.random.normal(ks[3], (b, s, n), dtype)
    y, st = ssd(x, a, B, C, chunk=chunk, interpret=True)
    ye, ste = jax.jit(ssd_chunked, static_argnums=4)(x, a, B, C, chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(ste),
                               rtol=2e-4, atol=2e-4)


SSD_GRAD_SHAPES = [                 # (s, chunk, h, p, n)
    (64, 16, 2, 8, 4),              # heads narrower than a lane tile
    (40, 16, 1, 16, 8),             # padding to a chunk multiple, h = 1
    (128, 32, 4, 64, 16),           # two heads per 128-lane block, 2 blocks
]


@functools.lru_cache(maxsize=None)
def _ssd_grads(fn, dtype, s, chunk, h, p, n):
    """Gradients of a weighted sum of (y, final state) w.r.t. x, a, B, C,
    with x, B, C given to ``fn`` in ``dtype``.  x, B, C and the weights lie
    on the bf16 grid, so a bf16 run's miss is its products' rounding and
    its outputs', not its inputs'."""
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    b = 2

    def normal(k, shape):
        return jax.random.normal(k, shape).astype(jnp.bfloat16).astype(
            jnp.float32)

    x = normal(ks[0], (b, s, h, p))
    a = -jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    B = normal(ks[2], (b, s, n))
    C = normal(ks[3], (b, s, n))
    wy = normal(ks[4], (b, s, h, p))
    wh = normal(ks[5], (b, h, p, n))

    def loss(x, a, B, C):
        y, hlast = fn(x.astype(dtype), a, B.astype(dtype), C.astype(dtype),
                      chunk)
        return jnp.sum(y.astype(jnp.float32) * wy) + jnp.sum(hlast * wh)

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))(x, a, B, C)


def _pallas_ssd(x, a, B, C, chunk):
    return ssd(x, a, B, C, chunk=chunk, interpret=True)


def _rel_err(got, want):
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))
                 / jnp.max(jnp.abs(want)))


def _rel_norm_err(got, want):
    return float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                 / jnp.linalg.norm(want))


@pytest.mark.parametrize("s,chunk,h,p,n", SSD_GRAD_SHAPES)
def test_ssd_grads(s, chunk, h, p, n):
    """dx, da, dB, dC of the Pallas op against jax.grad through the jnp
    scan, in f32."""
    got = _ssd_grads(_pallas_ssd, jnp.float32, s, chunk, h, p, n)
    want = _ssd_grads(ssd_chunked, jnp.float32, s, chunk, h, p, n)
    for name, g, w in zip("x a B C".split(), got, want):
        assert _rel_err(g, w) < 2e-4, name


@pytest.mark.parametrize("s,chunk,h,p,n", SSD_GRAD_SHAPES)
def test_ssd_grads_bf16(s, chunk, h, p, n):
    """At bf16 inputs each of the op's gradients misses the f32 one by no
    more than the jnp scan's own bf16 gradient of the same leaf misses it.
    The misses are norms over the leaf: the largest single element's miss
    is about one bf16 step of the output, whatever the products did.  (The
    op reads 0.92 of the jnp scan's miss at most here; one bf16 pass for
    the kernel's f32 operands read 1.08 to 2.4.)"""
    want = _ssd_grads(ssd_chunked, jnp.float32, s, chunk, h, p, n)
    jnp_bf16 = _ssd_grads(ssd_chunked, jnp.bfloat16, s, chunk, h, p, n)
    got = _ssd_grads(_pallas_ssd, jnp.bfloat16, s, chunk, h, p, n)
    for name, g, j, w in zip("x a B C".split(), got, jnp_bf16, want):
        assert _rel_norm_err(g, w) <= _rel_norm_err(j, w), name


def test_ssd_chunk_ref_single():
    """kernels/ref.ssd_chunk_ref matches the models-level chunked scan."""
    q, h, p, n = 32, 2, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    x = jax.random.normal(ks[0], (1, q, h, p))
    a = -jax.nn.softplus(jax.random.normal(ks[1], (1, q, h)))
    B = jax.random.normal(ks[2], (1, q, n))
    C = jax.random.normal(ks[3], (1, q, n))
    y_ref, st_ref = ref.ssd_chunk_ref(x[0], a[0], B[0], C[0])
    y_full, st_full = ssd_chunked(x, a, B, C, q)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_full[0]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st_ref), np.asarray(st_full[0]),
                               rtol=1e-4, atol=1e-4)
