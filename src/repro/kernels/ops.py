"""Public jit'd kernel wrappers with automatic backend dispatch.

On TPU the Pallas kernels run natively; on CPU (this container) they execute
through ``interpret=True`` when explicitly requested, and the production
model code falls back to the pure-jnp refs (kernels/ref.py) otherwise.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

from repro.core.tracing import flight_recorder

from . import ref
from . import flash_attention as fused
from .nbody import nbody_forces_tpu
from .ssd_scan import ssd
from .stencil5 import wave_step_tpu


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def flash_attention(q, k, v, *, window=None, interpret=None):
    """Causal self-attention, q [B,S,K,G,hd], k/v [B,S,K,hd], at any S,
    differentiable: the fused Pallas op on the TPU (or in interpret mode),
    which pads S to whole tiles, the blockwise jnp ref elsewhere."""
    if on_tpu() or interpret:
        return fused.causal_attention(
            q, k, v, window=window, interpret=bool(interpret) and not on_tpu())
    return ref.flash_attention_ref(q, k, v, causal=True, window=window)


def nbody_forces(p_all, *, soft=1e-3, interpret=None):
    if on_tpu() or interpret:
        return nbody_forces_tpu(p_all, soft=soft,
                                interpret=bool(interpret) and not on_tpu())
    return ref.nbody_forces_ref(p_all, p_all, soft)


def wave_step(um, u, *, c=0.25, interpret=None):
    if on_tpu() or interpret:
        return wave_step_tpu(um, u, c=c,
                             interpret=bool(interpret) and not on_tpu())
    return ref.wave_step_ref(um, u, c)


def ssd_scan(x, a, B, C, *, chunk=64, interpret=None):
    """(y, final state) of the SSD, differentiable on either path.  Each
    trace records ``ssd.kernel`` in the flight recorder: 1 where the site
    is built with the Pallas op, 0 where it falls back to jnp."""
    kernel = on_tpu() or bool(interpret)
    flight_recorder().counter("ssd.kernel", int(kernel))
    if kernel:
        return ssd(x, a, B, C, chunk=chunk,
                   interpret=bool(interpret) and not on_tpu())
    from repro.models.mamba2 import ssd_chunked
    return ssd_chunked(x, a, B, C, chunk)


# rows, contraction and output columns of one grouped-matmul tile, the
# fastest of four tilings for granite's experts (d 1024, F 512, 32768
# rows) on one TPU v5e; a whole d in one tile, so every tiling that keeps
# it sums in the same order
GMM_TILE = (256, 1024, 512)


def _gmm_kernel(lhs, rhs, group_sizes, interpret):
    """Megablox's Pallas grouped matmul (``gmm``, its gradient ``gmm`` and
    ``tgmm``, the names the device trace shows).  Rows are padded to a
    whole tile; the padding belongs to no group and is cut off."""
    m, k = lhs.shape
    n = rhs.shape[2]
    tm = min(GMM_TILE[0], -(-m // 128) * 128)
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = megablox.gmm(lhs, rhs, group_sizes, lhs.dtype,
                       (tm, min(GMM_TILE[1], k), min(GMM_TILE[2], n)),
                       interpret=interpret)
    return out[:m] if pad else out


def grouped_swiglu(rows, wg, wi, wo, group_sizes, *, interpret=None):
    """SwiGLU experts over rows sorted by expert: the ``group_sizes[e]``
    rows of expert e in turn go through ``silu(x wg[e]) * (x wi[e]) wo[e]``.
    rows [m, d]; wg, wi [E, d, F]; wo [E, F, d]; group_sizes [E] int32
    summing to m.  Each product is one grouped matmul: the Pallas kernel on
    the TPU (or in interpret mode), ``jax.lax.ragged_dot`` elsewhere and as
    the tests' oracle.  Each trace records ``moe.kernel`` in the flight
    recorder: 1 where the site is built with the Pallas kernel, 0 with
    ``ragged_dot``."""
    kernel = on_tpu() or bool(interpret)
    flight_recorder().counter("moe.kernel", int(kernel))
    if kernel:
        interp = bool(interpret) and not on_tpu()

        def gmm(x, w):
            return _gmm_kernel(x, w, group_sizes, interp)
    else:
        def gmm(x, w):
            return jax.lax.ragged_dot(x, w, group_sizes)
    h = jax.nn.silu(gmm(rows, wg)) * gmm(rows, wi)
    return gmm(h, wo)
