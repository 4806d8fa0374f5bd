"""Public jit'd kernel wrappers with automatic backend dispatch.

On TPU the Pallas kernels run natively; on CPU (this container) they execute
through ``interpret=True`` when explicitly requested, and the production
model code falls back to the pure-jnp refs (kernels/ref.py) otherwise.
"""

from __future__ import annotations

import jax

from repro.core.tracing import flight_recorder

from . import ref
from .flash_attention import flash_attention_tpu
from .nbody import nbody_forces_tpu
from .ssd_scan import ssd
from .stencil5 import wave_step_tpu


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def flash_attention(q, k, v, *, causal=True, window=None, interpret=None):
    if on_tpu() or interpret:
        return flash_attention_tpu(q, k, v, causal=causal, window=window,
                                   interpret=bool(interpret) and not on_tpu())
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window)


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
