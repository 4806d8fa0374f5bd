"""CPU rehearsal of ``chip_smoke.py``: its phase functions at the reduced
mamba2 config and with interpret-mode kernels.  Only the platform check is
bypassed, by calling the phases instead of ``main``."""

import importlib.util
import math
from pathlib import Path

import jax.numpy as jnp
import pytest

from repro.configs import get_config

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_train_phase_matches_plain_loop(smoke):
    cfg = get_config(smoke.TRAIN["arch"], reduced=True)
    out = smoke.train_phase(cfg, batch=2, seq=64, steps=4,
                            lr=smoke.TRAIN["lr"])
    losses = out["losses"]
    assert len(losses) == 4 and all(math.isfinite(x) for x in losses)
    assert losses[-1] < losses[0]


def test_kernel_phase_interpret(smoke):
    sizes = dict(nbody=dict(n=300, target_stride=7), wave=dict(h=64, w=256),
                 ssd=dict(b=1, s=128, h=2, p=16, n=8, chunk=32),
                 flash=dict(b=1, s=128, k=2, g=2, hd=32))
    assert set(sizes) == set(smoke.KERNELS)
    worst = smoke.kernel_phase(sizes, interpret=True)
    assert set(worst) == set(sizes)


@pytest.mark.parametrize("bad", [1.0, float("nan")])
def test_kernel_phase_fails_outside_tolerance(smoke, monkeypatch, bad):
    """A kernel that misses its reference, or returns NaN, fails the
    phase after every kernel has been checked."""
    def off(interpret, **_):
        return [(jnp.full(4, bad), jnp.zeros(4))]

    monkeypatch.setattr(smoke, "CHECKS", {"wave": off, "ssd": off})
    with pytest.raises(AssertionError, match=r"\['wave', 'ssd'\]"):
        smoke.kernel_phase(smoke.KERNELS)
