"""The control: the plain reference computed with float8 products (one
scale per tensor, forward and backward), the next precision below the
bfloat16 the configurations compute in, put in the program's place.  It
must come out not correct under each cell's limits.

At the cell's own size this needs the chip, where it reads the control
on three seeds (about a minute a cell once compiled):

    python -m pytest -q bench/tests/test_control.py

On the CPU only the float8 product itself is checked: the control's
error at a small size is below what the cells' limits see at 48 or 8
layers and thousands of tokens, so a small-size run would prove
nothing about the limits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from checks import decide, readings
from conftest import CELLS
from reference import exact_dot, fp8_dot
from spec import load_cell
from train_cell import reference

SEEDS = (7001, 7002, 7003)


def test_fp8_products_round_both_operands_and_the_gradient():
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 256))
    b = jax.random.normal(jax.random.PRNGKey(1), (256, 32))
    exact = exact_dot("ij,jk->ik", a, b)
    err = float(jnp.max(jnp.abs(fp8_dot("ij,jk->ik", a, b) - exact))
                / jnp.max(jnp.abs(exact)))
    # e4m3 keeps 3 mantissa bits: products off by about 2**-4 / sqrt(n)
    assert 1e-3 < err < 0.1
    ga = jax.grad(lambda a: jnp.sum(fp8_dot("ij,jk->ik", a, b) ** 2))(a)
    ge = jax.grad(lambda a: jnp.sum(exact_dot("ij,jk->ik", a, b) ** 2))(a)
    rel = float(jnp.linalg.norm(ga - ge) / jnp.linalg.norm(ge))
    assert 1e-3 < rel < 0.2
    np.testing.assert_array_equal(np.isfinite(ga), True)


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_control_is_not_correct_at_the_cells_size(workload):
    if jax.devices()[0].platform != "tpu":
        pytest.skip("reads the control at the cell's own size: needs a TPU")
    cell = load_cell(workload)
    n = cell.traffic["checked_steps"]
    for seed in SEEDS:
        ref = reference(cell, seed, n)
        ctl = reference(cell, seed, n, dot="fp8")
        ok, checks = decide(readings(dict(ctl, window_steps_wrong=0,
                                          rows_wrong=0), ref), cell.limits)
        assert not ok, (seed, checks)
