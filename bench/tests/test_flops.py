"""Each configuration's model-FLOPs function against XLA's count of the
program's forward pass (``compiled.cost_analysis()`` on the CPU), at the
configuration's widths with one layer: XLA counts a loop's body once, so
one layer is the depth at which it counts the whole forward.

The function counts forward and backward once (3x the forward).  What it
leaves out, and the count includes, is stated per configuration:

- mamba2-370m: elementwise work only (norms, conv, gates, exps,
  cross-entropy), a few percent.  The function counts the SSD at the
  source's chunk; here it is asked for the chunk the program runs at,
  which is what XLA counts.

In training the program also recomputes the forward in the backward
(remat), which the function leaves out by design.
"""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from spec import load_module  # noqa: E402

B, S = 1, 512

# the arguments that make a function count what the program runs
AS_RUN = {"mamba2-370m": lambda cfg: {"chunk": cfg.ssm_chunk}}


@pytest.mark.parametrize("name", sorted(AS_RUN))
def test_model_flops_against_xla(name):
    import jax
    import jax.numpy as jnp
    from train_cell import arch_config
    from repro.models import build_model

    config = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    config = dict(config, num_layers=1,
                  overrides=dict(config["overrides"], num_layers=1))
    cfg = arch_config(config)
    model = build_model(cfg)
    fn = load_module(BENCH / "configs" / f"{name}.py").model_flops_per_token
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((B, S), jnp.int32)
    compiled = jax.jit(lambda p, t: model.loss(
        p, {"tokens": t, "labels": t})).lower(params, toks).compile()
    cost = compiled.cost_analysis()
    xla = (cost[0] if isinstance(cost, list) else cost)["flops"]

    expected = fn(config, S, **AS_RUN[name](cfg)) / 3 * B * S
    assert expected <= xla <= 1.03 * expected, (xla, expected)


@pytest.mark.parametrize("name", sorted(AS_RUN))
def test_model_flops_scale_with_depth(name):
    config = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    fn = load_module(BENCH / "configs" / f"{name}.py").model_flops_per_token
    f1 = fn(dict(config, num_layers=1), S)
    f2 = fn(dict(config, num_layers=2), S)
    head = 3 * 2 * config["vocab_size"] * config["d_model"]
    assert f2 - f1 == pytest.approx(f1 - head)


def test_mamba2_count_does_not_follow_the_programs_chunk():
    config = json.loads((BENCH / "configs" / "mamba2-370m.json").read_text())
    fn = load_module(BENCH / "configs" / "mamba2-370m.py").model_flops_per_token
    assert fn(config, 2048) == fn(config, 2048, chunk=config["chunk_size"])
    assert fn(config, 2048) != fn(config, 2048, chunk=64)
