"""BENCHMARK.json and the files it names: every cell resolves, every
configuration file agrees with the program, the generator is a function
of the seed, and a run without a TPU fails without a result."""

import json
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH
from spec import load_cell, metric_reader
from traffic import TokenBatches
from train_cell import arch_config, seed_key

SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = load_cell(name)
    cfg = arch_config(cell.config)
    assert cfg.num_layers == cell.config["num_layers"]
    assert set(cell.limits) <= {"window_steps_wrong", "rows_wrong", "loss_gap",
                                "grad_gap", "grad_gap_large", "update_gap"}
    assert cell.limits["window_steps_wrong"] == 0
    assert cell.limits["rows_wrong"] == 0
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    for m in cell.per_layer:
        assert callable(metric_reader(m["name"]))


def test_configuration_file_must_agree_with_the_program():
    cell = load_cell(CELLS[0])
    with pytest.raises(ValueError):
        arch_config(dict(cell.config, num_layers=cell.config["num_layers"] + 1))


def test_batches_are_a_function_of_seed_and_step():
    tr = load_cell(CELLS[0]).traffic
    a = TokenBatches(tr, 50280, 2**31 + 9)
    b = TokenBatches(tr, 50280, 2**31 + 9)
    np.testing.assert_array_equal(a.tokens(3), b.tokens(3))
    assert a.tokens(3).shape == (tr["batch"], tr["seq"])
    assert not np.array_equal(a.tokens(3), a.tokens(4))
    assert not np.array_equal(a.tokens(3), TokenBatches(tr, 50280, 9).tokens(3))
    t = a.tokens(0)
    assert t.dtype == np.int32 and t.min() >= 0 and t.max() < 50280
    assert len({r.tobytes() for r in t}) == tr["batch"]


def test_generator_rows_are_the_programs_pipeline_rows():
    from repro.data.pipeline import SyntheticLMData
    cell = load_cell(CELLS[0])
    cfg, tr = arch_config(cell.config), cell.traffic
    seed = 2**31 + 11
    program = SyntheticLMData(cfg, tr["batch"], tr["seq"], seed=seed)
    ours = TokenBatches(tr, cfg.vocab_size, seed)
    for step in (0, 1, 57):
        fed = program.local_batch(step)
        np.testing.assert_array_equal(fed["tokens"], ours.tokens(step))
        np.testing.assert_array_equal(fed["labels"], ours.tokens(step))


def test_seed_keys_differ_beyond_32_bits():
    import jax
    keys = {tuple(np.asarray(jax.random.key_data(seed_key(s))).tolist())
            for s in (7, 2**32 + 7, 2**33 + 7)}
    assert len(keys) == 3


def test_run_without_a_tpu_fails_without_a_result():
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=BENCH.parent,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr
