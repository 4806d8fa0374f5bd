import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from spec import load_cell, make_cell  # noqa: E402

CELLS = {"mamba2-370m.train-s2048": "tiny-mamba2"}


def tiny_cell(workload: str):
    """The cell ``workload`` with its limits and reference, on a small
    configuration of the same model (``tests/data``) and a short mix."""
    real = load_cell(workload)
    data = BENCH / "tests" / "data"
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    config = next(w["config"] for w in spec["workloads"]
                  if w["name"] == workload)
    return make_cell(
        workload, chips=1,
        config_path=data / f"{CELLS[workload]}.json",
        model_path=BENCH / "configs" / f"{config}.py",
        traffic_path=data / "tiny-train.json",
        workload_path=BENCH / "workloads" / f"{workload}.json",
        end_to_end=real.end_to_end, per_layer=real.per_layer)


@pytest.fixture(params=sorted(CELLS))
def tiny(request):
    return tiny_cell(request.param)
