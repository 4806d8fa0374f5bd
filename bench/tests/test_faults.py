"""A whole run of each cell, on the CPU at a small size of its model,
with the program's train step broken underneath: ``correct`` must come
out false for each fault a training cell on one chip can have, and true
with none.  (One chip: there is no exchange between chips to leave out.)
The harness's look for a chip is the only part of a run skipped."""

import contextlib
import time

import pytest

import run as bench_run
from faults import FAULTS, planted


def test_rows_other_than_the_generators_make_the_run_incorrect(tiny,
                                                                monkeypatch):
    from repro.data.pipeline import SyntheticLMData
    fed = SyntheticLMData.local_batch

    def shifted(self, step, *a):
        return fed(self, step + 1, *a)

    monkeypatch.setattr(SyntheticLMData, "local_batch", shifted)
    out = bench_run.run_cell(tiny, 2**31 + 6, 0.5, False,
                             t_start=time.perf_counter())
    assert out["correct"] is False
    assert out["checks"]["rows_wrong"]["value"] == 3, out["checks"]


@pytest.mark.parametrize("fault", (None,) + FAULTS)
def test_fault_makes_the_run_incorrect(tiny, fault):
    with planted(fault) if fault else contextlib.nullcontext():
        out = bench_run.run_cell(tiny, 2**31 + 5, 0.5, False,
                                 t_start=time.perf_counter())
    assert out["correct"] is (fault is None), out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(tiny.limits)
