"""The trace reduction on a trace recorded on one TPU v5e chip: two steps
of training an 8-layer granite-moe-1b-a400m at batch 1 x 4096 through
this harness (``--seconds 0.4 --trace 1``), the profiler's xplane
compressed with xz.  The expected numbers were read
from the trace by hand: the two ``jit_train_step`` module events, the
``bench.window`` host span, and the gaps around them."""

import lzma
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402

TRACE = BENCH / "tests" / "data" / "granite-2steps.xplane.pb.xz"


@pytest.fixture(scope="module")
def reduction():
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(lzma.decompress(TRACE.read_bytes()))
    return devtrace.reduce(devtrace.extract(pd))


def test_window_and_programs(reduction):
    # bench.window: 92,725,524 ns to 516,459,068 ns
    assert reduction.window_s == pytest.approx(0.423733544, abs=1e-9)
    # jit_train_step ran twice: 103,206,761-304,978,653 and
    # 308,724,547-510,505,883 ns, so 3,745,894 ns idle between them
    assert reduction.program_runs == {"jit_train_step": 2}
    assert reduction.program_gaps_s["jit_train_step"] == pytest.approx(
        0.003745894, abs=1e-9)


def test_busy_is_the_union_of_operations(reduction):
    # the two programs hold 403.553 ms; their operations leave a few
    # microseconds between them inside
    assert 0.4034 < reduction.busy_s <= 0.403553228
    assert sum(t for _, t in reduction.top_ops) == pytest.approx(
        reduction.busy_s, rel=1e-9)


def test_gaps(reduction):
    # before the first program (the trainer's Runtime starting, the first
    # prefetch and dispatch), after the last, and between the two; the
    # rest are microseconds inside the programs, some while the next step
    # is dispatched
    assert {label for label, _ in reduction.gaps[:3]} == {devtrace.OUTSIDE}
    assert {label for label, _ in reduction.gaps} == {devtrace.OUTSIDE,
                                                      "bench.dispatch"}
    longest = [s for _, s in reduction.gaps[:3]]
    assert longest == pytest.approx([0.010482371, 0.005954995, 0.003748801],
                                    abs=2e-6)
    assert sum(s for _, s in reduction.gaps) == pytest.approx(
        reduction.window_s - reduction.busy_s, rel=1e-9)


def test_top_operations_are_self_times(reduction):
    name, secs = reduction.top_ops[0]
    # the layer loops (while.13, while.14) hold the step's operations and
    # keep little time of their own
    assert not name.startswith("while")
    assert name.startswith("fusion.1335 (f32[8,2,4096], bf16[8,2,4096,4096])")
    assert secs == pytest.approx(0.025614966, rel=1e-6)


def test_label_gap_prefers_the_span_covering_most():
    spans = [("bench.prefetch", 0, 4), ("bench.prefetch", 3, 6),
             ("bench.dispatch", 6, 7)]
    assert devtrace.label_gap(0, 10, spans) == "bench.prefetch"
    assert devtrace.label_gap(5, 10, spans) == devtrace.OUTSIDE
    assert devtrace.label_gap(6, 7.5, spans) == "bench.dispatch"


def test_self_times_of_nested_operations():
    ops = [("%while.1 = f32[] while()", 0, 10),
           ("%fusion.2 = f32[4]{0} fusion()", 1, 4),
           ("%fusion.3 = f32[4]{0} fusion()", 5, 9),
           ("%copy.4 = f32[4]{0} copy()", 12, 13)]
    assert dict(devtrace.self_times(ops)) == {
        "while.1 f32[]": 3, "fusion.2 f32[4]": 3, "fusion.3 f32[4]": 4,
        "copy.4 f32[4]": 1}
