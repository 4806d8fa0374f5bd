"""The readers of the trainer's spans and counter in the flight recorder,
on a recorder made by hand: three steps inside the window, a fourth that
ends after it, and the numbers worked out by hand below (milliseconds
after the recorder's epoch; the window is 4 to 1,000)."""

import pytest

import repro.core.tracing as tracing
from spec import metric_reader

EPOCH = 50.0
WINDOW = (EPOCH + 0.004, EPOCH + 1.0)
SPANS = {  # name: [(step, start ms, end ms)]
    "train.prefetch": [(0, 5, 10), (1, 15, 18), (2, 590, 603), (3, 890, 1002)],
    "data.batch": [(0, 5, 9), (1, 15, 17), (2, 590, 602), (3, 890, 1001)],
    "train.step": [(0, 20, 300), (1, 302, 600), (2, 605, 900),
                   (3, 1003, 1300)],
    "train.stage_read": [(0, 20, 20.1), (1, 302, 302.2), (2, 605, 605.3),
                         (3, 1003, 1003.1)],
    "train.dispatch": [(0, 20.1, 21.1), (1, 302.2, 303.7), (2, 605.3, 607.3),
                       (3, 1003.1, 1004)],
    "train.loss_wait": [(0, 21.1, 299.5), (1, 303.7, 599), (2, 607.3, 899.8),
                        (3, 1004, 1299)],
}
# instructions the Runtime executed: one run in the window, one after
COUNTS = [(950, 12), (1100, 99)]

EXPECTED = {
    # (303.7 - 299.5 + 607.3 - 599) / 2
    "host_turn_ms": 6.25,
    # (302 - 300 + 605 - 600) / 2
    "runtime_issue_ms": 3.5,
    "stage_read_ms": (0.1 + 0.2 + 0.3) / 3,
    "dispatch_ms": (1.0 + 1.5 + 2.0) / 3,
    # step 1 waited 603 - 600 for its data, step 0 not at all
    "data_wait_ms": 1.5,
    "runtime_instructions_per_step": 12 / 3,
}


@pytest.fixture
def recorder(monkeypatch):
    rec = tracing.Tracer(capacity=64)
    rec.epoch = EPOCH
    monkeypatch.setattr(tracing, "flight_recorder", lambda: rec)
    return rec


def fill(rec):
    for name, spans in SPANS.items():
        for step, a, b in spans:
            rec.span("train", "scope", name, a / 1e3, b / 1e3, {"step": step})
    rec.counters["runtime.instructions"].extend(
        (t / 1e3, v) for t, v in COUNTS)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_the_hand_computed_value(recorder, name):
    fill(recorder)
    got = metric_reader(name)({"window": WINDOW, "steps": 3})
    assert got == pytest.approx(EXPECTED[name], abs=1e-9)


def test_host_turn_is_its_parts(recorder):
    """The rest of step t after its loss wait (0.5 and 1 ms), the
    Runtime's issue, then step t+1 up to its dispatch's end (1.7, 2.3)."""
    fill(recorder)
    ctx = {"window": WINDOW, "steps": 3}
    rest, upto = (0.5 + 1.0) / 2, (1.7 + 2.3) / 2
    assert metric_reader("host_turn_ms")(ctx) == pytest.approx(
        rest + metric_reader("runtime_issue_ms")(ctx) + upto, abs=1e-9)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_none_with_no_spans(recorder, name):
    assert metric_reader(name)({"window": WINDOW, "steps": 3}) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_none_outside_the_window(recorder, name):
    fill(recorder)
    late = (EPOCH + 2.0, EPOCH + 3.0)
    assert metric_reader(name)({"window": late, "steps": 3}) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_none_for_a_program_without_the_recorder(monkeypatch,
                                                              name):
    monkeypatch.delattr(tracing, "flight_recorder")
    assert metric_reader(name)({"window": WINDOW, "steps": 3}) is None
