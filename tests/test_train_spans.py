"""The trainer's spans and counter in the flight recorder
(``core/tracing.py``, ``runtime/train_loop.py``): a tiny Mamba2 trained for
four steps through ``TrainLoop`` inside a CPU profiler session."""

import dataclasses
from collections import defaultdict

import pytest

import repro.runtime.train_loop as train_loop
from repro.configs import get_config
from repro.core.tracing import FLIGHT_RECORDER_CAPACITY, Tracer, flight_recorder
from repro.runtime import TrainLoop

STEPS = 4
CFG = dataclasses.replace(get_config("mamba2-370m"), num_layers=2,
                          d_model=128, vocab_size=512, ssm_state=16)
STEP_CHILDREN = ("train.stage_read", "train.dispatch", "train.loss_wait")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The recorder of one four-step run, the profile of the session it
    ran in, and the Runtimes the trainer built."""
    import jax
    from jax.profiler import ProfileData

    built = []

    class Runtime(train_loop.Runtime):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            built.append(self)

    rec = Tracer()
    loop = TrainLoop(CFG, global_batch=2, seq_len=64, lr=1e-4, tracer=rec)
    out = tmp_path_factory.mktemp("profile")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(train_loop, "Runtime", Runtime)
        jax.profiler.start_trace(str(out), profiler_options=opts)
        try:
            loop.run(STEPS)
        finally:
            jax.profiler.stop_trace()
    pd = ProfileData.from_file(str(sorted(out.rglob("*.xplane.pb"))[-1]))
    return rec, pd, built, loop


def by_name(rec):
    out = defaultdict(dict)
    for s in rec.spans:
        assert s.meta["step"] not in out[s.name], (s.name, s.meta)
        out[s.name][s.meta["step"]] = s
    return out


def test_one_step_and_one_prefetch_span_per_step(traced):
    spans = by_name(traced[0])
    for name in ("train.step", "train.prefetch", "data.batch") + STEP_CHILDREN:
        assert sorted(spans[name]) == list(range(STEPS)), name
    assert {s.lane for s in traced[0].spans} == {"run", "prefetch", "step"}
    assert "train.ckpt" not in spans          # no checkpoint directory given
    [run] = spans["train.run"].values()       # one run, holding every task
    assert all(run.t0 <= s.t0 <= s.t1 <= run.t1 for s in traced[0].spans)


def test_stage_read_dispatch_and_loss_wait_nest_in_their_step(traced):
    spans = by_name(traced[0])
    for t in range(STEPS):
        step = spans["train.step"][t]
        kids = [spans[n][t] for n in STEP_CHILDREN]
        assert step.t0 <= kids[0].t0
        for a, b in zip(kids, kids[1:]):
            assert a.t1 <= b.t0                  # in this order, apart
        assert kids[-1].t1 <= step.t1
        pre, batch = spans["train.prefetch"][t], spans["data.batch"][t]
        assert pre.t0 <= batch.t0 <= batch.t1 <= pre.t1
        assert pre.t1 <= step.t0                 # the step reads the batch


def test_host_turn_equals_its_parts(traced):
    """From the end of step t's loss wait to the end of step t+1's
    dispatch: the rest of step t, the Runtime issuing step t+1, step t+1
    up to its dispatch's end."""
    spans = by_name(traced[0])
    step, wait, disp = (spans[n] for n in ("train.step", "train.loss_wait",
                                           "train.dispatch"))
    for t in range(STEPS - 1):
        turn = disp[t + 1].t1 - wait[t].t1
        parts = (step[t].t1 - wait[t].t1, step[t + 1].t0 - step[t].t1,
                 disp[t + 1].t1 - step[t + 1].t0)
        assert min(parts) >= 0, parts
        assert turn == pytest.approx(sum(parts), abs=1e-6)


def test_capacity_keeps_only_the_newest_spans():
    rec = Tracer(capacity=8)
    for t in range(20):
        with rec.scope("step", "train.step", step=t):
            pass
        rec.counter("runtime.instructions", t)
    assert [s.meta["step"] for s in rec.spans] == list(range(12, 20))
    assert [v for _, v in rec.counters["runtime.instructions"]] == list(
        range(12, 20))


def test_a_span_is_kept_when_its_block_raises():
    rec = Tracer()
    with pytest.raises(RuntimeError):
        with rec.scope("step", "train.step", step=3):
            raise RuntimeError("step failed")
    [s] = rec.spans
    assert (s.name, s.meta) == ("train.step", {"step": 3})


def test_spans_reach_the_profilers_host_plane(traced):
    rec, pd = traced[:2]
    seen = defaultdict(list)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("train.", "data.")):
                    seen[e.name].append(dict(e.stats).get("step_num"))
    assert {n: len(v) for n, v in seen.items()} == {
        n: len(v) for n, v in by_name(rec).items()}
    assert sorted(seen["train.step"]) == list(range(STEPS))


def test_the_trainers_runtime_records_no_instructions(traced):
    rec, _, built, loop = traced
    assert len(built) == 1 and built[0].tracer is None
    assert not hasattr(loop, "overlap")
    [(_, n)] = rec.counters["runtime.instructions"]
    assert n >= 2 * STEPS                      # a prefetch and a step each


def test_a_trainer_writes_into_the_flight_recorder_by_default():
    loop = TrainLoop(CFG, global_batch=2, seq_len=64)
    assert loop.tracer is flight_recorder() is flight_recorder()
    assert loop.tracer.spans.maxlen == FLIGHT_RECORDER_CAPACITY


def test_each_step_is_enqueued_before_the_previous_loss_is_read():
    """The loss wait trails the dispatch by one step: step t is enqueued
    before step t-1's loss is read on the host, so the device has the
    next step queued whenever it finishes one.  Losses still come back
    in order, each with its own step."""
    events = []

    class Loss:
        def __init__(self, t):
            self.t = t

        def __float__(self):
            events.append(("read", self.t))
            return float(self.t)

    loop = TrainLoop(CFG, global_batch=2, seq_len=64, lr=1e-4,
                     tracer=Tracer())
    calls = iter(range(STEPS))

    def step(params, opt, batch):
        t = next(calls)
        events.append(("dispatch", t))
        return params, opt, {"loss": Loss(t)}

    loop.train_step = step
    state = {"params": {}, "opt": {}}
    _, _, m = loop.run(STEPS, start_step=0, state=state)
    assert m.steps == list(range(STEPS))
    assert m.losses == [float(t) for t in range(STEPS)]
    assert events == [("dispatch", 0)] + [
        e for t in range(1, STEPS) for e in (("dispatch", t), ("read", t - 1))
    ] + [("read", STEPS - 1)]
