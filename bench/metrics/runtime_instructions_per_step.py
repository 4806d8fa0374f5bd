"""runtime_instructions_per_step: instructions the trainer's Runtime
executed per step: the ``runtime.instructions`` samples (one per
``TrainLoop.run``) in the flight recorder inside the window, over the
window's ``train.step`` spans.  Moves tokens_per_s."""


def read(ctx):
    sp = spans(ctx, "train.step")
    if sp is None:
        return None
    from repro.core.tracing import flight_recorder
    rec = flight_recorder()
    w0, w1 = ctx["window"]
    done = [v for t, v in list(rec.counters.get("runtime.instructions", ()))
            if w0 <= t + rec.epoch <= w1]
    return sum(done) / len(sp["train.step"]) if done else None


def spans(ctx, *names):
    """{name: {step: (start, end)}} of the trainer's spans in the flight
    recorder that lie inside the window, on the window's clock
    (``perf_counter``); None where the program keeps none of them."""
    try:
        from repro.core.tracing import flight_recorder
    except ImportError:
        return None
    rec = flight_recorder()
    w0, w1 = ctx["window"]
    out = {n: {} for n in names}
    for s in list(rec.spans):
        a, b = s.t0 + rec.epoch, s.t1 + rec.epoch
        if s.name in out and w0 <= a and b <= w1:
            out[s.name][s.meta["step"]] = (a, b)
    return out if all(out.values()) else None
