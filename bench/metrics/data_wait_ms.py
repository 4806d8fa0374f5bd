"""data_wait_ms: the time a step waited for its data.  Per pair of
consecutive steps, max(0, end of ``train.prefetch`` t+1 - end of
``train.step`` t) milliseconds, from the flight recorder's spans; the mean
over the window's pairs.  Moves tokens_per_s."""


def read(ctx):
    sp = spans(ctx, "train.step", "train.prefetch")
    if sp is None:
        return None
    step, pre = sp["train.step"], sp["train.prefetch"]
    waits = [max(0.0, pre[t + 1][1] - step[t][1]) for t in step
             if t + 1 in pre]
    return 1e3 * sum(waits) / len(waits) if waits else None


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
