"""runtime_issue_ms: per pair of consecutive steps, milliseconds from the
end of ``train.step`` t to the start of ``train.step`` t+1, from the flight
recorder's spans: the Runtime's executor completing step t's instruction,
resolving step t+1's dependency and handing it to a host worker.  The mean
over the window's pairs.  Moves tokens_per_s."""


def read(ctx):
    sp = spans(ctx, "train.step")
    if sp is None:
        return None
    step = sp["train.step"]
    gaps = [step[t + 1][0] - step[t][1] for t in step if t + 1 in step]
    return 1e3 * sum(gaps) / len(gaps) if gaps else None


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
