"""dispatch_ms: mean milliseconds of ``train.dispatch`` per step in the
window, from the flight recorder's spans: host time in the call of the
jitted step until it is enqueued.  Moves tokens_per_s."""


def read(ctx):
    sp = spans(ctx, "train.dispatch")
    if sp is None:
        return None
    d = [b - a for a, b in sp["train.dispatch"].values()]
    return 1e3 * sum(d) / len(d)


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
