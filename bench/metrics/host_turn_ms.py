"""host_turn_ms: the trainer's turn between two steps, the program's own
view of the host leg that ``host_gap_ms`` sees from the device.  Per pair
of consecutive steps, milliseconds from the end of step t's
``train.loss_wait`` (its loss on the host) to the end of step t+1's
``train.dispatch`` (the next step enqueued), from the flight recorder's
spans; the mean over the window's pairs.  It is step t's rest after the
wait, then ``runtime_issue_ms``, then step t+1 up to its dispatch's end;
the wait for the loss itself is not in it.  Moves tokens_per_s."""


def read(ctx):
    sp = spans(ctx, "train.loss_wait", "train.dispatch")
    if sp is None:
        return None
    wait, disp = sp["train.loss_wait"], sp["train.dispatch"]
    turns = [disp[t + 1][1] - wait[t][1] for t in wait if t + 1 in disp]
    return 1e3 * sum(turns) / len(turns) if turns else None


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
