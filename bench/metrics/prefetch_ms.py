"""prefetch_ms: milliseconds per step spent in the data pipeline's
``local_batch`` (the benchmark's host span around it) during the window.
Moves tokens_per_s where it is not hidden behind the device."""


def read(ctx):
    t0, t1 = ctx["window"]
    return 1e3 * ctx["spans"].total("bench.prefetch", t0, t1) / ctx["steps"]
