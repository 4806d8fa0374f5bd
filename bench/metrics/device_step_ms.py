"""device_step_ms: device-busy milliseconds per step, the union of the
intervals in which an operation ran on the chip over the window, from
the profiler trace.  Moves tokens_per_s."""


def read(ctx):
    return 1e3 * ctx["trace"].busy_s / ctx["steps"]
