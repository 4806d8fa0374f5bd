"""host_gap_ms: device-idle milliseconds between consecutive runs of the
train-step program in the window, per step, from the profiler trace.
This is the host's share of each step: the trainer and its Runtime
issuing the next step.  Moves tokens_per_s."""


def read(ctx):
    red = ctx["trace"]
    runs = {n: k for n, k in red.program_runs.items() if "train_step" in n}
    if not runs:
        return None
    name = max(runs, key=runs.get)
    return 1e3 * red.program_gaps_s[name] / ctx["steps"]
