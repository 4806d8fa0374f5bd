"""device_idle_share: the share of the traced window in which no
operation ran on the chip, in percent.  Moves tokens_per_s."""


def read(ctx):
    red = ctx["trace"]
    return 100.0 * (1.0 - red.busy_s / red.window_s)
