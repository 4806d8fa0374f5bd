"""attention_kernel_ms: device self time per step of the fused causal
attention, in ms: splash attention's Pallas kernels ``splash_mha_fwd*``
(the forward and ``remat``'s second forward), ``splash_mha_dkv*`` and
``splash_mha_dq*`` (the backward), found by name among the trace's
operations and summed over the window, over the window's steps.  Nothing
where no such kernel ran (the unfused einsum-softmax-einsum path in their
place, or no attention).  Moves tokens_per_s."""

import re

SPLASH = re.compile(r"splash_mha_(fwd|dkv|dq)\w*(\.\d+)? ")


def read(ctx):
    t = sum(s for name, s in ctx["trace"].top_ops if SPLASH.match(name))
    return 1e3 * t / ctx["steps"] if t > 0 else None
