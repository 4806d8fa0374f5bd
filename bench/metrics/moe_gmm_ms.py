"""moe_gmm_ms: device self time per step of the MoE's grouped matmuls,
in ms: megablox's Pallas kernels ``gmm`` (the forward products and each
product's input gradient) and ``tgmm`` (each weight gradient), found by
name among the trace's operations and summed over the window, over the
window's steps.  Nothing where no such kernel ran (``ragged_dot`` in
their place, or no MoE).  Moves tokens_per_s."""

import re

GMM = re.compile(r"t?gmm(\.\d+)? ")


def read(ctx):
    t = sum(s for name, s in ctx["trace"].top_ops if GMM.match(name))
    return 1e3 * t / ctx["steps"] if t > 0 else None
