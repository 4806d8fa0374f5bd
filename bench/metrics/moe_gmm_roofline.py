"""moe_gmm_roofline: the MoE's grouped matmuls' share of their roofline,
in percent: the least time their work needs on the chip, the larger of
the configuration's ``expert_flops_per_step`` over the bf16 peak and its
``expert_bytes_per_step`` over the HBM bandwidth (``peaks.json``), over
their measured self time per step (``moe_gmm_ms``).  The cell is the one
named by the command line's ``--workload``.  Nothing where the kernels
did not run or the configuration counts no expert work.  Moves
tokens_per_s."""

import argparse
import sys
from pathlib import Path

from spec import load_cell, load_module

BENCH = Path(__file__).resolve().parents[1]


def running_cell():
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload")
    name = ap.parse_known_args(sys.argv[1:])[0].workload
    return load_cell(name) if name else None


def share(ctx, cell):
    ms = load_module(BENCH / "metrics" / "moe_gmm_ms.py").read(ctx)
    model = cell.model if cell is not None else None
    if ms is None or not hasattr(model, "expert_flops_per_step"):
        return None
    B, S = cell.traffic["batch"], cell.traffic["seq"]
    peak = ctx["peak"]
    least_s = max(
        model.expert_flops_per_step(cell.config, B, S) / peak["bf16_flops"],
        model.expert_bytes_per_step(cell.config, B, S)
        / peak["hbm_bytes_per_s"])
    return 100.0 * 1e3 * least_s / ms


def read(ctx):
    return share(ctx, running_cell())
