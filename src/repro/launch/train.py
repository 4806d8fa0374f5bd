"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \
        --steps 100 --batch 8 --seq 128 [--full] [--ckpt DIR] \
        [--trace-out spans.json]

By default it trains the smoke-sized variant of the family; ``--full``
trains the published config, which needs an accelerator (``mamba2-370m``
at batch 4 x seq 1024 fits one TPU v5e chip).  The loop is the
IDAG-orchestrated TrainLoop: data prefetch, step dispatch and async
checkpointing overlap via the paper's scheduling machinery.  Compiled steps
go to the persistent cache of ``launch/compile_cache.py``.  ``--trace-out``
writes the trainer's spans and counters from the flight recorder as a
Chrome/Perfetto trace (open it in https://ui.perfetto.dev).
"""

from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-interval", type=int, default=50)
    ap.add_argument("--full", action="store_true",
                    help="use the full published config (TPU-scale)")
    ap.add_argument("--trace-out", default=None,
                    help="write the flight recorder's spans here as a "
                         "Chrome/Perfetto trace")
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.launch.compile_cache import use_persistent_cache
    from repro.runtime import TrainLoop

    use_persistent_cache()
    cfg = get_config(args.arch, reduced=not args.full)
    print(f"[train] {cfg.name} ({'full' if args.full else 'reduced'}): "
          f"{cfg.param_count() / 1e6:.1f}M params, "
          f"batch={args.batch} seq={args.seq}")
    loop = TrainLoop(cfg, global_batch=args.batch, seq_len=args.seq,
                     ckpt_dir=args.ckpt, ckpt_interval=args.ckpt_interval,
                     lr=args.lr)
    t0 = time.perf_counter()
    end, _, m = loop.run(args.steps)
    wall = time.perf_counter() - t0
    print(f"[train] {args.steps} steps in {wall:.1f}s "
          f"({wall / args.steps * 1e3:.0f} ms/step)")
    print(f"[train] loss {m.losses[0]:.4f} -> {m.losses[-1]:.4f}")
    if args.trace_out:
        n = loop.tracer.to_chrome_trace(args.trace_out)
        print(f"[train] {n} trace events -> {args.trace_out}")


if __name__ == "__main__":
    main()
