"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout that holds the program (``src/``).  The cell
is an entry of ``workloads`` in ``BENCHMARK.json``; everything it needs is
found by name under ``bench/`` (see ``bench/spec.py``).  With ``--trace 0``
the result carries the cell's end-to-end metrics; with ``--trace 1`` the
window runs under the profiler and the result carries the per-layer
metrics, the device's busy and window seconds, and a breakdown.  Either
way the run checks what its timed path produced against the plain
reference and prints each compared number beside its limit, as the last
lines of standard error and under ``checks``, the last key of the
result, the last line of standard output.

It runs on the machine's first TPU chip and exits non-zero, printing no
result, where JAX finds no TPU or fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from spec import load_cell, metric_reader  # noqa: E402


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def require_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < n:
        sys.exit(f"bench: the cell needs {n} chips, JAX found {len(devs)}")


def peak_of(kind: str) -> dict:
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]


def finite_or_none(v):
    return v if isinstance(v, int) or math.isfinite(v) else None


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, trace_dir=None) -> dict:
    """One run of ``cell``: the result line as a dict."""
    import jax
    import train_cell
    from devtrace import extract, find_xplane, load, reduce

    if cell.traffic["kind"] != "train":
        raise ValueError(f"unknown traffic kind {cell.traffic['kind']!r}")
    tmp = Path(tempfile.mkdtemp(prefix="bench-trace-")) if trace else None
    try:
        r = train_cell.run(cell, seed, seconds, trace, t_start,
                           trace_dir=trace_dir or tmp, log=log)
        red = (reduce(extract(load(find_xplane(trace_dir or tmp))))
               if trace else None)
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)
    dev = r["device"]
    t0, t1 = r["window"]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": r["memory_peak"]}
    values = {"tokens_per_s": r["tokens"] / (t1 - t0),
              "step_ms_p95": statistics.quantiles(
                  r["steps_ms"], n=20, method="inclusive")[18]
              if len(r["steps_ms"]) > 1 else r["steps_ms"][0],
              "setup_s": r["setup_s"]}
    out = {"correct": r["correct"], "attempted": r["attempted"],
           "failed": r["failed"], "metrics": {}, "device": device}
    if not trace:
        for m in cell.end_to_end:
            out["metrics"][m["name"]] = {"value": values[m["name"]],
                                         "unit": m["unit"]}
    else:
        ctx = dict(r, trace=red, flops_per_token=cell.model
                   .model_flops_per_token(cell.config, cell.traffic["seq"]),
                   peak=peak_of(dev.device_kind))
        for m in cell.per_layer:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        out["breakdown"] = {"device_ops": [list(x) for x in red.top_ops[:10]],
                            "idle_gaps": [list(x) for x in red.gaps[:10]]}
    gc_s = r["gc_s"]
    slowest = max(range(len(r["steps_ms"])), key=r["steps_ms"].__getitem__)
    log(f"[run] {cell.name} seed {seed}: {r['steps']} steps in "
        f"{t1 - t0:.3f} s (warm-up step {r['warm_step_s']:.4f} s, timed in "
        f"groups of {r['step_group']}), "
        f"set-up {r['setup_s']:.2f} s, compile {r['compile_s']:.2f} s, "
        f"compiles in the window {r['window_compiles']}; step ms min "
        f"{min(r['steps_ms']):.2f} median {statistics.median(r['steps_ms']):.2f}"
        f" max {max(r['steps_ms']):.2f} (step {slowest} of the window); "
        f"{len(gc_s)} garbage collections in the window, "
        f"{1e3 * sum(gc_s):.1f} ms in all, the longest "
        f"{1e3 * max(gc_s, default=0):.1f} ms; end-to-end {values}")
    out["checks"] = {k: {"value": finite_or_none(c["value"]),
                         "limit": c["limit"]}
                     for k, c in r["checks"].items()}
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the raw profiler trace here (default: a "
                         "temporary directory, removed after reading)")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")
    cell = load_cell(args.workload)
    require_chips(cell.chips)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   t_start=T_START, trace_dir=args.trace_dir)
    for name, c in out["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
