"""Readings that set a training cell's limits, on the chip at the cell's
own size:

    python bench/control.py --workload <name> --seeds 11 12 13 [--faults]
        [--sound-seeds 14 15 ...]

For each seed: the plain reference; the program's checked steps (a sound
run, for the lower reading); the control, the reference computed with
float8 products in the program's place (for the upper reading); and with
``--faults`` the program with each fault of ``faults.py`` planted.  Each
is compared with the reference as a run compares the program.  One JSON
line per seed and kind; the benchmark's own runs do not run this.
"""

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from checks import readings  # noqa: E402
from faults import FAULTS, planted  # noqa: E402
from spec import load_cell  # noqa: E402
from train_cell import Trainer, reference  # noqa: E402


def program_readings(cell, seed: int, n: int, log=print) -> dict:
    t = Trainer(cell, seed, log)
    try:
        prog = t.checked_steps(n)
    finally:
        t.close()
    return prog


def seed_readings(cell, seed: int, faults: bool, control: bool = True,
                  log=print) -> list[dict]:
    n = cell.traffic["checked_steps"]
    ref = reference(cell, seed, n)
    runs = [("sound", lambda: program_readings(cell, seed, n, log))]
    if control:
        runs.append(("control_fp8",
                     lambda: reference(cell, seed, n, dot="fp8")))
    if faults:
        for f in FAULTS:
            def run(f=f):
                with planted(f):
                    return program_readings(cell, seed, n, log)
            runs.append((f, run))
    out = []
    for kind, fn in runs:
        t0 = time.perf_counter()
        got = fn()
        v = readings(dict({"rows_wrong": 0}, **got, window_steps_wrong=0),
                     ref)
        where = v.pop("_where")
        out.append({"seed": seed, "kind": kind, "readings": v,
                    "where": where, "losses": got["losses"],
                    "ref_losses": ref["losses"],
                    "leaves": {k: {q: [got[k][q], ref[k][q]] for q in ref[k]}
                               for k in ("grad", "update")},
                    "seconds": time.perf_counter() - t0})
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--sound-seeds", type=int, nargs="*", default=[],
                    help="seeds on which only the program is read")
    args = ap.parse_args()
    cell = load_cell(args.workload)
    err = lambda *a: print(*a, file=sys.stderr)  # noqa: E731
    for seed in args.seeds + args.sound_seeds:
        full = seed in args.seeds
        for rec in seed_readings(cell, seed, args.faults and full,
                                 control=full, log=err):
            print(json.dumps(dict(rec, workload=cell.name)), flush=True)


if __name__ == "__main__":
    main()
