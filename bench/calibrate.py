"""Readings that the limits of ``check.py`` are set from, for one cell.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10 \
        [--out chiprun_out/calibrate/<cell>.json]

Makes one run of the cell per seed, in one process, through ``run.run``:
the same set-up, window and sample of compared answers as a benchmark run,
at the cell's own load for ``--seconds``.  Besides the program's numbers it
prints the control's: the reference computed in bfloat16, one precision
below the engine's float32 scores, put in the program's place for the same
requests.  The lower reading of a limit is the largest the program gives
over the seeds, the upper the smallest the control gives.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import run as bench_run  # noqa: E402

NUMBERS = ("wrong_hits", "score_gap", "rank_gap")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    import ml_dtypes
    out = {"workload": args.workload, "seconds": args.seconds, "seeds": []}
    for seed in [int(s) for s in args.seeds.split(",")]:
        try:
            res = bench_run.run(args.workload, seed, args.seconds, False,
                                t_start=time.monotonic(),
                                controls={"bfloat16": ml_dtypes.bfloat16})
        except bench_run.NoAccelerator as e:
            print(f"calibrate: {e}", file=sys.stderr)
            return 1
        row = {"seed": seed, "correct": res["correct"],
               "requests": res["attempted"], "failed": res["failed"],
               "metrics": res["metrics"],
               "program": res["controls"]["program"],
               "control": res["controls"]["bfloat16"]}
        print(f"seed: {json.dumps(row)}", flush=True)
        out["seeds"].append(row)
    for side in ("program", "control"):
        for name in NUMBERS:
            vals = [s[side][name] for s in out["seeds"]]
            print(f"{side} {name}: min {min(vals)} max {max(vals)}")
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
