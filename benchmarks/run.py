"""Benchmark runner: one function per paper table + roofline report.

    PYTHONPATH=src python -m benchmarks.run [--full] [--skip-distributed]
                                            [--json PATH]

Prints ``name,us_per_call,derived`` CSV rows (the harness contract).
``--json PATH`` additionally writes the rows as a machine-readable artifact
(``{"bench": {name: us_per_call}, "beam_sweep": {...}, "serving": {...},
"megabatch": {...}, "anytime": {...}}`` — the BENCH_PR10.json artifact that
carries the perf trajectory; beam-sweep entries hold iters/pops ratios vs
P=1, serving entries the table 6 throughput/percentile/cache metrics —
every serving entry now also carries the queue-wait/service percentile
split, and the ``open_obs`` entry the registry-derived per-stage latency
attribution (queue_wait/device/slice/total) fed from the spans — megabatch
entries the table 7 skew/heavy-band tail latencies for mega vs lockstep vs
unbatched serving — anytime entries the table 8 budget ladder
(latency/recall/certified-fraction per rung) plus the served monotone
p99-vs-certified-fraction Pareto ``frontier``).  The artifact is also
mirrored into ``artifacts/`` so the committed trajectory and the CI upload
stay in one place.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-complete sweep (bands i-iv, 1-6 words, k=10/20)")
    ap.add_argument("--skip-distributed", action="store_true")
    ap.add_argument("--docs", type=int, default=2500)
    ap.add_argument("--mean-doc-len", type=int, default=200)
    ap.add_argument("--vocab", type=int, default=30_000)
    ap.add_argument("--json", type=str, default=None, metavar="PATH",
                    help="also write results as a JSON artifact")
    args = ap.parse_args()

    from repro.launch import compile_cache
    compile_cache.place_compile_cache()
    from benchmarks import (common, distributed_scaling, table1_compression,
                            table2_conjunctive, table3_bagofwords,
                            table4_positional, table5_beam, table6_serving,
                            table7_megabatch, table8_anytime)

    rows: dict[str, float] = {}

    def collect(line: str) -> None:
        """Print a CSV row and record it for the --json artifact."""
        print(line)
        try:
            name, us, _derived = line.split(",", 2)
            rows[name] = float(us)
        except ValueError:
            pass

    t0 = time.time()
    print("# building benchmark corpus ...", file=sys.stderr, flush=True)
    bench = common.build(n_docs=args.docs, mean_doc_len=args.mean_doc_len,
                         vocab=args.vocab)
    print(f"# corpus: {bench.cp.n_tokens} tokens, {bench.cp.n_docs} docs, "
          f"build {bench.build_s:.1f}s", file=sys.stderr, flush=True)

    print("name,us_per_call,derived")
    table1_compression.run(bench, print_rows=collect)

    if args.full:
        sweep = dict(n_queries=32, words_list=(1, 2, 3, 4, 6), ks=(10, 20),
                     band_names=("i", "ii", "iii", "iv"))
        sweep3 = dict(n_queries=32, words_list=(2, 3, 4, 6), ks=(10, 20),
                      band_names=("i", "ii", "iii", "iv"))
    else:
        sweep = dict(n_queries=16, words_list=(1, 2, 4), ks=(10,),
                     band_names=("i", "ii", "iii"))
        sweep3 = dict(n_queries=16, words_list=(2, 4), ks=(10,),
                      band_names=("i", "ii", "iii"))
    table2_conjunctive.run(bench, conjunctive=True, print_rows=collect, **sweep)
    table3_bagofwords.run(bench, print_rows=collect, **sweep3)
    if args.full:
        table4_positional.run(bench, n_queries=32, words_list=(2, 3, 4),
                              ks=(10, 20), windows=(4, 16, 64),
                              print_rows=collect)
    else:
        table4_positional.run(bench, print_rows=collect)

    beam = table5_beam.run(bench, print_rows=collect,
                           with_sharded=not args.skip_distributed)
    serving = table6_serving.run(bench, print_rows=collect)
    megabatch = table7_megabatch.run(bench, print_rows=collect)
    anytime = table8_anytime.run(bench, print_rows=collect)

    if not args.skip_distributed:
        distributed_scaling.run(print_rows=collect)

    # roofline summary (reads dry-run artifacts if present)
    try:
        from repro.analysis import roofline
        for r in roofline.load_all("single"):
            if r.skipped:
                continue
            collect(common.csv_row(
                f"roofline/{r.cell.replace(':', '__')}", 0.0,
                f"dom={r.dominant};frac={r.roofline_fraction():.3f}"))
    except Exception as e:  # artifacts absent: benches still usable
        print(f"# roofline artifacts unavailable: {e}", file=sys.stderr)

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"bench": rows, "beam_sweep": beam, "serving": serving,
                       "megabatch": megabatch, "anytime": anytime,
                       "config": {"docs": args.docs, "full": args.full}},
                      f, indent=2, sort_keys=True)
        print(f"# wrote {args.json}", file=sys.stderr)
        mirror = pathlib.Path(__file__).resolve().parent.parent / "artifacts"
        mirror.mkdir(exist_ok=True)
        target = mirror / pathlib.Path(args.json).name
        if target.resolve() != pathlib.Path(args.json).resolve():
            shutil.copy2(args.json, target)
            print(f"# mirrored to {target}", file=sys.stderr)

    print(f"# total {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
