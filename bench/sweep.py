"""Find a cell's knee once: open-loop rates swept in one process.

    python bench/sweep.py --workload <cell> --seed <n> --rates 2,4,8 \
        --seconds 20 [--clients 8,16] [--memory 8,16]

Builds the cell's collection, engine and warm server once, then serves the
cell's query mix open loop at each rate for ``--seconds`` (latency from the
due time), or closed loop at each client count, and prints one line per
point: offered rate, answered per second within the window, p50/p95 ms,
requests still unanswered when the window closed, and the mean batch.  The
knee is the highest rate whose answers keep up with no growing backlog; the
cell's traffic file takes 4/5 of it as a number.  ``--memory`` compiles the
mix's longest-query executor at those batch sizes and prints the v5e's
``memory_analysis()`` of each, which sets the configuration's
``max_batch``.  Writes the points to ``--out`` as JSON.
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


def point(sess, seconds, *, rate=None, clients=None) -> dict:
    """One load point through the benchmark's own window (``run.serve``)."""
    import numpy as np
    arrivals = ({"loop": "open", "rate_qps": rate} if rate is not None
                else {"loop": "closed", "clients": clients})
    win = bench_run.serve(sess, arrivals, seconds)
    ok = [r for r in win.requests if r.failure is None]
    lat = np.asarray([r.latency_s for r in ok]) * 1e3
    n_b = sum(win.batch_hist.values())
    return {"rate": rate, "clients": clients, "sent": len(win.requests),
            "answered_per_s": sum(r.ticket.t_done <= win.t_end for r in ok)
            / seconds,
            "p50_ms": float(np.percentile(lat, 50)) if len(lat) else None,
            "p95_ms": float(np.percentile(lat, 95)) if len(lat) else None,
            "unanswered_at_close": len(win.requests) - sum(
                r.ticket.t_done <= win.t_end for r in ok),
            "failed": len(win.requests) - len(ok),
            "mean_batch": (sum(b * c for b, c in win.batch_hist.items())
                           / n_b if n_b else None),
            "batches": win.batch_hist,
            "gc_full": len(win.gc_pauses)}


def memory(sess, batches) -> list[dict]:
    """v5e memory of the longest-query executor at each batch size."""
    out = []
    q = sess.queries.warmup_set()[-1]
    kw = sess.profile.search_kwargs()
    for b in batches:
        ma = sess.engine.lower([q] * b, **kw).compile().memory_analysis()
        row = {"batch": b, "q": len(q)}
        for f in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "generated_code_size_in_bytes"):
            row[f] = getattr(ma, f, None)
        print(f"memory: {row}", flush=True)
        out.append(row)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--clients", default="")
    ap.add_argument("--memory", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    spec, cell, config, traffic = bench_run.load_cell(args.workload)
    from repro.launch import compile_cache
    compile_cache.place_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        bench_run.device_info(True, cell["chips"])
    except bench_run.NoAccelerator as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 1
    from lib import session
    t = time.monotonic()
    sess = session.build(config, traffic, args.seed)
    print(f"setup {time.monotonic() - t:.1f} s {sess.phases}", flush=True)
    res = {"workload": args.workload, "seed": args.seed,
           "seconds": args.seconds, "points": []}
    if args.memory:
        res["memory"] = memory(sess, [int(b) for b in args.memory.split(",")])
    for r in [float(x) for x in args.rates.split(",") if x]:
        p = point(sess, args.seconds, rate=r)
        print(f"point: {json.dumps(p)}", flush=True)
        res["points"].append(p)
        if p["unanswered_at_close"] > max(5, r):    # past the knee: stop
            break
    for c in [int(x) for x in args.clients.split(",") if x]:
        p = point(sess, args.seconds, clients=c)
        print(f"point: {json.dumps(p)}", flush=True)
        res["points"].append(p)
    import jax
    res["device_memory"] = jax.devices()[0].memory_stats()
    print(f"device memory: {res['device_memory']}", flush=True)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(json.dumps(res, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
