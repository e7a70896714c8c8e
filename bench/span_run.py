"""One run of one cell with the program's span recorder on, and the span
metrics read from it.

    python bench/span_run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--spans 0|1] [--out chiprun_out/spans/<name>.json]

The run is ``run.run``'s (the same set-up, window, check and result line),
with the program's span recorder (``repro.obs.SPANS``) on from before the
window opens until its answers are in (``--spans 0`` leaves it off, to
measure what it costs on the same seeds).  Besides the result line it
reports, under ``"spans"``:

* ``dispatch_busy_pct``, ``engine_host_p95_ms`` and, traced,
  ``idle_in_batch_pct``: what ``bench/metrics/<name>.py`` reads;
* ``longest``: the five longest dispatch-thread spans of the window (name,
  offset in the window, duration, batch id), also printed to stderr;
* traced: ``gap_cover``, the device-idle gaps over 1 ms of the traced
  sub-window and the share of their time each span name held (innermost
  span first), and ``modules``, the programs named on the trace's
  ``XLA Modules`` line.

The last line of standard output is the JSON result with ``"spans"``.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import run as bench_run  # noqa: E402

METRICS = ("dispatch_busy_pct", "engine_host_p95_ms", "idle_in_batch_pct")


def modules(xplane: str) -> dict[str, int]:
    """Program name -> events on the device planes' ``XLA Modules`` line."""
    from lib import trace as trace_lib
    out = collections.Counter()
    for plane in trace_lib.load(xplane).planes:
        if trace_lib.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    out.update(e.name.split("(")[0] for e in line.events)
    return dict(out)


def span_run(workload: str, seed: int, seconds: float, trace: bool,
             spans_on: bool = True, **run_kw) -> dict:
    """``run.run`` (``run_kw`` passed on) with the recorder on around the
    window; the result with ``"spans"`` added."""
    from repro.obs import tracing
    from lib import spans as spans_lib
    seen = {}
    serve, view = bench_run.serve, bench_run.view

    def serve_spans(sess, arrivals, secs, *, trace_dir=None):
        tracing.SPANS.drain()
        tracing.enable(spans_on)
        try:
            win = serve(sess, arrivals, secs, trace_dir=trace_dir)
        finally:
            tracing.enable(False)
        seen["log"] = tracing.SPANS.drain()
        seen["t0"], seen["t_end"] = win.t0, win.t_end
        return win

    def view_modules(xplane, rec, t_end, t_stop):
        seen["modules"] = modules(xplane)
        seen["trace"] = view(xplane, rec, t_end, t_stop)
        return seen["trace"]

    run_kw.setdefault("t_start", T_START)
    bench_run.serve, bench_run.view = serve_spans, view_modules
    try:
        result = bench_run.run(workload, seed, seconds, trace, **run_kw)
    finally:
        bench_run.serve, bench_run.view = serve, view
    log, t0, t1 = seen["log"], seen["t0"] * 1e9, seen["t_end"] * 1e9
    data = types.SimpleNamespace(spans=log.spans, t0=seen["t0"],
                                 t_end=seen["t_end"],
                                 trace=seen.get("trace"))
    out = {"recorded": len(log.spans), "dropped": log.dropped,
           "longest": spans_lib.longest(log.spans, t0, t1)}
    for name in METRICS:
        v = bench_run.reader(name)(data)
        if v is not None:
            out[name] = v
    if data.trace is not None:
        tv = data.trace
        out["gap_cover"] = spans_lib.gap_cover(
            tv.ops, log.spans, tv.offset_ns, tv.start_ns, tv.window_end_ns)
        out["modules"] = seen["modules"]
    result["spans"] = out
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--spans", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    try:
        result = span_run(args.workload, args.seed, args.seconds,
                          bool(args.trace), bool(args.spans))
    except bench_run.NoAccelerator as e:
        print(f"span_run: {e}", file=sys.stderr)
        return 1
    for sp in result["spans"]["longest"]:
        print(f"longest span: {sp['name']} at {sp['offset_s']:.3f} s, "
              f"{1e3 * sp['duration_s']:.3f} ms, batch {sp['batch']}",
              file=sys.stderr)
    sys.stderr.flush()
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(json.dumps(result, indent=1))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
