"""Run one cell of the benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration (``bench/configs/<config>.json``) and its
traffic mix (``bench/traffic/<traffic>.json``) are found by name from
``BENCHMARK.json``; each metric is read by ``bench/metrics/<metric>.py``
(``<base>.py`` for a metric split by the kind of cell, ``<base>.<kind>``).
The run builds the collection and the engine from ``--seed``, warms every
executor the mix can reach, serves the mix through ``SearchServer`` for
``--seconds``, and checks a sample of the answers against the NumPy
reference (``lib/reference.py``).  With ``--trace 1`` it traces the last
seconds of the window and reports the per-layer metrics instead of the
end-to-end ones.

Standard output ends with one JSON line: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), then
``checks``, every compared number beside its limit; standard error ends
with the same numbers.  Without a TPU, or with fewer chips than the cell
asks for, it prints no result and exits 1.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

TRACE_SECONDS = 2.0      # traced sub-window at the end of the measured one
WAIT_AFTER_S = 60.0      # answers may come this long after the close
CHECK_SEED = 2           # stream of the seed the check sample is drawn from


class NoAccelerator(RuntimeError):
    pass


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark spec, cell, configuration, traffic) of cell ``name``."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = json.loads((BENCH / "configs" / f"{cell['config']}.json")
                        .read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    return spec, cell, config, traffic


def metric_names(spec: dict, cell: str, kind: str) -> list[dict]:
    return [m for m in spec[kind] if cell in m.get("workloads", [cell])]


def reader(name: str):
    """``read`` of ``bench/metrics/<name>.py``; a metric split by the kind of
    cell (``<base>.<kind>``, such as ``mean_batch.closed``) without a file
    of its own is read by ``<base>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists() and "." in name:
        path = BENCH / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
    mod_spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def device_info(require_tpu: bool, chips: int):
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < chips):
        raise NoAccelerator(
            f"the cell needs {chips} TPU chip(s); JAX found {len(devices)} "
            f"{dev.platform} device(s) ({dev.device_kind})")
    print(f"device: platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devices)}", flush=True)
    return dev, devices


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        spec=None, cell=None, config=None, traffic=None,
        require_tpu: bool = True, t_start: float | None = None,
        controls: dict | None = None) -> dict:
    """One run of one cell; returns the result object.  ``controls``
    (name -> dtype) adds ``"controls"``: every number ``check.py`` compares,
    for the program (``"program"``) and for the reference computed in each
    such precision, on the same compared requests."""
    import numpy as np
    if spec is None:
        spec, cell, config, traffic = load_cell(cell_name)
    t_start = T_START if t_start is None else t_start
    from repro.launch import compile_cache
    cache_dir = compile_cache.place_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev, devices = device_info(require_tpu, cell["chips"])

    from lib import check, rundata, session
    counter = session.CompileCounter()
    sess = session.build(config, traffic, seed)
    setup_s = time.monotonic() - t_start
    programs0, traces0 = counter.programs, counter.traces
    print(f"setup: {setup_s:.3f} s; phases "
          + ", ".join(f"{k} {v:.3f} s" for k, v in sess.phases.items())
          + f"; executors warmed {sess.executors}; programs compiled "
          f"{counter.fresh} (loaded from the cache {counter.hits}); "
          f"compile cache {cache_dir}", flush=True)

    tmp = tempfile.TemporaryDirectory(prefix="bench_trace_") if trace else None
    win = serve(sess, traffic["arrivals"], seconds,
                trace_dir=tmp.name if trace else None)
    reqs = win.requests
    in_window = counter.programs - programs0
    traced_in_window = counter.traces - traces0
    stats = dev.memory_stats() or {}
    # the TPU runtime keeps the programs' scratch apart from the buffers
    # (``bytes_reserved``): the chip's peak holds both
    mem = (stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0)
           if "peak_bytes_in_use" in stats else None)

    late = np.asarray([r.t_sent - r.due for r in reqs if r.due is not None])
    failures = {f: sum(r.failure == f for r in reqs)
                for f in ("shed", "error", "timeout")}
    print(f"window: {len(reqs)} requests in {seconds} s "
          f"({traffic['arrivals']}); failed {failures}; programs compiled "
          f"in the window {in_window}, functions traced {traced_in_window}; "
          f"batches {win.batch_hist}; full collections of the garbage "
          f"collector {len(win.gc_pauses)}, longest "
          f"{1e3 * max(win.gc_pauses, default=0.0):.3f} ms", flush=True)
    print(f"device memory: {stats}", flush=True)
    if len(late):
        print(f"generator lateness: mean {1e3 * late.mean():.3f} ms, "
              f"p99 {1e3 * np.percentile(late, 99):.3f} ms, "
              f"max {1e3 * late.max():.3f} ms", flush=True)

    data = rundata.Run(
        requests=reqs, t0=win.t0, t_end=win.t_end, setup_s=setup_s,
        batch_hist=win.batch_hist,
        index_bytes=sess.engine.space_report()["total"],
        n_tokens=sess.coll.n_tokens, block=config["block"],
        cw_len=sess.cw_len, device_kind=dev.device_kind)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out = {}
    if trace:
        data.trace = view(win.xplane, win.recorder, win.t_end, win.t_stop)
        tmp.cleanup()
        device["busy_s"] = data.trace.busy_s()
        device["window_s"] = data.trace.window_s()
        out["breakdown"] = breakdown(data.trace)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metric_names(spec, cell["name"], kind):
        v = reader(m["name"])(data)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # the check: the program's state goes first, the reference is host NumPy
    del sess.engine, sess.server, win
    answers = sample(data.completed, traffic["check_sample"], seed)
    t = time.monotonic()
    compared = [(r.query, r.row.docs[:r.row.n_found],
                 r.row.scores[:r.row.n_found]) for r in answers]
    nums = check.numbers(sess.coll, compared, traffic["profile"])
    correct, rows = check.verdict(nums, traffic["limits"])
    correct = correct and in_window == 0
    print(f"check: {len(answers)} answers against the reference in "
          f"{time.monotonic() - t:.3f} s", flush=True)
    rows.append(["compiles_in_window", in_window, 0])
    if controls:
        out["controls"] = {"program": nums}
    for name, dtype in (controls or {}).items():
        out["controls"][name] = check.numbers(
            sess.coll, compared, traffic["profile"], control_dtype=dtype)
    result = {"correct": bool(correct), "attempted": len(reqs),
              "failed": sum(failures.values()), "metrics": metrics,
              "device": device, **out,
              "checks": {n: {"value": v, "limit": lim} for n, v, lim in rows}}
    return result


@dataclasses.dataclass
class Window:
    requests: list            # driver.Request, in the order sent
    t0: float                 # the window opened (monotonic s)
    t_end: float              # it closed
    t_stop: float             # the last answer came, or the wait ran out
    batch_hist: dict          # batch size -> dispatches, in the window
    gc_pauses: list           # seconds of each full garbage collection
    xplane: str | None = None
    recorder: object = None


def serve(sess, arrivals: dict, seconds: float, *,
          trace_dir: str | None = None) -> Window:
    """The measured window: the session's server under ``arrivals`` for
    ``seconds``, every answer waited for up to ``WAIT_AFTER_S`` past the
    close; with ``trace_dir``, a device trace of its last
    ``TRACE_SECONDS``."""
    from lib import driver, traffic as traffic_lib
    from lib import trace as trace_lib
    server = sess.server
    hist0 = dict(server.stats["batch_hist"])
    pauses, started = [], []

    def on_gc(phase, info):
        if info["generation"] == 2:
            if phase == "start":
                started.append(time.monotonic())
            elif started:
                pauses.append(time.monotonic() - started.pop())

    if arrivals["loop"] == "open":
        offsets = traffic_lib.open_offsets(arrivals["rate_qps"], seconds)
        queries = iter(sess.queries.window(len(offsets)))
    gc.callbacks.append(on_gc)
    rec = xplane = None
    with server:
        t0 = time.monotonic() + 0.05
        t_end = t0 + seconds
        if trace_dir is not None:
            rec = trace_lib.Recorder(trace_dir)
            timer = threading.Timer(
                max(0.0, t_end - TRACE_SECONDS - time.monotonic()), rec.start)
            timer.start()
        if arrivals["loop"] == "open":
            reqs = driver.open_loop(server, sess.profile, queries, offsets,
                                    t0)
            time.sleep(max(0.0, t_end - time.monotonic()))
        else:
            reqs = driver.closed_loop(server, sess.profile, sess.queries,
                                      arrivals["clients"], t_end,
                                      WAIT_AFTER_S)
        driver.finish(reqs, t_end + WAIT_AFTER_S)
        t_stop = time.monotonic()
        if trace_dir is not None:
            timer.join()
            xplane = rec.stop()
    gc.callbacks.remove(on_gc)
    hist = {b: c - hist0.get(b, 0)
            for b, c in server.stats["batch_hist"].items()
            if c - hist0.get(b, 0)}
    return Window(requests=reqs, t0=t0, t_end=t_end, t_stop=t_stop,
                  batch_hist=hist, gc_pauses=pauses, xplane=xplane,
                  recorder=rec)


def sample(completed: list, n: int, seed: int) -> list:
    """Up to ``n`` answered requests drawn from the seed, always with the one
    that popped the most segments."""
    import numpy as np
    if len(completed) <= n:
        return completed
    heavy = max(range(len(completed)),
                key=lambda i: completed[i].row.pops or 0)
    rng = np.random.default_rng([seed, CHECK_SEED])
    pick = set(rng.choice(len(completed), n - 1, replace=False).tolist())
    pick.add(heavy)
    return [completed[i] for i in sorted(pick)][:n]


def view(xplane: str, rec, t_end: float, t_stop: float):
    """The trace reduced to device op intervals and host events."""
    from lib import rundata, trace as trace_lib
    pd = trace_lib.load(xplane)
    ops = trace_lib.device_ops(pd)
    host = trace_lib.host_events(pd)
    to_ns = lambda t: t * 1e9 - rec.offset_ns   # noqa: E731
    return rundata.TraceView(ops=ops, host=host, start_ns=to_ns(
        rec.t_host_ns / 1e9), window_end_ns=to_ns(t_end),
        stop_ns=to_ns(t_stop), offset_ns=rec.offset_ns)


def breakdown(tv) -> dict:
    from lib import rundata, trace as trace_lib
    leaf = {p: [e for e in evs
                if rundata.opcode(e[2]) not in rundata.CONTAINER_OPS]
            for p, evs in tv.ops.items()}
    top = trace_lib.top_ops(
        {p: [(s, e, rundata.op_label(n)) for s, e, n in evs]
         for p, evs in leaf.items()}, tv.start_ns, tv.window_end_ns)
    gaps = trace_lib.idle_gaps(tv.ops, tv.host, tv.start_ns,
                               tv.window_end_ns)
    return {"device_ops": top, "idle_gaps": gaps}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    except ImportError as e:
        print(f"bench: the system under test is not importable from "
              f"{ROOT / 'src'}: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
