"""The span metrics (``metrics/dispatch_busy_pct.py``,
``idle_in_batch_pct.py``, ``engine_host_p95_ms.py``) and ``lib/spans.py``:
on hand-made spans and op intervals, on the small TPU trace
(``data/trace_small``) with spans placed through its clock offset, and a
whole ``span_run.py`` run on the CPU."""
import json
import time
import types

import numpy as np
import pytest

import run as bench_run
from conftest import DATA, traffic
from lib import rundata, spans as spans_lib, trace
from repro.obs.tracing import Span

WINDOW_S = (0.0, 1e-6)                 # the window: 0 to 1000 ns


def span(name, s, e, sid, parent=0, ref=0):
    return Span(name, s, e, sid, parent, ref, None)


SPANS = [
    span("serve.wait", 0, 100, 1, ref=2),
    span("serve.batch", 100, 400, 2, ref=2),
    span("engine.launch", 110, 150, 3, parent=2, ref=2),
    span("engine.wait", 150, 350, 4, parent=2, ref=2),
    span("serve.wait", 400, 600, 5, ref=6),
    span("serve.batch", 600, 700, 6, ref=6),
    span("engine.wait", 620, 690, 7, parent=6, ref=6),
    span("serve.wait", 700, 1000, 8),
    span("serve.request", 50, 700, 9, ref=10),      # not a dispatch span
]
OPS = {"/device:TPU:0": [(0.0, 120.0, "%a = f32[] fusion(f32[] %x)"),
                         (300.0, 650.0, "%b = f32[] fusion(f32[] %x)")]}


def run_of(spans, trace_view=None):
    return types.SimpleNamespace(spans=spans, t0=WINDOW_S[0],
                                 t_end=WINDOW_S[1], trace=trace_view)


def view(ops, offset_ns, t0=0.0, t1=1000.0):
    return rundata.TraceView(ops=ops, host=[], start_ns=t0, window_end_ns=t1,
                             stop_ns=t1, offset_ns=offset_ns)


def test_dispatch_busy_and_engine_host():
    run = run_of(SPANS)
    # batches 300 + 100 ns of the 1000 ns window
    assert bench_run.reader("dispatch_busy_pct.open")(run) == \
        pytest.approx(40.0)
    # batch minus its engine.wait: 300 - 200 and 100 - 70 ns
    assert spans_lib.engine_host_ms(SPANS, 0, 1000) == \
        pytest.approx([1e-4, 3e-5])
    assert bench_run.reader("engine_host_p95_ms.closed")(run) == \
        pytest.approx(float(np.percentile([1e-4, 3e-5], 95)))
    # a batch that started before the window is not the window's
    assert spans_lib.engine_host_ms(SPANS, 500, 1000) == pytest.approx([3e-5])


def test_idle_in_batch_on_hand_made_ops():
    # device idle in [120, 300) and [650, 1000); batches [100, 400) and
    # [600, 700): idle inside a batch 180 + 50 ns
    read = bench_run.reader("idle_in_batch_pct.open")
    assert read(run_of(SPANS, view(OPS, 0.0))) == pytest.approx(23.0)
    # on the trace's clock the spans sit 50 ns earlier: [50, 350), [550, 650)
    assert read(run_of(SPANS, view(OPS, 50.0))) == pytest.approx(18.0)
    # two planes: the mean of the two
    two = {**OPS, "/device:TPU:1": [(0.0, 1000.0, "%c = f32[] fusion()")]}
    assert read(run_of(SPANS, view(two, 0.0))) == pytest.approx(11.5)


def test_readers_without_spans_read_nothing():
    for name in ("dispatch_busy_pct", "idle_in_batch_pct",
                 "engine_host_p95_ms"):
        assert bench_run.reader(name)(run_of(None, view(OPS, 0.0))) is None
        assert bench_run.reader(name)(run_of([], view(OPS, 0.0))) is None
    # the benchmark's own record of a run, which has no spans field
    plain = rundata.Run(requests=[], t0=0.0, t_end=1.0, setup_s=1.0,
                        batch_hist={}, index_bytes=1, n_tokens=1, block=1,
                        cw_len=np.ones(2), device_kind="cpu",
                        trace=view(OPS, 0.0))
    assert bench_run.reader("idle_in_batch_pct")(plain) is None


def test_gap_cover_gives_each_moment_to_the_innermost_span():
    tv = view(OPS, 0.0)
    got = spans_lib.gap_cover(tv.ops, SPANS, 0.0, 0.0, 1000.0, min_gap_ns=0)
    # gaps [120, 300) and [650, 1000): engine.launch 30, engine.wait
    # 150 + 40, serve.batch 10 (after its engine.wait), serve.wait 300
    # (serve.request is no dispatch-thread span)
    assert got["gaps"] == 2
    assert got["gap_s"] == pytest.approx(530e-9)
    assert got["covered_s"] == pytest.approx(530e-9)
    assert got["longest_uncovered_s"] == 0.0
    assert got["by_span"] == pytest.approx({
        "serve.wait": 300 / 530, "engine.wait": 190 / 530,
        "engine.launch": 30 / 530, "serve.batch": 10 / 530})
    # without the trailing serve.wait, 300 ns of gap are nobody's
    got = spans_lib.gap_cover(tv.ops, SPANS[:7], 0.0, 0.0, 1000.0,
                              min_gap_ns=0)
    assert got["longest_uncovered_s"] == pytest.approx(300e-9)
    assert got["by_span"]["none"] == pytest.approx(300 / 530)
    # gaps of 1 ms and under are left out by default
    assert spans_lib.gap_cover(tv.ops, SPANS, 0.0, 0.0, 1000.0)["gaps"] == 0


def test_longest_dispatch_spans():
    top = spans_lib.longest(SPANS, 0, 1000, n=3)
    assert [(s["name"], s["batch"]) for s in top] == [
        ("serve.batch", 2), ("serve.wait", 0), ("engine.wait", 2)]
    assert top[0]["offset_s"] == pytest.approx(100e-9)
    assert top[0]["duration_s"] == pytest.approx(300e-9)


def test_idle_in_batch_on_the_recorded_trace():
    """Spans placed on ``data/trace_small`` through a clock offset: a batch
    over the whole trace holds all its idle time, one over its first half
    the idle time of that half, one before it none."""
    ops = trace.device_ops(trace.load(DATA / "trace_small" /
                                      "trace.xplane.pb"))
    evs = ops["/device:TPU:0"]
    t0, t1 = evs[0][0], max(e for _, e, _ in evs)
    mid = (t0 + t1) / 2
    offset = 123_456_789_000.0          # host monotonic ns - trace ns
    tv = view(ops, offset, t0, t1)
    whole = [span("serve.batch", t0 + offset, t1 + offset, 1)]
    half = [span("serve.batch", t0 + offset, mid + offset, 1)]
    before = [span("serve.batch", t0 - 1e6 + offset, t0 + offset, 1)]
    idle = (t1 - t0) - trace.busy_ns(ops, t0, t1)
    assert idle > 0
    assert spans_lib.idle_in_batch_ns(ops, whole, offset, t0, t1) == \
        pytest.approx(idle)
    assert spans_lib.idle_in_batch_ns(ops, half, offset, t0, t1) == \
        pytest.approx((mid - t0) - trace.busy_ns(ops, t0, mid))
    assert spans_lib.idle_in_batch_ns(ops, before, offset, t0, t1) == 0.0
    read = bench_run.reader("idle_in_batch_pct.closed")
    assert read(types.SimpleNamespace(spans=whole, trace=tv)) == \
        pytest.approx(100.0 * idle / (t1 - t0))
    # the device-idle share the benchmark already reads is the same time
    assert read(types.SimpleNamespace(spans=whole, trace=tv)) == \
        pytest.approx(bench_run.reader("device_idle_pct")(
            types.SimpleNamespace(trace=tv)))


@pytest.mark.parametrize("mix,trace_on,spans_on", [
    ("dr_or", True, True), ("drb_or_bm25", False, False)])
def test_span_run_on_the_cpu(tiny_config, mix, trace_on, spans_on):
    import span_run
    from repro.obs import SPANS
    t = traffic(mix)
    t["arrivals"] = ({"loop": "open", "rate_qps": 20.0}
                     if t["arrivals"]["loop"] == "open"
                     else {"loop": "closed", "clients": 4})
    spec = {"end_to_end": [{"name": "setup_s", "unit": "s"}],
            "per_layer": []}
    cell = {"name": f"tiny.{mix}", "config": "tiny", "traffic": mix,
            "chips": 1}
    out = span_run.span_run(cell["name"], 987654321987, 2.0, trace_on,
                            spans_on, spec=spec, cell=cell,
                            config=tiny_config, traffic=t,
                            require_tpu=False, t_start=time.monotonic())
    json.dumps(out)
    assert out["correct"], out["checks"]
    assert not SPANS.enabled
    got = out["spans"]
    if not spans_on:
        assert got["recorded"] == 0 and "dispatch_busy_pct" not in got
        return
    assert got["recorded"] > 0 and got["dropped"] == 0
    assert 0 < got["dispatch_busy_pct"] <= 100
    assert got["engine_host_p95_ms"] > 0
    assert got["longest"] and all(s["name"] in spans_lib.DISPATCH
                                  for s in got["longest"])
    assert "gap_cover" in got and "modules" in got
    assert got["idle_in_batch_pct"] == 0.0     # no TPU plane on the CPU
