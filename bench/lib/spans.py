"""The program's spans (``repro.obs.tracing``), as the span metrics read them.

A span is ``(name, start_ns, end_ns, span_id, parent_id, ref_id, attrs)`` on
the host's monotonic clock; ``trace.Recorder.offset_ns`` puts it on a device
trace's clock (trace ns = span ns - offset).  The dispatch thread records
``serve.wait`` and ``serve.batch``, and inside each batch ``engine.prepare``,
``engine.launch``, ``engine.wait``, ``serve.fetch`` and ``serve.complete``
(``engine.record`` too when the program's metrics registry is on).
"""
from __future__ import annotations

from lib import trace as trace_lib

BATCH = "serve.batch"
WAIT = "serve.wait"
DEVICE_WAIT = "engine.wait"
DISPATCH = (WAIT, BATCH, "engine.prepare", "engine.launch", "engine.record",
            DEVICE_WAIT, "serve.fetch", "serve.complete")
_DEPTH = {WAIT: 0, BATCH: 0}          # the batch's children are depth 1


def merged(intervals) -> list[tuple[float, float]]:
    """The union of ``(start, end, ...)`` intervals as sorted disjoint
    ``(start, end)`` pairs."""
    out = []
    for iv in sorted(intervals):
        s, e = iv[0], iv[1]
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def overlap_ns(a, b) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_intervals(evs, t0: float, t1: float) -> list[tuple[float, float]]:
    """The gaps in ``[t0, t1)`` in which none of the op events ``evs`` ran."""
    gaps, last = [], t0
    for s, e in merged(evs):
        if e <= t0 or s >= t1:
            continue
        if s > last:
            gaps.append((last, s))
        last = max(last, e)
    if t1 > last:
        gaps.append((last, t1))
    return gaps


def dispatch_busy_pct(spans, t0_ns: float, t1_ns: float) -> float | None:
    """Union of ``serve.batch`` spans clipped to ``[t0_ns, t1_ns)``, in %
    of it: how near the dispatch thread is to saturation."""
    if t1_ns <= t0_ns:
        return None
    busy = trace_lib.union_ns([(sp.start_ns, sp.end_ns) for sp in spans
                               if sp.name == BATCH], t0_ns, t1_ns)
    return 100.0 * busy / (t1_ns - t0_ns)


def engine_host_ms(spans, t0_ns: float, t1_ns: float) -> list[float]:
    """Per batch started in ``[t0_ns, t1_ns)``, in start order:
    ``serve.batch`` minus its ``engine.wait``, in ms: the batch's host
    time."""
    wait = {}
    for sp in spans:
        if sp.name == DEVICE_WAIT:
            wait[sp.parent_id] = wait.get(sp.parent_id, 0) + sp.duration_ns
    return [(sp.duration_ns - wait.get(sp.span_id, 0)) / 1e6
            for sp in sorted(spans, key=lambda sp: sp.start_ns)
            if sp.name == BATCH and t0_ns <= sp.start_ns < t1_ns]


def idle_in_batch_ns(ops: dict, spans, offset_ns: float, t0: float,
                     t1: float) -> float:
    """Trace time in ``[t0, t1)`` in which no op ran on the device while the
    dispatch thread was inside a ``serve.batch`` span (span times moved onto
    the trace's clock by ``offset_ns``), averaged over the device planes."""
    if not ops:
        return 0.0
    inside = merged([(sp.start_ns - offset_ns, sp.end_ns - offset_ns)
                     for sp in spans if sp.name == BATCH])
    return sum(overlap_ns(idle_intervals(evs, t0, t1), inside)
               for evs in ops.values()) / len(ops)


def gap_cover(ops: dict, spans, offset_ns: float, t0: float, t1: float,
              min_gap_ns: float = 1e6) -> dict:
    """The device-idle gaps longer than ``min_gap_ns`` in ``[t0, t1)`` on the
    first device plane, and which dispatch-thread span held each moment of
    them: ``{"gaps", "gap_s", "covered_s", "longest_uncovered_s", "by_span":
    {name: share of gap time}}``, each moment given to the innermost span
    covering it (a batch's child before the batch), ``"none"`` where no
    dispatch-thread span covers it."""
    gaps = [g for g in idle_intervals(next(iter(ops.values())), t0, t1)
            if g[1] - g[0] > min_gap_ns] if ops else []
    own = [(sp.start_ns - offset_ns, sp.end_ns - offset_ns, sp.name,
            _DEPTH.get(sp.name, 1)) for sp in spans if sp.name in DISPATCH]
    by, total, uncovered_max = {}, 0.0, 0.0
    for a, b in gaps:
        total += b - a
        near = [x for x in own if x[0] < b and x[1] > a]
        cuts = sorted({a, b} | {t for x in near for t in x[:2] if a < t < b})
        run_uncovered = 0.0
        for s, e in zip(cuts, cuts[1:]):
            mid = (s + e) / 2
            cover = [x for x in near if x[0] <= mid < x[1]]
            name = max(cover, key=lambda x: (x[3], x[0]))[2] if cover \
                else "none"
            by[name] = by.get(name, 0.0) + (e - s)
            run_uncovered = run_uncovered + (e - s) if name == "none" else 0.0
            uncovered_max = max(uncovered_max, run_uncovered)
    return {"gaps": len(gaps), "gap_s": total / 1e9,
            "covered_s": (total - by.get("none", 0.0)) / 1e9,
            "longest_uncovered_s": uncovered_max / 1e9,
            "by_span": {k: v / total for k, v in
                        sorted(by.items(), key=lambda kv: -kv[1])}
            if total else {}}


def longest(spans, t0_ns: float, t1_ns: float, n: int = 5) -> list[dict]:
    """The ``n`` longest dispatch-thread spans that started in the window:
    name, offset from the window's start (s), duration (s), batch id."""
    own = sorted((sp for sp in spans if sp.name in DISPATCH
                  and t0_ns <= sp.start_ns < t1_ns),
                 key=lambda sp: -sp.duration_ns)[:n]
    return [{"name": sp.name, "offset_s": (sp.start_ns - t0_ns) / 1e9,
             "duration_s": sp.duration_ns / 1e9, "batch": sp.ref_id}
            for sp in own]
