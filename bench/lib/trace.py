"""Profiler traces: recording one window, and reducing it to device busy time,
idle share and kernel time.

A trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Its event times
are nanoseconds from the start of the trace.  To put host times (the
requests' ``time.monotonic``) on the same clock, :class:`Recorder` opens a
``jax.profiler.TraceAnnotation`` named ``CLOCK_ANNOTATION`` right after the
trace starts and notes the host's monotonic clock as it does; the
annotation's start in the trace then gives the offset between the two.
"""
from __future__ import annotations

import collections
import pathlib
import re
import time

CLOCK_ANNOTATION = "bench.clock"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


class Recorder:
    """Starts and stops one trace into ``log_dir``; ``offset_ns`` maps host
    monotonic nanoseconds to trace nanoseconds (trace = host - offset)."""

    def __init__(self, log_dir):
        self.log_dir = str(log_dir)
        self.t_host_ns = None
        self.offset_ns = None

    def start(self) -> None:
        import jax
        jax.profiler.start_trace(self.log_dir)
        self.t_host_ns = time.monotonic_ns()
        with jax.profiler.TraceAnnotation(CLOCK_ANNOTATION):
            pass

    def stop(self) -> str:
        """Stops the trace; returns the ``.xplane.pb`` path."""
        import jax
        jax.profiler.stop_trace()
        path = find_xplane(self.log_dir)
        self.offset_ns = self.t_host_ns - clock_mark_ns(load(path))
        return path


def find_xplane(log_dir) -> str:
    found = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return str(found[-1])


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def clock_mark_ns(pd) -> float:
    """Trace time at which the clock annotation started."""
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == CLOCK_ANNOTATION:
                    return float(ev.start_ns)
    raise ValueError(f"trace holds no {CLOCK_ANNOTATION!r} annotation")


def device_ops(pd) -> dict[str, list[tuple[float, float, str]]]:
    """``{plane name: [(start_ns, end_ns, op name), ...]}`` over the ops line
    of every TPU device plane, sorted by start."""
    out = {}
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        evs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            evs += [(float(e.start_ns), float(e.start_ns + e.duration_ns),
                     e.name) for e in line.events]
        out[plane.name] = sorted(evs)
    return out


def union_ns(intervals, t0: float, t1: float) -> float:
    """Length of the union of ``(start, end, ...)`` intervals clipped to
    ``[t0, t1)``."""
    total = 0.0
    cur_s = cur_e = None
    for iv in sorted(intervals):
        s, e = max(iv[0], t0), min(iv[1], t1)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_ns(ops: dict, t0: float, t1: float) -> float:
    """Device busy time in ``[t0, t1)``, averaged over the device planes."""
    if not ops:
        return 0.0
    return sum(union_ns(evs, t0, t1) for evs in ops.values()) / len(ops)


def kernel_ns(ops: dict, pattern: str, t0: float, t1: float) -> float:
    """Summed device duration of the op events whose name matches
    ``pattern`` (a regular expression) and that start in ``[t0, t1)``,
    averaged over the device planes."""
    if not ops:
        return 0.0
    rx = re.compile(pattern)
    return sum(e - s for evs in ops.values() for s, e, name in evs
               if t0 <= s < t1 and rx.search(name)) / len(ops)


def top_ops(ops: dict, t0: float, t1: float, n: int = 10):
    """The ``n`` op names with most device time in ``[t0, t1)``, as
    ``[[name, seconds], ...]`` averaged over the device planes."""
    tot = collections.Counter()
    for evs in ops.values():
        for s, e, name in evs:
            if t0 <= s < t1:
                tot[name] += (e - s) / 1e9
    k = max(len(ops), 1)
    return [[name, sec / k] for name, sec in tot.most_common(n)]


def host_events(pd) -> list[tuple[float, float, str, str]]:
    """``[(start_ns, end_ns, name, line name), ...]`` of every host plane."""
    out = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name) or not plane.name.startswith(
                "/host"):
            continue
        for line in plane.lines:
            out += [(float(e.start_ns), float(e.start_ns + e.duration_ns),
                     e.name, line.name) for e in line.events]
    return out


def host_label(host, t: float, n_lines: int = 3) -> str:
    """What the host was doing at trace time ``t``: the innermost (shortest)
    event covering ``t`` on each host line, for up to ``n_lines`` lines."""
    inner = {}
    for s, e, name, line in host:
        if s <= t < e and (line not in inner or e - s < inner[line][0]):
            inner[line] = (e - s, name)
    if not inner:
        return "host: no traced event"
    parts = [f"{line}: {name}" for line, (_, name) in
             sorted(inner.items(), key=lambda kv: kv[1][0])[:n_lines]]
    return "; ".join(parts)[:240]


def idle_gaps(ops: dict, host, t0: float, t1: float, n: int = 10):
    """The ``n`` longest gaps in ``[t0, t1)`` in which no op ran on the
    first device plane, as ``[[label, seconds], ...]``, each labelled by
    what the host was doing in its middle (:func:`host_label`)."""
    if not ops:
        return []
    evs = next(iter(ops.values()))
    gaps = []
    last_end = t0
    for s, e, _ in evs:
        if e <= t0 or s >= t1:
            continue
        if s > last_end:
            gaps.append((last_end, s))
        last_end = max(last_end, e)
    if t1 > last_end:
        gaps.append((last_end, t1))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[host_label(host, (a + b) / 2), (b - a) / 1e9]
            for a, b in gaps[:n]]


def describe(path) -> str:
    """Planes, lines and the busiest event names of a trace, as text."""
    pd = load(path)
    out = []
    for plane in pd.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            tot = collections.Counter()
            cnt = collections.Counter()
            for e in evs:
                tot[e.name] += e.duration_ns
                cnt[e.name] += 1
            span = ((min(e.start_ns for e in evs),
                     max(e.start_ns + e.duration_ns for e in evs))
                    if evs else None)
            out.append(f"  line {line.name!r}: {len(evs)} events, span {span}")
            for name, ns in tot.most_common(12):
                out.append(f"    {cnt[name]:6d} x {ns / 1e3:12.1f} us  {name}")
    return "\n".join(out)
