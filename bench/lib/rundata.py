"""What one run measured, as the metric readers (``bench/metrics``) see it."""
from __future__ import annotations

import dataclasses
import re

import numpy as np

from lib import trace as trace_lib

CONTAINER_OPS = ("while", "conditional", "call")
_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")


def op_label(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``%fusion.12 fusion``."""
    head, _, rest = name.partition(" = ")
    m = _OPCODE.search(rest)
    return f"{head} {m.group(1)}" if m else head


def opcode(name: str) -> str:
    m = _OPCODE.search(name.partition(" = ")[2])
    return m.group(1) if m else ""


@dataclasses.dataclass
class TraceView:
    """One traced sub-window, on the trace's clock (nanoseconds)."""
    ops: dict                 # device plane -> [(start, end, name), ...]
    host: list                # [(start, end, name, line)] host events
    start_ns: float           # the trace started
    window_end_ns: float      # the measured window closed
    stop_ns: float            # the last traced request completed
    offset_ns: float          # host monotonic ns - trace ns

    def to_ns(self, t_monotonic_s: float) -> float:
        return t_monotonic_s * 1e9 - self.offset_ns

    @property
    def start_s(self) -> float:
        """Trace start on the host's monotonic clock (seconds)."""
        return (self.start_ns + self.offset_ns) / 1e9

    def busy_s(self) -> float:
        return trace_lib.busy_ns(self.ops, self.start_ns,
                                 self.window_end_ns) / 1e9

    def window_s(self) -> float:
        return (self.window_end_ns - self.start_ns) / 1e9


@dataclasses.dataclass
class Run:
    requests: list            # driver.Request, the window's
    t0: float                 # window start (monotonic s)
    t_end: float              # window end (monotonic s)
    setup_s: float
    batch_hist: dict          # batch size -> dispatches, in the window
    index_bytes: int          # engine.space_report()["total"]
    n_tokens: int             # words in the collection
    block: int
    cw_len: np.ndarray        # codeword bytes of every word id
    device_kind: str
    trace: TraceView | None = None

    @property
    def seconds(self) -> float:
        return self.t_end - self.t0

    @property
    def completed(self) -> list:
        return [r for r in self.requests if r.failure is None]

    def traced(self) -> list:
        """Completed requests dispatched after the trace started."""
        t = self.trace.start_s
        return [r for r in self.completed if r.ticket.t_dispatch >= t]


def percentile(values, q: float) -> float | None:
    v = np.asarray([x for x in values if x is not None], dtype=np.float64)
    return float(np.percentile(v, q)) if len(v) else None
