"""Share of the traced sub-window in which no op ran on the device while
the dispatch thread was inside a ``serve.batch`` span, in %: the host
holding the chip idle.  The rest of ``device_idle_pct`` is idle for want of
work.  Reads the program's spans (``run.spans``) and the device trace; None
without either."""
from lib import spans as spans_lib


def read(run):
    spans = getattr(run, "spans", None)
    tv = run.trace
    if not spans or tv is None or tv.window_end_ns <= tv.start_ns:
        return None
    idle = spans_lib.idle_in_batch_ns(tv.ops, spans, tv.offset_ns,
                                      tv.start_ns, tv.window_end_ns)
    return 100.0 * idle / (tv.window_end_ns - tv.start_ns)
