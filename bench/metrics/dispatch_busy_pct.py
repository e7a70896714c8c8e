"""Share of the window the dispatch thread spent inside ``serve.batch``
spans (their union over the window), in %: how near the thread is to
saturation.  Reads the program's spans (``run.spans``); None without
them."""
from lib import spans as spans_lib


def read(run):
    spans = getattr(run, "spans", None)
    if not spans:
        return None
    return spans_lib.dispatch_busy_pct(spans, run.t0 * 1e9, run.t_end * 1e9)
