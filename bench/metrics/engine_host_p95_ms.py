"""95th percentile over the window's batches of ``serve.batch`` minus its
``engine.wait`` span, in ms: a batch's host time.  Reads the program's
spans (``run.spans``); None without them."""
from lib import spans as spans_lib
from lib.rundata import percentile


def read(run):
    spans = getattr(run, "spans", None)
    if not spans:
        return None
    return percentile(spans_lib.engine_host_ms(spans, run.t0 * 1e9,
                                               run.t_end * 1e9), 95)
