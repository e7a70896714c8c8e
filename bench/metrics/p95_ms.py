"""95th percentile latency over every request of the window, in ms.

Open loop: from the request's due time; closed loop: from its send.  A
request that failed (shed, errored, not answered within a minute of the
window's close) ranks as slower than every answered one; if that puts it in
the percentile, the reading is the minute the harness waited."""
import numpy as np


def read(run):
    lat = [r.latency_s if r.failure is None else np.inf for r in run.requests]
    if not lat:
        return None
    p = float(np.percentile(np.asarray(lat), 95))
    return 1e3 * (p if np.isfinite(p) else run.seconds + 60.0)
