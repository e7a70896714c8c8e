"""Device time of the descent kernel's events over the device's busy time,
in %, from the first request dispatched in the trace to its end."""
from lib import roofline, trace


def read(run):
    reqs = run.traced()
    if not reqs:
        return None
    t0 = run.trace.to_ns(min(r.ticket.t_dispatch for r in reqs))
    busy = trace.busy_ns(run.trace.ops, t0, run.trace.stop_ns)
    kernel = trace.kernel_ns(run.trace.ops, roofline.DESCENT_OP, t0,
                             run.trace.stop_ns)
    return 100.0 * kernel / busy if busy > 0 and kernel > 0 else None
