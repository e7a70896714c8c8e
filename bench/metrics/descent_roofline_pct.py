"""The descent kernel's share of its HBM roofline, in %: the least time the
chip could take to read the bytes the traced requests' descents need
(``lib/roofline.py``) at the chip's HBM peak, over the device time of the
kernel's events in the trace from the first of those requests on."""
from lib import peaks, roofline, trace


def read(run):
    reqs = run.traced()
    if not reqs:
        return None
    t0 = run.trace.to_ns(min(r.ticket.t_dispatch for r in reqs))
    kernel_ns = trace.kernel_ns(run.trace.ops, roofline.DESCENT_OP, t0,
                                run.trace.stop_ns)
    if kernel_ns <= 0:
        return None
    n = sum(roofline.probes(r.row.pops, run.cw_len[r.query].sum())
            for r in reqs)
    least_s = (n * roofline.probe_bytes(run.block)
               / peaks.peak(run.device_kind, "hbm_bytes_per_s"))
    return 100.0 * least_s / (kernel_ns / 1e9)
