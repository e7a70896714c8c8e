"""95th percentile of ``Ticket.service_s`` (dispatch to completion), ms."""
from lib.rundata import percentile


def read(run):
    p = percentile([r.ticket.service_s for r in run.completed], 95)
    return None if p is None else 1e3 * p
