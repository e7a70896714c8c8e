"""95th percentile of ``Ticket.queue_wait_s`` (submit to dispatch), ms."""
from lib.rundata import percentile


def read(run):
    p = percentile([r.ticket.queue_wait_s for r in run.completed], 95)
    return None if p is None else 1e3 * p
