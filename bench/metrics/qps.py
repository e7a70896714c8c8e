"""Requests answered within the window, per second of the window."""


def read(run):
    done = [r for r in run.completed if r.ticket.t_done <= run.t_end]
    return len(done) / run.seconds
