"""Open-loop latency runs from the due time; the generator's lateness is
kept for every request."""
import threading
import time

from lib import driver


class Ticket:
    def __init__(self, delay):
        self.t_submit = time.monotonic()
        self.t_dispatch = self.t_submit
        self.t_done = None
        self.error = None
        self._ev = threading.Event()
        threading.Timer(delay, self._complete).start()

    def _complete(self):
        self.t_done = time.monotonic()
        self._ev.set()

    def done(self):
        return self._ev.is_set()

    def result(self, timeout=None):
        if not self._ev.wait(timeout):
            raise TimeoutError("in flight")
        return "row"

    def cancel(self, err):
        self.error = err


class StallingServer:
    """Answers after 10 ms; its first submit blocks the generator 300 ms."""

    def __init__(self):
        self.n = 0

    def submit(self, query, profile):
        self.n += 1
        if self.n == 1:
            time.sleep(0.3)
        return Ticket(0.01)


def test_latency_from_due_time_counts_the_stall():
    offsets = [0.0, 0.05, 0.10, 0.15, 0.5]
    t0 = time.monotonic() + 0.01
    reqs = driver.open_loop(StallingServer(), None, iter([[1]] * 5), offsets,
                            t0)
    driver.finish(reqs, time.monotonic() + 5)
    late = [r.t_sent - r.due for r in reqs]
    assert all(x >= -1e-3 for x in late)
    # requests 2-4 were due during the stall and sent after it
    for r, off in zip(reqs[1:4], offsets[1:4]):
        assert r.due == t0 + off
        assert r.t_sent - r.due > 0.3 - off - 0.02
        assert abs(r.latency_s - (r.ticket.t_done - r.due)) < 1e-9
        assert r.latency_s > (r.ticket.t_done - r.t_sent) + 0.1
    # the last one was due after the stall: on time, ~10 ms
    assert reqs[4].t_sent - reqs[4].due < 0.02
    assert reqs[4].latency_s < 0.05


def test_closed_loop_times_from_send():
    reqs = driver.closed_loop(StallingServer(), None, iter([[1]] * 1000), 2,
                              time.monotonic() + 0.5, 5.0)
    driver.finish(reqs, time.monotonic() + 5)
    assert reqs and all(r.due is None for r in reqs)
    assert all(abs(r.latency_s - (r.ticket.t_done - r.t_sent)) < 1e-9
               for r in reqs)
