"""Load loops over a ``SearchServer``: an open loop timed from each request's
due time, and a closed loop of waiting clients.

Adapted from ``serve/loadgen.py`` (``open_loop``, ``closed_loop``), with two
changes: an open-loop request's latency runs from when it was due, not from
when the generator got round to submitting it, so a stall of the generator
or the server counts against every request it delays; and the generator's
lateness (submit minus due) is kept for every request.
"""
from __future__ import annotations

import dataclasses
import threading
import time

from repro.serve.server import RequestTimeout, ShedError


@dataclasses.dataclass
class Request:
    query: list[int]
    t_sent: float                 # when submit was called (monotonic)
    due: float | None = None      # open loop: when it was due (monotonic)
    ticket: object = None         # serve.server.Ticket; None when shed
    failure: str | None = None    # "shed", "error" or "timeout"

    @property
    def start(self) -> float:
        return self.t_sent if self.due is None else self.due

    @property
    def latency_s(self) -> float | None:
        if self.failure is not None:
            return None
        return self.ticket.t_done - self.start

    @property
    def row(self):
        return None if self.failure is not None else self.ticket.result(0)


def _submit(server, profile, query, due=None) -> Request:
    req = Request(query=query, t_sent=time.monotonic(), due=due)
    try:
        req.ticket = server.submit(query, profile)
    except ShedError:
        req.failure = "shed"
    return req


def open_loop(server, profile, queries, offsets, t0: float) -> list[Request]:
    """Submit one query of the ``queries`` iterator at each ``t0 + offset``;
    never waits for a reply."""
    out = []
    for off in offsets:
        due = t0 + float(off)
        wait = due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        out.append(_submit(server, profile, next(queries), due))
    return out


def closed_loop(server, profile, queries, clients: int,
                t_end: float, wait_s: float) -> list[Request]:
    """``clients`` threads, each sending its next query when its last one
    returned, until ``t_end``; requests sent before ``t_end`` are waited for
    up to ``wait_s`` past it."""
    out, lock = [], threading.Lock()

    def client():
        while time.monotonic() < t_end:
            req = _submit(server, profile, next(queries))
            with lock:
                out.append(req)
            if req.ticket is not None:
                try:
                    req.ticket.result(max(0.0, t_end + wait_s
                                          - time.monotonic()))
                except Exception:        # settled by finish()
                    return

    threads = [threading.Thread(target=client, name=f"client-{i}")
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out


def finish(requests: list[Request], deadline: float) -> None:
    """Wait for every admitted request until ``deadline``; then mark the ones
    that errored, and cancel and mark the ones still out."""
    for req in requests:
        if req.ticket is None:
            continue
        try:
            req.ticket.result(max(0.0, deadline - time.monotonic()))
        except RequestTimeout:
            pass
        except TimeoutError:
            req.ticket.cancel(RequestTimeout("not answered by the deadline"))
        except Exception:
            pass
        if req.failure is None and req.ticket.error is not None:
            req.failure = ("timeout" if isinstance(req.ticket.error,
                                                   RequestTimeout)
                           else "error")
