"""The load loop.  ``ClosedLoop`` has ``warm()``, run in set-up, and
``run()``, the measured window, which records every request with its answer
for the check that follows the window.

Every call into the program sits in a ``jax.profiler.TraceAnnotation``
named ``bench.*`` (see ``devtrace``); outside a trace they cost a few
microseconds.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

from . import traffic as tr


@dataclass
class Request:
    user: str
    n_rows: int
    row_start: int  # offset into the run's RowSource
    status: str = "pending"
    answer: object = None


@dataclass
class Window:
    """One measured window: every request in it, the calls that carried
    them, and its bounds on the host's clock."""

    start: float
    end: float
    requests: list = field(default_factory=list)
    calls: list = field(default_factory=list)  # [(t0, t1, [i...])]

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def rows(self) -> int:
        return sum(r.n_rows for r in self.requests if r.status == "ok")


def _annotation(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class ClosedLoop:
    """One client sending call after call through ``ForestServer.serve``,
    each call the next composition of the traffic's user rotation."""

    def __init__(self, traffic: dict, server, users: list[str],
                 rows: tr.RowSource) -> None:
        self.traffic = traffic
        self.server = server
        self.users = users
        self.rows = rows
        self.period = tr.closed_period(traffic, users)

    def _call(self, k: int) -> tuple[list[Request], list]:
        reqs, xs = [], []
        for user, n in tr.closed_call(self.traffic, self.users, k):
            x, start = self.rows.take(n)
            reqs.append(Request(user, n, start))
            xs.append((user, x))
        return reqs, xs

    def warm(self) -> None:
        """Every composition of the rotation, twice: the first pass
        compiles and gathers, the second finds every plan and pack
        cached."""
        for k in range(2 * self.period):
            _, xs = self._call(k)
            self.server.serve(xs)

    def run(self, seconds: float) -> Window:
        win = Window(time.perf_counter(), 0.0)
        k = 0
        with _annotation("bench.window"):
            t_end = win.start + seconds
            while True:
                reqs, xs = self._call(k)
                with _annotation("bench.call"):
                    t0 = time.perf_counter()
                    answers = self.server.serve(xs)
                    t1 = time.perf_counter()
                first = len(win.requests)
                for r, a in zip(reqs, answers):
                    r.answer, r.status = a, "ok"
                win.requests.extend(reqs)
                win.calls.append((t0, t1, list(range(first,
                                                      len(win.requests)))))
                k += 1
                if t1 >= t_end:
                    break
        win.end = win.calls[-1][1]
        return win
