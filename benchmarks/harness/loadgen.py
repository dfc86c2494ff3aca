"""The closed loop both kinds of traffic use: `workers` threads, each with
a connection of its own, each sending its next request when its last was
answered, until the deadline. Requests come from one shared sequence made
from the seed, so the same seed offers the same requests in the same
order, whichever worker takes each."""

from __future__ import annotations

import itertools
import threading
import time

from .client import BenchFailure, Client

FAILED_MS = 3_600_000.0    # a failed request misses any latency limit


class ClosedLoop:
    """`make(i)` -> (method, path, body, tag) or None when the sequence is
    exhausted; runs in the worker, so keep it cheap. A record is
    (i, tag, t_send, t_done, ok, answer bytes or error text)."""

    def __init__(self, port: int, workers: int, make, keep_answers: bool):
        self.port, self.workers, self.make = port, workers, make
        self.keep_answers = keep_answers
        self.records: list[tuple] = []
        self.exhausted = False
        self._threads: list[threading.Thread] = []
        self._counter = itertools.count()

    def start(self, seconds: float) -> None:
        self.t_open = time.perf_counter()
        self.t_close = self.t_open + seconds
        for w in range(self.workers):
            t = threading.Thread(target=self._work, name=f"loop-{w}",
                                 daemon=True)
            t.start()
            self._threads.append(t)

    def _work(self) -> None:
        client = Client(self.port)
        try:
            while True:
                i = next(self._counter)
                req = self.make(i)
                if req is None:
                    self.exhausted = True
                    return
                method, path, body, tag = req
                t0 = time.perf_counter()
                if t0 >= self.t_close:
                    return
                try:
                    answer = client.request(method, path, body)
                    ok = True
                except (BenchFailure, OSError) as e:
                    answer, ok = str(e).encode(), False
                t1 = time.perf_counter()
                self.records.append(
                    (i, tag, t0, t1, ok,
                     answer if (self.keep_answers or not ok) else
                     answer[:200]))
        finally:
            client.close()

    def join(self, grace_s: float = 60.0) -> None:
        """Wait for the requests in flight at the close (up to a minute
        past it: late is late, not wrong)."""
        for t in self._threads:
            t.join(max(0.0, self.t_close + grace_s - time.perf_counter()))
        if any(t.is_alive() for t in self._threads):
            raise BenchFailure("a request was still unanswered a minute "
                               "after the window closed")
        self.records.sort(key=lambda r: r[0])


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile; inf counts (a failed request misses any
    limit)."""
    if not values:
        return float("nan")
    s = sorted(values)
    k = max(0, min(len(s) - 1, int(-(-q * len(s) // 100)) - 1))
    return s[k]
