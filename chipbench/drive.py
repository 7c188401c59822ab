"""Drive a serving frontend with a traffic mix for one measured window.

Every request is one ``ServeFrontend.submit`` of query rows.  Its send time
is its scheduled time in an open loop and the moment its client sends in a
closed loop; its completion time is taken when its future resolves (a
done-callback, on the frontend's worker thread), so a client thread's own
wake-up never counts.  Requests sent in the window are awaited after the
close, a minute at the most; one that does not resolve by then is
unanswered.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
from concurrent.futures import Future, wait
from typing import Callable, List, Optional

import numpy as np

from chipbench.traffic import arrival_offsets, block_order, request_rows

LATE_S = 60.0


@dataclasses.dataclass
class Request:
    index: int
    rows: np.ndarray              # query-pool rows
    t_due: float                  # scheduled (open) or sent (closed), perf_counter
    t_sent: float = 0.0
    t_done: Optional[float] = None
    result: object = None         # (ids, dists, stats) once resolved
    error: Optional[BaseException] = None


class Window:
    """The requests of one window, and how to send one."""

    def __init__(self, submit: Callable, queries: np.ndarray, rows_per_request: int,
                 seed: int, span: Callable[[str], object]):
        self.submit, self.queries = submit, queries
        self.rows_per_request = rows_per_request
        self.order = block_order(queries.shape[0], rows_per_request, seed)
        self.span = span
        self.requests: List[Request] = []
        self._lock = threading.Lock()
        self._all_resolved = threading.Condition(self._lock)
        self._outstanding = 0

    def send(self, t_due: float) -> Optional[Future]:
        """Submit the next request; its future, or None if it was refused."""
        with self._lock:
            i = len(self.requests)
            req = Request(i, request_rows(i, self.rows_per_request,
                                          self.queries.shape[0], self.order), t_due)
            self.requests.append(req)
            self._outstanding += 1
        with self.span("bench.submit"):
            req.t_sent = time.perf_counter()
            try:
                fut = self.submit(self.queries[req.rows])
            except Exception as e:  # noqa: BLE001 - a rejected request is data
                req.error, req.t_done = e, time.perf_counter()
                self._resolved()
                return None
        fut.add_done_callback(functools.partial(self._on_done, req))
        return fut

    def _on_done(self, req: Request, fut: Future):
        req.t_done = time.perf_counter()
        try:
            req.result = fut.result()
        except Exception as e:  # noqa: BLE001 - a failed request is data
            req.error = e
        self._resolved()

    def _resolved(self):
        with self._lock:
            self._outstanding -= 1
            if not self._outstanding:
                self._all_resolved.notify_all()

    def wait_all(self, deadline: float) -> int:
        """Wait for every sent request until ``deadline``; returns how many
        never resolved."""
        with self.span("bench.result_wait"), self._lock:
            while self._outstanding:
                left = deadline - time.perf_counter()
                if left <= 0:
                    break
                self._all_resolved.wait(left)
        return sum(1 for r in self.requests if r.t_done is None)


def run_closed(window: Window, clients: int, t0: float, t_end: float) -> None:
    """``clients`` callers, each sending its next request when its last one
    resolved, until the window closes."""
    def client():
        while time.perf_counter() < t_end:
            fut = window.send(time.perf_counter())
            if fut is not None:
                with window.span("bench.result_wait"):
                    wait([fut], t_end + LATE_S - time.perf_counter())

    threads = [threading.Thread(target=client, name=f"bench-client-{c}")
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(t_end + 2 * LATE_S - time.perf_counter())


def run_open(window: Window, rate_per_s: float, seconds: float, seed: int,
             t0: float) -> None:
    """Send each request at its scheduled time, from this thread."""
    for off in arrival_offsets(rate_per_s, seconds, seed):
        due = t0 + float(off)
        left = due - time.perf_counter()
        if left > 0:
            time.sleep(left)
        window.send(due)
