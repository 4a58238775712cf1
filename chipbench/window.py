"""Closed-loop sessions over the program's TCP client, and the guard
that keeps a run short of the device store's end.

A session sends its next request only when the last one is answered
(callers that wait for a reply: what upstream's benchmark and clients
do). The window starts at the first send and ends at the last reply.
Each session sends a fixed number of requests, `--seconds` times the
rate the traffic mix states (the rate the cell ran at when it was
defined), so a window lasts about `--seconds` and every run of a cell
does the same work: the same requests, the same number of checkpoints.
A window cut at a fixed time instead let a checkpoint that ran 3%
longer push 9% of the requests out of it (PERF.md, PR 26). A window
that runs past HARD_STOP x `--seconds` stops sending and says so.
"""

from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np

from . import wire
from .traffic import Request

REPLY_TIMEOUT_S = 90.0  # a late answer is late, not wrong
HARD_STOP = 3.0         # x --seconds: a program three times slower is cut


@dataclasses.dataclass
class Sent:
    """One request and what came back."""
    phase: str                  # "setup" or "window"
    session: int
    request: Request
    t_send: float               # time.monotonic()
    t_reply: float | None = None
    wall_send: float = 0.0      # time.time()
    results: np.ndarray | None = None   # records of `request.result`: a
    #                           write's wire.RESULTs, a read's rows
    error: str | None = None

    @property
    def seconds(self) -> float:
        return self.t_reply - self.t_send

    @property
    def created(self) -> int:
        if self.request.is_read:
            return 0
        return int((self.results["status"] == wire.CREATED).sum())


class StoreBudget:
    """Counts created transfers against the device store's capacity:
    `start` asserts when the store fills, so the harness stops a
    request short of it and never lets the server get there."""

    def __init__(self, capacity: int, request_events: int):
        self.limit = capacity - request_events  # one request of margin
        self.created = 0
        self.in_flight = 0
        self.exhausted = False
        self._lock = threading.Lock()

    def reserve(self, n: int) -> bool:
        with self._lock:
            if self.created + self.in_flight + n > self.limit:
                self.exhausted = True
                return False
            self.in_flight += n
            return True

    def settle(self, n: int, created: int) -> None:
        with self._lock:
            self.in_flight -= n
            self.created += created


def percentile_ms(seconds: list[float], q: float) -> float:
    """The one percentile request latencies are read by: linear
    interpolation between the order statistics (numpy's default)."""
    return 1e3 * float(np.percentile(np.asarray(seconds), q))


def send(client, operation, sent: Sent,
         timeout_s: float = REPLY_TIMEOUT_S) -> Sent:
    """One request through the program's client; fills in the reply."""
    request = sent.request
    body = wire.encode_one(request.payload, request.event_size)
    sent.wall_send = time.time()
    sent.t_send = time.monotonic()
    try:
        reply = client.request(operation, body, timeout_s=timeout_s)
        sent.results = np.frombuffer(
            wire.decode_one(reply, request.result.itemsize),
            dtype=request.result)
    except (TimeoutError, ValueError, OSError) as e:
        sent.error = f"{type(e).__name__}: {e}"
    sent.t_reply = time.monotonic()
    return sent


def run_window(clients: list, operations, make_request, quota: int,
               seconds: float, budget: StoreBudget,
               ) -> tuple[list[Sent], float, float, bool]:
    """Drive one session per client through `quota` requests each, each
    under the member of `operations` (the program's Operation enum) that
    the request names;
    returns every request in send order per session, the window's start
    and its end (the last reply), both on time.monotonic(), and whether
    the hard stop cut it."""
    sent: list[list[Sent]] = [[] for _ in clients]
    barrier = threading.Barrier(len(clients) + 1)
    t0 = [0.0]
    halt = threading.Event()
    cut = threading.Event()

    def session(s: int) -> None:
        barrier.wait()
        for k in range(quota):
            if halt.is_set():
                break
            if time.monotonic() - t0[0] > HARD_STOP * seconds:
                cut.set()
                break
            request = make_request(s, k)
            stores = request.operation == "create_transfers"
            if stores and not budget.reserve(request.n_events):
                halt.set()
                break
            one = send(clients[s], getattr(operations, request.operation),
                       Sent("window", s, request, 0.0))
            sent[s].append(one)
            if stores:
                budget.settle(request.n_events,
                              one.created if one.error is None else
                              request.n_events)
            if one.error is not None:
                halt.set()  # an unanswered request ends the window
                break

    threads = [threading.Thread(target=session, args=(s,), daemon=True)
               for s in range(len(clients))]
    for th in threads:
        th.start()
    t0[0] = time.monotonic()
    barrier.wait()
    for th in threads:
        th.join(timeout=HARD_STOP * seconds + 2 * REPLY_TIMEOUT_S)
        if th.is_alive():
            raise RuntimeError("a client session hung past its timeouts")
    flat = [one for per in sent for one in per]
    end = max((one.t_reply for one in flat), default=t0[0])
    return flat, t0[0], end, cut.is_set()
