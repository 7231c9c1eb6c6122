"""Drive a started server with a plan's requests through ``Server.submit``.

One thread sends every request. Its work is marked with host spans that
the profiler records beside the device's operations: ``bench.generate``
(waiting for the next due time), ``bench.submit`` (inside
``Server.submit``) and ``bench.wait`` (a closed loop waiting for a
completion).
"""

from __future__ import annotations

import collections
import dataclasses
import time

from jax.profiler import TraceAnnotation


@dataclasses.dataclass(slots=True)
class Sent:
    """One request: which pool frame, when it was due, and the handle
    until the request has an outcome. ``settle`` then keeps what the
    metrics and the check read and drops the handle: a window holds tens
    of thousands of requests, and their handles (an event, a condition
    and its locks each) would otherwise lengthen every full garbage
    collection of the process, which stalls the server's threads too."""

    frame: int
    due: float
    req: object = None
    t_submit: float | None = None
    t_done: float | None = None
    value: object = None            # the served answer, once completed
    answered: bool = False

    def done(self) -> bool:
        return self.req is None or self.req.done()

    def settle(self) -> None:
        """Keep the outcome of a request that has one, and drop the
        handle; a request still pending keeps no answer."""
        req = self.req
        if req is None:
            return
        self.t_submit, self.t_done = req.t_submit, req.t_done
        if req.outcome == "completed":
            self.value = req.result(timeout=0)
            self.answered = True
        self.req = None


def open_loop(server, model: str, frames, plan, t0: float) -> list[Sent]:
    """Send request i at ``t0 + offsets[i]``, late or not; return once
    the last one is sent."""
    sent = []
    for off, idx in zip(plan.offsets, plan.frame_idx):
        due = t0 + float(off)
        with TraceAnnotation("bench.generate"):
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
        with TraceAnnotation("bench.submit"):
            req = server.submit(model, frames[idx])
        sent.append(Sent(int(idx), due, req))
    return sent


def closed_loop(server, model: str, frames, plan, t_end: float,
                start: int = 0, limit: int | None = None) -> list[Sent]:
    """Keep ``plan.clients`` requests outstanding until ``t_end`` (or
    until ``limit`` requests have completed). A request is due when it
    is sent. The oldest outstanding request is waited on; every request
    found complete then is replaced."""
    sent: list[Sent] = []
    outstanding: collections.deque = collections.deque()
    n_cycle = len(plan.frame_idx)
    completed = 0

    def send() -> None:
        idx = int(plan.frame_idx[(start + len(sent)) % n_cycle])
        with TraceAnnotation("bench.submit"):
            due = time.perf_counter()
            s = Sent(idx, due, server.submit(model, frames[idx]))
        sent.append(s)
        outstanding.append(s)

    for _ in range(plan.clients):
        send()
    while True:
        now = time.perf_counter()
        if now >= t_end or (limit is not None and completed >= limit):
            break
        with TraceAnnotation("bench.wait"):
            if not resolved(outstanding[0].req, t_end - now):
                continue
        still = collections.deque()
        for s in outstanding:
            if s.req.done():
                s.settle()
                completed += 1
            else:
                still.append(s)
        n_done = len(outstanding) - len(still)
        outstanding = still
        if time.perf_counter() < t_end:
            for _ in range(n_done):
                send()
    return sent


def resolved(req, timeout: float) -> bool:
    """Wait up to ``timeout`` seconds; True once the request has an
    outcome, whether served or failed."""
    try:
        req.result(timeout=max(0.0, timeout))
    except TimeoutError:
        return False
    except Exception:  # noqa: BLE001 - a failed request is resolved too
        pass
    return True


def wait_all(sent: list[Sent], deadline: float) -> None:
    """Wait for every request to resolve, until ``deadline`` at most, and
    settle each: those served by then are answered."""
    for s in sent:
        if s.req is not None:
            resolved(s.req, deadline - time.perf_counter())
    for s in sent:
        s.settle()
