"""The benchmark's one traffic generator, driven by a traffic file.

Two disciplines, chosen by the file's ``arrivals``:

* ``"poisson"`` — open loop at ``rate_rps``: independent users.  Every seed
  gets the same multiset of inter-arrival gaps (stratified exponential
  quantiles, so exactly ``round(rate * seconds)`` requests are due in the
  window), shuffled by the seed.  Each request is timed from the instant it
  was *due*, so lateness
  of the generator and stalls of the server both land in the latency; the
  lateness itself (submit minus due) is kept as its own stamp.
* ``"closed"`` — ``outstanding`` requests always in flight: offline batch
  work.  A completed request is replaced at once.

A ``lead_s`` of the same traffic runs before the window, so the queue has
settled when the first timed request is due.  Requests refused by
admission (``Overloaded``) are counted, not retried.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time

import numpy as np


@dataclasses.dataclass
class Request:
    index: int            # order of submission
    pool: int             # which input of the pool it carried
    due: float            # perf_counter instant it was due
    submitted: float = math.nan
    ticket: object = None
    status: str = "pending"   # ok | refused | error | missing
    done: float = math.nan    # ticket's completed_at
    output: np.ndarray | None = None


def gaps(n: int, rate: float, rng: np.random.Generator) -> np.ndarray:
    """Inter-arrival gaps (s) of ``n`` requests at mean ``rate``: the same
    multiset for every seed, in the seed's order."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u) / rate
    g = g * (n / rate) / g.sum()                # exactly n / rate seconds
    return rng.permutation(g)


def _submit(server, tenant, req: Request, x, overloaded) -> None:
    req.submitted = time.perf_counter()
    try:
        req.ticket = server.submit(tenant, x)
    except overloaded:
        req.status = "refused"
        req.done = req.submitted


def run_open_loop(server, tenant, traffic: dict, pool: np.ndarray,
                  rng: np.random.Generator, seconds: float, overloaded,
                  annotate) -> tuple[list[Request], float, float]:
    """Drive Poisson arrivals for ``lead_s`` then ``seconds``; returns every
    request and the window's (start, end) perf_counter instants."""
    rate = float(traffic["rate_rps"])
    lead = float(traffic.get("lead_s", 0.0))
    n_lead, n_win = round(rate * lead), round(rate * seconds)
    g = np.concatenate([gaps(n_lead, rate, rng) if n_lead else [],
                        gaps(n_win, rate, rng)])
    picks = rng.integers(0, len(pool), len(g))
    t0 = time.perf_counter() + 0.01
    due = t0 + np.cumsum(g)
    w0 = t0 + (n_lead / rate if n_lead else 0.0)
    w1 = w0 + seconds
    reqs = [Request(i, int(p), float(d)) for i, (p, d) in
            enumerate(zip(picks, due))]
    for req in reqs:
        wait = req.due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        with annotate("bench.submit"):
            _submit(server, tenant, req, pool[req.pool], overloaded)
    return reqs, w0, w1


def run_closed_loop(server, tenant, traffic: dict, pool: np.ndarray,
                    rng: np.random.Generator, seconds: float, overloaded,
                    annotate) -> tuple[list[Request], float, float]:
    """Keep ``outstanding`` requests in flight for ``lead_s`` + ``seconds``;
    a request is due the instant it is submitted."""
    k = int(traffic["outstanding"])
    lead = float(traffic.get("lead_s", 0.0))
    reqs: list[Request] = []
    live: collections.deque[Request] = collections.deque()
    t0 = time.perf_counter()
    w0, w1 = t0 + lead, t0 + lead + seconds
    while True:
        now = time.perf_counter()
        if now >= w1:
            break
        while len(live) < k:
            req = Request(len(reqs), int(rng.integers(len(pool))),
                          time.perf_counter())
            with annotate("bench.submit"):
                _submit(server, tenant, req, pool[req.pool], overloaded)
            reqs.append(req)
            if req.ticket is not None:
                live.append(req)
        if not live:
            continue
        with annotate("bench.wait"):
            try:
                live[0].ticket.result(
                    timeout=max(1e-3, w1 - time.perf_counter()))
            except Exception:  # noqa: BLE001 — outcomes are read by collect
                pass
        while live and live[0].ticket.done():
            live.popleft()
    return reqs, w0, w1


DRIVERS = {"poisson": run_open_loop, "closed": run_closed_loop}


def collect(reqs: list[Request], deadline: float) -> None:
    """Wait (until ``deadline``, a perf_counter instant) for every submitted
    request and record its outcome and output."""
    for req in reqs:
        if req.ticket is None:
            continue
        try:
            out = req.ticket.result(timeout=max(0.0, deadline
                                                - time.perf_counter()))
        except TimeoutError:
            req.status = "missing"
            continue
        except Exception:  # noqa: BLE001 — a failed dispatch is an answer
            req.status = "error"
            req.done = req.ticket.completed_at
            continue
        req.status = "ok"
        req.done = req.ticket.completed_at
        req.output = out
