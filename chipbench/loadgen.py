"""The load generator: one general reader of the traffic files.

A traffic file (``chipbench/traffic/<name>.json``) holds parameters
only.  ``"loop": "open"`` is a Poisson stream at a fixed ``rate_per_s``
(independent senders: requests are due on a schedule, whether or not
the server keeps up); ``"loop": "closed"`` is ``clients`` callers, each
with one request outstanding, sending its next as soon as its reply is
on the host.  ``"jets": [lo, hi]`` bounds the jets per request.

Every seed gets the same work in another order: request sizes are an
even spread over ``lo..hi`` and the open loop's gaps are the
exponential distribution's quantiles, both permuted by the seed, so the
total of jets and the window's length do not vary with the seed.

The pacing loop (poll the server between arrivals, submit each request
when it falls due) has the shape of ``_bench_queue`` in
``benchmarks/bench_serving.py`` of this repository.  Latency is timed
from when a request was due, not from when it was submitted, so a stall
counts against every request it delays.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

#: Seconds past the window's close that the generator waits for the
#: last answers before it counts them as never served.
GRACE_S = 60.0

#: A call into the server longer than this is logged as a stall.
STALL_S = 2e-3

#: Length of the closed loop's request sequence (reused cyclically).
CLOSED_SEQUENCE = 16384


@dataclasses.dataclass
class Schedule:
    jets: np.ndarray          # jets of request k
    offsets: np.ndarray       # first pool row of request k
    due: np.ndarray | None    # open loop: seconds after the window opens


@dataclasses.dataclass
class RequestLog:
    """What the generator saw, per request, on the host's clock, and
    each request's answer (``None``: never answered or shed)."""
    due: np.ndarray
    submit: np.ndarray
    jets: np.ndarray
    offsets: np.ndarray
    outputs: list
    #: open loop: (start, seconds) of every call into the server that
    #: held the generator for more than ``STALL_S``
    stalls: list = dataclasses.field(default_factory=list)


class _Collector:
    """Takes each request's answer off its future as soon as the plans
    carrying it are realized, and drops the future: the generator keeps
    arrays, not one live Python object graph per request, so its own
    bookkeeping does not grow the collector's work over the window."""

    def __init__(self, proxy):
        self._realized = proxy.realized_rids
        self.live: dict = {}          # loop rid -> (request index, future)
        self.outputs: list = []

    def add(self, k: int, fut) -> int:
        self.live[fut.rid] = (k, fut)
        return fut.rid

    def collect(self) -> None:
        realized = self._realized
        while realized:
            for rid in realized.pop():
                entry = self.live.get(rid)
                if entry is not None and entry[1].done:
                    del self.live[rid]
                    self.outputs[entry[0]] = entry[1].result()


def _spread(lo: int, hi: int, n: int, rng) -> np.ndarray:
    sizes = lo + (np.arange(n) * (hi - lo + 1)) // n
    rng.shuffle(sizes)
    return sizes.astype(np.int64)


def schedule(traffic: dict, rng: np.random.RandomState, seconds: float,
             pool_jets: int) -> Schedule:
    lo, hi = (int(v) for v in traffic["jets"])
    if traffic["loop"] == "open":
        rate = float(traffic["rate_per_s"])
        n = max(1, int(round(rate * seconds)))
        jets = _spread(lo, hi, n, rng)
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
        rng.shuffle(gaps)
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    elif traffic["loop"] == "closed":
        n = CLOSED_SEQUENCE
        jets = _spread(lo, hi, n, rng)
        due = None
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    offsets = (rng.random_sample(n) * (pool_jets - jets + 1)).astype(np.int64)
    return Schedule(jets=jets, offsets=offsets, due=due)


def run_open(loop, proxy, pool, sched: Schedule, t0: float,
             clock=time.perf_counter) -> RequestLog:
    """Submit request k at ``t0 + due[k]``; poll the server between
    arrivals; then poll until every jet is back (or ``GRACE_S``)."""
    n = len(sched.jets)
    due = t0 + sched.due
    submit = np.zeros(n)
    got = _Collector(proxy)
    got.outputs = [None] * n
    jets, offsets = sched.jets, sched.offsets
    stalls = []
    i, last = 0, clock()
    while i < n:
        now = clock()
        if now - last > STALL_S:
            stalls.append((last, now - last))
        last = now
        if now >= due[i]:
            submit[i] = now
            got.add(i, loop.submit(pool[offsets[i]:offsets[i] + jets[i]]))
            i += 1
        else:
            loop.poll()
        got.collect()
    total = int(jets.sum())
    give_up = clock() + GRACE_S
    while proxy.realized_jets < total and clock() < give_up:
        loop.poll()
        got.collect()
    return RequestLog(due=due, submit=submit, jets=jets, offsets=offsets,
                      outputs=got.outputs, stalls=stalls)


def run_closed(loop, proxy, pool, sched: Schedule, clients: int, t0: float,
               seconds: float, clock=time.perf_counter) -> RequestLog:
    """``clients`` callers from ``t0``: each sends its next request when
    its reply is on the host, until ``t0 + seconds``; then the
    outstanding ones are drained."""
    due, submit, idx = [], [], []
    got = _Collector(proxy)
    n_seq = len(sched.jets)

    def issue(t_due: float) -> int:
        """Send the next request; returns the loop's id for it."""
        r = len(idx)
        k = r % n_seq
        off, n = int(sched.offsets[k]), int(sched.jets[k])
        due.append(t_due)
        idx.append(k)
        submit.append(clock())
        got.outputs.append(None)
        return got.add(r, loop.submit(pool[off:off + n]))

    outstanding = {c: issue(t0) for c in range(clients)}
    t_end, give_up = t0 + seconds, t0 + seconds + GRACE_S
    while outstanding:
        loop.poll()
        got.collect()
        now = clock()
        for c, rid in list(outstanding.items()):
            if rid not in got.live:
                if now < t_end:
                    outstanding[c] = issue(now)
                else:
                    del outstanding[c]
        if now > give_up:
            break
    idx = np.asarray(idx, np.int64)
    return RequestLog(due=np.asarray(due), submit=np.asarray(submit),
                      jets=sched.jets[idx], offsets=sched.offsets[idx],
                      outputs=got.outputs)
