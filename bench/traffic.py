"""Load generation and latency arithmetic for the benchmark.

A traffic mix is a JSON file under ``bench/traffic/``. The cells drive a
closed loop: ``clients`` callers each keep one request of ``samples``
samples outstanding and send the next when the previous one completes.

The open-loop generator below is for latency cells: requests are due on a
schedule drawn from the seed, whatever the server does, and each request is
timed from when it was *due* until it completed, so a stall in the server
(or a late generator) shows in every request queued behind it. Every seed
gets the same multiset of request sizes and inter-arrival gaps, in a
different order. No cell uses it yet (PERF.md, open questions).
"""
from __future__ import annotations

import dataclasses
import math
import statistics
import time
from typing import Callable, List, Sequence

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due_s: float  # offset from the window's start (open loop); 0 for closed
    samples: int


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator keyed by the run's seed and a stream id (any size of
    seed: numpy takes arbitrarily large non-negative ints)."""
    return np.random.default_rng((abs(int(seed)),) + tuple(int(s) for s in stream))


def balanced_sizes(lo: int, hi: int, n: int) -> List[int]:
    """``n`` request sizes spread evenly over ``lo..hi`` (uniform mix with
    no sampling noise)."""
    span = hi - lo + 1
    return [lo + (i % span) for i in range(n)]


def open_schedule(seed: int, rate_rps: float, seconds: float,
                  lo: int, hi: int) -> List[Request]:
    """The open-loop schedule: ``round(rate * seconds)`` requests whose
    inter-arrival gaps are the evenly spaced quantiles of an exponential
    distribution of mean ``1/rate`` (Poisson arrivals without sampling
    noise), shuffled by the seed, as are the balanced sizes."""
    n = max(1, int(round(rate_rps * seconds)))
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate_rps for i in range(n)]
    sizes = balanced_sizes(lo, hi, n)
    rng = rng_for(seed, 1)
    gaps = [gaps[i] for i in rng.permutation(n)]
    sizes = [sizes[i] for i in rng.permutation(n)]
    # first request due at 0; the window spans the gaps between them
    due, out = 0.0, []
    for i in range(n):
        out.append(Request(i, due, sizes[i]))
        due += gaps[i]
    return [r for r in out if r.due_s < seconds]


def quantile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated quantile (numpy's default), ``q`` in [0, 1]."""
    v = sorted(values)
    if not v:
        raise ValueError("quantile of an empty sample")
    pos = q * (len(v) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def spread(values: Sequence[float]) -> float:
    """Inter-quartile distance as a share of the median, with the
    quartiles of ``statistics.quantiles(values, n=4)``."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


@dataclasses.dataclass
class Outcome:
    """One request as the generator saw it."""

    request: Request
    handle: object
    due_abs: float        # perf_counter when it was due
    submitted: float      # perf_counter when submit() returned


def drive_open(submit: Callable[[Request], object], schedule: List[Request],
               *, clock=time.perf_counter, sleep=time.sleep,
               annotate=None) -> tuple:
    """Submit each request when it is due (never earlier), from one thread.
    Returns ``(outcomes, t0)``; ``t0`` is the window's start."""
    t0 = clock()
    outcomes = []
    for r in schedule:
        due = t0 + r.due_s
        wait = due - clock()
        if wait > 0:
            sleep(wait)
        with (annotate or null_context)("bench.submit"):
            h = submit(r)
        outcomes.append(Outcome(r, h, due, clock()))
    return outcomes, t0


def drive_closed(submit: Callable[[Request], object], wait: Callable[[object], None],
                 *, clients: int, samples: int, seconds: float,
                 clock=time.perf_counter, annotate=None) -> tuple:
    """``clients`` callers, each with one request outstanding, until
    ``seconds`` have passed; a request is due when its caller sends it.
    The server completes in submission order, so waiting on the oldest
    outstanding request is waiting on the first to complete."""
    t0 = clock()
    deadline = t0 + seconds
    outcomes: List[Outcome] = []
    pending: List[Outcome] = []
    i = 0

    def send():
        nonlocal i
        r = Request(i, clock() - t0, samples)
        i += 1
        with (annotate or null_context)("bench.submit"):
            h = submit(r)
        o = Outcome(r, h, t0 + r.due_s, clock())
        outcomes.append(o)
        pending.append(o)

    for _ in range(clients):
        send()
    while pending:
        o = pending.pop(0)
        with (annotate or null_context)("bench.wait"):
            wait(o.handle)
        if clock() < deadline:
            send()
    return outcomes, t0


class null_context:
    """A do-nothing stand-in for ``jax.profiler.TraceAnnotation``."""

    def __init__(self, *_a, **_kw):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
