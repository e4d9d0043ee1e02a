"""Query streams made from a seed: the one generator that reads every traffic file.

A traffic file (``bench/traffic/<mix>.json``) holds only parameters:

``{"loop": "closed", "seeds_per_query": 16, "check_sample": 8}``
    One client sends queries back to back; each is a fresh set of seeds
    drawn uniformly, without replacement, from the graph's largest
    component.

``{"loop": "open", "rate_qps": 9.0, "sizes": {"law": "log_uniform", "min": 3,
"max": 64}, "check_sample": 128}``
    Independent users: arrivals at a fixed rate over the whole window, seed
    sets of a size drawn from ``sizes``, never repeated.

Every run makes the same work: the seed sets are drawn by position in the
component from the configuration's ``graph_seed``, so they are the same
vertices under the names the run's seed gave them (``graphs.make_graph``).
A closed loop sends them in the same order in every run; an open mix gives
every seed the same inter-arrival gaps and the same seed sets (the quantiles
of the exponential and of the size law), in an order drawn from the run's
seed.
"""

from __future__ import annotations

import numpy as np


STREAM_WARMUP, STREAM_QUERIES, STREAM_SCHEDULE, STREAM_CHECK = range(4)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per purpose, so that adding a draw for one
    purpose never shifts another."""
    return np.random.default_rng(np.random.SeedSequence((seed, 1000 + stream)))


class ClosedStream:
    """Fresh seed sets of one size, drawn one at a time."""

    def __init__(self, traffic: dict, component: np.ndarray, seed: int,
                 stream: int = STREAM_QUERIES):
        self.k = int(traffic["seeds_per_query"])
        if self.k > component.size:
            raise ValueError(f"{self.k} seeds do not fit a component of {component.size}")
        self.component = component
        self._rng = rng_for(seed, stream)

    def next(self) -> np.ndarray:
        return self._rng.choice(self.component, size=self.k, replace=False).astype(np.int32)


def size_quantiles(sizes: dict, count: int) -> np.ndarray:
    """``count`` seed-set sizes at evenly spaced quantiles of the size law.

    ``log_uniform`` is the law of the program's serving benchmark: the size
    is ``floor(exp(U(log min, log(max + 1))))``, clipped to ``[min, max]``.
    """
    if sizes["law"] != "log_uniform":
        raise ValueError(f"unknown size law {sizes['law']!r}")
    lo, hi = int(sizes["min"]), int(sizes["max"])
    q = (np.arange(count) + 0.5) / count
    k = np.exp(np.log(lo) + q * (np.log(hi + 1) - np.log(lo))).astype(np.int64)
    return np.clip(k, lo, hi)


def open_schedule(traffic: dict, seconds: float, seed: int):
    """``(due, order)``: arrival offsets in seconds from the window's start,
    ascending, all below ``seconds``, and for each arrival the index of its
    seed set in :func:`open_pool`."""
    rate = float(traffic["rate_qps"])
    count = max(1, int(round(rate * seconds)))
    q = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-q) / rate
    # the same gaps for every seed: scale them to end inside the window
    gaps *= seconds * (count - 0.5) / count / gaps.sum()
    rng = rng_for(seed, STREAM_SCHEDULE)
    due = np.cumsum(rng.permutation(gaps)) - gaps.min() / 2
    return due, rng.permutation(count)


def open_pool(traffic: dict, count: int, component: np.ndarray, seed: int) -> list:
    """``count`` distinct seed sets, one per quantile of the size law; a set
    that repeats an earlier one (as a canonical sorted tuple) is drawn
    again, so no answer is cached."""
    sizes = size_quantiles(traffic["sizes"], count)
    rng = rng_for(seed, STREAM_QUERIES)
    seen, out = set(), []
    for k in sizes:
        while True:
            q = rng.choice(component, size=int(k), replace=False).astype(np.int32)
            key = tuple(np.sort(q).tolist())
            if key not in seen:
                break
        seen.add(key)
        out.append(q)
    return out


def open_arrivals(traffic: dict, seconds: float, component: np.ndarray, seed: int,
                  query_seed: int):
    """``(due, queries)`` of an open mix: the schedule from the run's
    ``seed``, the seed sets from ``query_seed``."""
    due, order = open_schedule(traffic, seconds, seed)
    pool = open_pool(traffic, len(due), component, query_seed)
    return due, [pool[j] for j in order]


def check_sample(count: int, traffic: dict, seed: int) -> np.ndarray:
    """Indices of the answers the reference checks: all of them, or a
    sample of ``check_sample`` drawn from the seed."""
    want = int(traffic.get("check_sample", count))
    if want >= count:
        return np.arange(count)
    return np.sort(rng_for(seed, STREAM_CHECK).choice(count, size=want, replace=False))
