"""The two loops that drive a system through the measured window.

Each returns a :class:`Window`: what was attempted and failed,
its end-to-end values, the answers to check, and what the per-layer
metrics read.  Host phases are annotated for the profiler
(``jax.profiler.TraceAnnotation``), so a traced run can say what the host
was doing in each of the device's idle gaps.
"""

from __future__ import annotations

import dataclasses
import json
import statistics
import time

import numpy as np

from bench.lib import traffic as tmod


@dataclasses.dataclass
class Window:
    attempted: int
    failed: int
    end_to_end: dict
    answers: list  # what the check compares
    iterations: list = dataclasses.field(default_factory=list)
    counters: dict = dataclasses.field(default_factory=dict)
    latencies: list = dataclasses.field(default_factory=list)  # in order of arrival


def _annotate(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def closed(system, traffic: dict, component, query_seed: int, seconds: float) -> Window:
    """One client, back-to-back queries drawn from ``query_seed``.  The
    window ends at the first query boundary after ``seconds``; ``tree_s``
    is its length over the queries it completed."""
    stream = tmod.ClosedStream(traffic, component, query_seed)
    answers, rounds, ends = [], [], []
    start = time.perf_counter()
    with _annotate("window"):
        while True:
            with _annotate("draw"):
                seeds = stream.next()
            with _annotate("solve"):
                out = system.solve(seeds)
            with _annotate("fetch"):
                answers.append(system.fetch(out, seeds))
            rounds.append(system.rounds(out))
            ends.append(time.perf_counter() - start)
            if ends[-1] >= seconds:
                break
    length = time.perf_counter() - start
    print(json.dumps({"queries": {"rounds": rounds, "seconds": np.diff(np.r_[0.0, ends]).tolist()}}),
          flush=True)
    return Window(len(answers), 0, {"tree_s": length / len(answers)}, answers,
                  iterations=rounds)


def warm_closed(system, traffic: dict, component, query_seed: int) -> None:
    """Compiles and runs the query shape of the window once, on a query the
    window does not send."""
    stream = tmod.ClosedStream(traffic, component, query_seed, tmod.STREAM_WARMUP)
    seeds = stream.next()
    system.fetch(system.solve(seeds), seeds)


def open_loop(server, traffic: dict, component, seed: int, query_seed: int,
              seconds: float, grace: float = 60.0) -> Window:
    """Arrivals on a fixed schedule; the harness is the front end: it submits
    what is due, then flushes.  Latency runs from each request's due time to
    the return of the flush that answers it.  Requests still unanswered
    ``grace`` seconds after the window count as failed.  The schedule comes
    from ``seed``, the seed sets from ``query_seed``."""
    due, queries = tmod.open_arrivals(traffic, seconds, component, seed, query_seed)
    n = len(due)
    answer_at = np.full(n, np.nan)
    answers = [None] * n
    tickets = {}
    submitted_at = np.full(n, np.nan)
    oversleep = []
    before = server.counters()
    i = 0
    start = time.perf_counter()
    with _annotate("window"):
        while True:
            now = time.perf_counter() - start
            if i < n and due[i] <= now:
                with _annotate("submit"):
                    while i < n and due[i] <= now:
                        tickets[server.submit(queries[i])] = i
                        submitted_at[i] = now
                        i += 1
            elif server.pending():
                with _annotate("flush"):
                    got = server.flush()
                t = time.perf_counter() - start
                for ticket, ans in got.items():
                    j = tickets.pop(ticket)
                    answer_at[j], answers[j] = t, ans
            elif i < n:
                with _annotate("wait_arrival"):
                    time.sleep(max(0.0, due[i] - now))
                oversleep.append(time.perf_counter() - start - due[i])
            else:
                break
            if now > seconds + grace:
                break
    after = server.counters()
    done = ~np.isnan(answer_at)
    lat = answer_at[done] - due[done]
    failed = int(n - done.sum())
    # a request that never got an answer is slower than any that did
    lat_all = np.concatenate([lat, np.full(failed, seconds + grace)])
    p95 = statistics.quantiles(lat_all, n=20, method="inclusive")[-1] if n > 1 else lat_all[0]
    qps = float(np.sum(answer_at[done] <= seconds)) / seconds
    # how late the generator ran: submission after the due time (a flush in
    # progress holds it back), and the wake-up after a wait for an arrival
    lag = submitted_at[~np.isnan(submitted_at)] - due[~np.isnan(submitted_at)]
    print(json.dumps({"generator": {
        "submit_lag_s_median": float(np.median(lag)) if lag.size else None,
        "submit_lag_s_max": float(np.max(lag)) if lag.size else None,
        "wake_late_s_max": max(oversleep) if oversleep else None,
        "waits": len(oversleep)}}), flush=True)
    return Window(
        n, failed, {"serve_p95_s": float(p95), "serve_qps": qps},
        [a for a in answers if a is not None],
        counters={k: after[k] - before[k] for k in after},
        latencies=(answer_at - due).tolist())
