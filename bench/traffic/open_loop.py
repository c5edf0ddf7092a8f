"""Open loop: requests due on a Poisson schedule, sent whatever the
server's progress, each timed from when it was due.

The schedule's gaps are exponential at ``rate_qps``, drawn from the
run's seed by stratified sampling (one gap from each of ``n`` equal
slices of the distribution, at a place in the slice and in an order the
seed draws), and scaled so that exactly ``n = round(rate_qps * seconds)``
requests fall due in the window: every seed offers the same amount of
work, with its own arrivals. Every
request runs on its own thread, so a stall delays only the answers, never
the schedule; how late each one was sent is recorded.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List

import numpy as np

from bench.traffic import client


def count(mix: Dict, seconds: float) -> int:
    return max(int(round(mix["rate_qps"] * seconds)), 1)


def schedule(mix: Dict, seconds: float, rng: np.random.Generator):
    """Due offsets in [0, seconds), one per request."""
    n = count(mix, seconds)
    gaps = rng.permutation(-np.log1p(-(np.arange(n) + rng.random(n)) / n))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due * (seconds / (due[-1] + gaps[-1]))


def drive(port: int, bodies: List[Dict], mix: Dict, seconds: float,
          rng: np.random.Generator, on_done=None, grace_s: float = 60.0):
    """Send ``bodies`` on a schedule drawn from ``rng``; returns (records,
    t_start, t_end) where the window is [t_start, t_end) on perf_counter.
    Waits up to ``grace_s`` past the window's end for answers still
    out."""
    offsets = schedule(mix, seconds, rng)
    records = [{"due": 0.0, "body": b} for b in bodies[:len(offsets)]]
    threads = []

    def fire(rec):
        client.post(port, rec["body"], rec)
        if on_done is not None:
            on_done(rec)

    t_start = time.perf_counter()
    for off, rec in zip(offsets, records):
        rec["due"] = t_start + off
        delay = rec["due"] - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        th = threading.Thread(target=fire, args=(rec,), daemon=True)
        th.start()
        threads.append(th)
    t_end = t_start + seconds
    delay = t_end - time.perf_counter()
    if delay > 0:
        time.sleep(delay)
    stop = t_end + grace_s
    for th in threads:
        th.join(timeout=max(stop - time.perf_counter(), 0.0))
    return records, t_start, t_end


def needed(mix: Dict, seconds: float) -> int:
    """Bodies a window of ``seconds`` sends."""
    return count(mix, seconds)
