"""Closed loop: ``clients`` callers, each sending its next request when
its answer arrives. A request is due when its client sends it.

When the window's time is up no caller sends again, and the window closes
when the last answer still out has come back: every request sent counts,
over all of that time, so a stall that runs to the end of the window still
counts as time and no answer is left half-counted. The callers take the
bodies in order, and the bodies are drawn ``stratum`` at a time (one
stratified draw each; ``clients`` where the mix names none), so that
however many the window answers, every seed sends the same spread of
sizes."""
from __future__ import annotations

import threading
import time
from typing import Dict, List

from bench.traffic import client


def needed(mix: Dict, seconds: float) -> int:
    """An upper bound on the bodies a window of ``seconds`` can send."""
    return int(mix["clients"] + mix["max_qps"] * seconds) + 1


def stratum(mix: Dict) -> int:
    """Bodies per stratified draw."""
    return int(mix.get("stratum", mix["clients"]))


def drive(port: int, bodies: List[Dict], mix: Dict, seconds: float,
          rng=None, on_done=None, grace_s: float = 60.0):
    """Callers send ``bodies`` in turn (``rng`` is unused: a closed loop
    draws no arrivals). Returns (records, t_start, t_end), where t_end is
    when the last answer came back, or the window's end plus ``grace_s``
    if some never did."""
    records: List[Dict] = []
    lock = threading.Lock()
    it = iter(bodies)
    t_start = time.perf_counter()
    t_stop = t_start + seconds

    def caller():
        while time.perf_counter() < t_stop:
            with lock:
                body = next(it, None)
                if body is None:
                    return
                rec = {"body": body}
                records.append(rec)
            rec["due"] = time.perf_counter()
            client.post(port, body, rec)
            if on_done is not None:
                on_done(rec)

    threads = [threading.Thread(target=caller, daemon=True)
               for _ in range(int(mix["clients"]))]
    for th in threads:
        th.start()
    deadline = t_stop + grace_s
    for th in threads:
        th.join(timeout=max(deadline - time.perf_counter(), 0.0))
    with lock:
        out = list(records)
    if len(out) >= len(bodies):
        raise RuntimeError(f"closed loop ran out of its {len(bodies)} "
                           f"bodies: raise max_qps in the mix")
    if any("done" not in r for r in out):
        return out, t_start, deadline
    return out, t_start, max([t_stop] + [r["done"] for r in out])
