"""Helpers the per-layer readers share: which device windows lie wholly
inside the traced window, and how much device time named operations took
inside them."""
from __future__ import annotations

from bench.trace_reduce import matches


def inside_windows(ctx):
    dev = ctx["device"]
    to_ns = dev["to_trace_ns"]
    return [w for w in ctx["windows"]
            if to_ns(w["t0"]) >= dev["lo"] and to_ns(w["t1"]) <= dev["hi"]]


def kernel_time_s(ctx, windows, patterns):
    """Device seconds of the operations matching any of ``patterns`` that
    ran inside ``windows``."""
    dev = ctx["device"]
    to_ns = dev["to_trace_ns"]
    spans = [(to_ns(w["t0"]), to_ns(w["t1"])) for w in windows]
    total = 0.0
    for op in dev["ops"]:
        if not any(matches(op, p) for p in patterns):
            continue
        for s, e in spans:
            lo, hi = max(op.start, s), min(op.end, e)
            if hi > lo:
                total += hi - lo
    return total / 1e9


def per_query_device_ms(ctx, patterns):
    dev = ctx["device"]
    if dev is None:
        return None
    wins = inside_windows(ctx)
    n = sum(w["size"] for w in wins)
    t = kernel_time_s(ctx, wins, patterns)
    if not n or not t:
        return None
    return 1e3 * t / n
