"""Helpers the span-tree readers share. Each window request's trace
(``ctx["spans"]``: trace id -> the program's trace dict) holds one span
tree; a span a batched window shares lands on every trace of the window
under one span id, so totals count each id once."""
from __future__ import annotations


def distinct(ctx, names):
    """Every span named in ``names`` over the window's traces, each id
    once."""
    seen = {}
    for tr in ctx["spans"].values():
        for sp in tr.get("spans", []):
            if sp["name"] in names:
                seen.setdefault(sp["id"], sp)
    return list(seen.values())


def per_request_ms(ctx, names):
    """Mean over the traces that carry any span named in ``names`` of
    the sum of those spans, in ms; None when no trace carries one."""
    totals = []
    for tr in ctx["spans"].values():
        durs = [sp["dur_s"] for sp in tr.get("spans", [])
                if sp["name"] in names]
        if durs:
            totals.append(sum(durs))
    return 1e3 * sum(totals) / len(totals) if totals else None
