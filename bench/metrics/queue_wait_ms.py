"""Admission and window: mean time a window request spent queued, from
admission to the serving thread taking it (the program's ``queue`` span,
which includes the batching window's wait)."""


def read(ctx):
    waits = [sp["dur_s"] for tr in ctx["spans"].values()
             for sp in tr.get("spans", []) if sp["name"] == "queue"]
    return 1e3 * sum(waits) / len(waits) if waits else None
