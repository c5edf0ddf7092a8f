"""Fit: mean ``fit`` span of a window request, in ms. The span is host
wall time around the window's batched device fit (host split tables,
uploads, both growth rounds, selection and expansion, and the syncs that
read their results); every request of a window carries the same span."""


def read(ctx):
    fits = [sp["dur_s"] for tr in ctx["spans"].values()
            for sp in tr.get("spans", []) if sp["name"] == "fit"]
    return 1e3 * sum(fits) / len(fits) if fits else None
