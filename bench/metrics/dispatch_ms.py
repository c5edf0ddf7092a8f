"""Device rounds: host milliseconds per answered query spent launching
the score loop's per-subset programs — the distinct ``dispatch`` spans
(each round's launch loop, a child of its ``device_round``; a window's
round counted once) summed over the window and divided by the queries
answered. Compiles met while launching fall inside it."""
from bench.metrics_spans import distinct


def read(ctx):
    spans = distinct(ctx, ("dispatch",))
    answered = sum(1 for r in ctx["records"] if r.get("ok"))
    if not spans or not answered:
        return None
    return 1e3 * sum(sp["dur_s"] for sp in spans) / answered
