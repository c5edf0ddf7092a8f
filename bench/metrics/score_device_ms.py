"""Kernels: device milliseconds per answered query of the score loop's
programs — the named per-subset score programs (``jit_score_*``: zone
prune, gather, refine and the accumulate in one program) and the static
path's ``fused_query`` and ``accumulate_scores`` — from the profiler
trace, over the device windows that ran wholly inside the traced
window."""
from bench.metrics_common import per_query_device_ms

PROGRAMS = ("jit_score_", "fused_query", "accumulate_scores")


def read(ctx):
    return per_query_device_ms(ctx, PROGRAMS)
