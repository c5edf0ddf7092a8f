"""Ranking: device milliseconds per answered query of the ranking program
(``kernels/ops.rank_topk``: the jitted ``_rank_topk_compose`` or
``_rank_sort``), from the profiler trace, over the device windows that ran
wholly inside the traced window."""
from bench.metrics_common import per_query_device_ms

PROGRAMS = ("_rank_topk_compose", "_rank_sort")


def read(ctx):
    return per_query_device_ms(ctx, PROGRAMS)
