"""Admission and window: mean ``window_wait`` per request, in ms — the
part of the request's ``queue`` span in which the serving thread held
its batching window open (up to ``batch_window_s``) after taking the
window's first request. The queue's time outside it is the wait behind
other work."""
from bench.metrics_spans import per_request_ms


def read(ctx):
    return per_request_ms(ctx, ("window_wait",))
