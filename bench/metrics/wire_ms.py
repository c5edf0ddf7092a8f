"""Wire: mean time per request the HTTP front end spends on the request
itself, in ms — ``http_read`` (headers, body and JSON parse after the
request line, up to the ``QueryRequest``) + ``handoff`` (the serving
thread's answer to the event loop resuming) + ``http_encode`` (payload
build and ``json.dumps``), all children of the request's root span."""
from bench.metrics_spans import per_request_ms

WIRE = ("http_read", "handoff", "http_encode")


def read(ctx):
    return per_request_ms(ctx, WIRE)
