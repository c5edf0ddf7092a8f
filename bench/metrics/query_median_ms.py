"""Wire: the median time from a request's sending to its ranked ids in
the client's hands, over every request of the window (a failed one counts
as the client's whole timeout). The end-to-end metrics take the rate and
the 90th percentile; this median stands beside them, because where the mix
holds both models in equal shares it falls between the fast dbranch and
the slow dbens answers and swings from run to run."""
from bench.traffic import client


def read(ctx):
    return client.percentile_ms([client.latency_s(r)
                                 for r in ctx["records"]], 50)
