"""Device rounds: the score loop's host syncs per answered query, from the
engine's own ``n_host_syncs`` counter of each device window (one deferred
sync per round; overflow retries add rounds), summed over the windows the
traffic drove and divided by the queries they answered."""


def read(ctx):
    w = ctx["windows"]
    n = sum(x["size"] for x in w)
    return sum(x["syncs"] for x in w) / n if n else None
