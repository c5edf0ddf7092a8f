"""Device rounds: executables JAX built, or loaded from its persistent
cache, while the window's traffic ran (JAX's own compile event). Each is
a stall for the requests behind it; a warm-up that covered every shape
reads 0."""


def read(ctx):
    return float(len(ctx["compiles"]))
