"""Host index build: seconds to construct the engine over the catalog
(zone-map indexes of every feature subset, host Morton sort), timed by the
harness around ``SearchEngine(...)``."""


def read(ctx):
    return ctx["index_build_s"]
