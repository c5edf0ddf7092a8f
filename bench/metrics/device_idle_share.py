"""Device: percent of the traced window in which no operation ran on the
device (1 - union of operation intervals / window)."""


def read(ctx):
    dev = ctx["device"]
    if dev is None or dev["idle_share"] is None or dev["busy_s"] <= 0:
        return None
    return 100.0 * dev["idle_share"]
