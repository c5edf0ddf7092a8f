"""Device: percent of the traced window's device idle time that no
program span covers — no span of any window request's tree other than
its root ``request`` alone. What is left is idle the program cannot
explain: the client's turnaround, the connection, the write of the
answer. Host spans are put on the trace's clock with the harness's one
offset. None for a program whose traces have no root."""
from bench.trace_reduce import clip, union

ROOT = "request"


def _length(intervals):
    return sum(e - s for s, e in intervals)


def _overlap(a, b):
    """Total length of the intersection of two sorted disjoint lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(ctx):
    dev = ctx["device"]
    if dev is None:
        return None
    spans = [sp for tr in ctx["spans"].values()
             for sp in tr.get("spans", [])]
    if not any(sp["name"] == ROOT for sp in spans):
        return None
    lo, hi, to_ns = dev["lo"], dev["hi"], dev["to_trace_ns"]
    covered = union((max(to_ns(sp["t0"]), lo),
                     min(to_ns(sp["t0"] + sp["dur_s"]), hi))
                    for sp in spans if sp["name"] != ROOT)
    by_dev = {}
    for op in dev["ops"]:
        by_dev.setdefault(op.device, []).append(op)
    idle = unlabelled = 0.0
    for ops in by_dev.values():
        gaps, edge = [], lo
        for s, e in union(clip(ops, lo, hi)):
            if s > edge:
                gaps.append((edge, s))
            edge = e
        if hi > edge:
            gaps.append((edge, hi))
        idle += _length(gaps)
        unlabelled += _length(gaps) - _overlap(gaps, covered)
    return 100.0 * unlabelled / idle if idle > 0 else None
