"""Kernels: the refine scan's (``kernels/box_scan.py`` ``box_scan_seg``)
share of its roofline. Least time = the bytes the algorithm needs (rows of
the blocks that survived the zone prune, on the subset's real dims, plus
the box bounds; ``bench/roofline.py``) over HBM bandwidth; kernel time =
its device time in the trace. Both over the device windows that ran
wholly inside the traced window."""
from bench.metrics_common import inside_windows, kernel_time_s
from bench.roofline import box_scan_bytes, least_time_s

KERNEL = "box_scan_seg"


def read(ctx):
    dev = ctx["device"]
    if dev is None or ctx["peaks"] is None:
        return None
    wins = inside_windows(ctx)
    t = kernel_time_s(ctx, wins, (KERNEL,))
    if not t:
        return None
    eng = ctx["config"]["engine"]
    need = sum(box_scan_bytes(w["blocks_touched"], eng["block"],
                              eng["subset_dim"], w["n_boxes"])
               for w in wins)
    return 100.0 * least_time_s(need, ctx["peaks"]) / t
