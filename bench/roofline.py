"""Chip peaks and the work a kernel needs, counted from the algorithm.

The work is what the algorithm has to touch, whatever implements it: for
the refine scan (``box_scan_seg``), every row of every block that the zone
prune lets through, on the subset's real dims (``subset_dim`` float32
values a row), plus the box bounds it compares them with. The 128-lane
padding of the gathered rows and the capacity-sized gather are not work
the algorithm needs, so a kernel that pays for them reads below 100%.

The scan makes ``2 * subset_dim`` comparisons a row and box: there is no
published peak for the chip's vector compare rate, so the bound used is
the bytes bound, least time = bytes / HBM bandwidth. Reading faster than
HBM's peak is impossible, so the share cannot pass 100% unless the bytes
are overcounted or the time undercounted.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
F32_BYTES = 4


def peaks_for(device_kind: str, table: dict = None) -> dict:
    if table is None:
        with open(PEAKS) as f:
            table = json.load(f)
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add its published numbers") from None


def box_scan_bytes(blocks_touched: int, block_rows: int, subset_dim: int,
                   n_boxes: int) -> int:
    """Bytes the refine scan needs: surviving rows on the real dims, plus
    each box's lower and upper bounds."""
    rows = int(blocks_touched) * int(block_rows)
    return (rows * subset_dim + 2 * int(n_boxes) * subset_dim) * F32_BYTES


def least_time_s(n_bytes: float, peaks: dict) -> float:
    return float(n_bytes) / float(peaks["hbm_bytes_per_s"])
