"""Reduce a profiler trace (``.xplane.pb``) of a steady window to metrics.

What it gives, all on the trace's own clock (nanoseconds):

* the window: the host annotation the harness opens around the measured
  traffic (``WINDOW``);
* device operations: on a TPU, the events of each ``/device:TPU:n``
  plane's "XLA Ops" line, named after the program ("XLA Modules" event)
  they ran in; on the CPU backend, which has no device plane, the host
  events that carry an ``hlo_op`` stat, named after their ``hlo_module``
  (how a CPU trace records XLA's work; the tests use it);
* device busy time: the union of operation intervals inside the window,
  averaged over the devices that ran anything; idle share is the rest;
* the device time of a named kernel or program: durations of the
  operations whose ``<program>/<instruction>`` name holds the pattern;
* the longest idle gaps, each labelled by the host span that covers its
  midpoint (spans are handed in already on the trace's clock).
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench_window"


class Op:
    """One device operation: ``<program>/<instruction>``, its interval in
    trace nanoseconds, and the device plane it ran on."""
    __slots__ = ("name", "start", "end", "device")

    def __init__(self, name, start, end, device):
        self.name, self.start, self.end = name, start, end
        self.device = device


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _stats(event) -> Dict:
    try:
        return dict(event.stats)
    except (TypeError, ValueError):
        return {}


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def window_bounds(pd, name: str = WINDOW) -> Tuple[float, float]:
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == name:
                    return e.start_ns, e.start_ns + e.duration_ns
    raise ValueError(f"the trace has no {name!r} annotation")


def _short(hlo_text: str) -> str:
    """``%box_scan_seg_pallas.1 = s32[...] custom-call(...)`` -> the
    instruction's name, ``box_scan_seg_pallas.1``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def _tpu_ops(plane) -> List[Op]:
    """A TPU plane's operations, each named ``<program>/<instruction>``
    after the "XLA Modules" event it ran in."""
    lines = {line.name: line for line in plane.lines}
    mods = []
    if "XLA Modules" in lines:
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       e.name.split("(", 1)[0])
                      for e in lines["XLA Modules"].events)
    starts = [m[0] for m in mods]
    ops = []
    if "XLA Ops" not in lines:
        return ops
    for e in lines["XLA Ops"].events:
        mod = ""
        i = bisect.bisect_right(starts, e.start_ns) - 1
        if i >= 0 and mods[i][0] <= e.start_ns < mods[i][1]:
            mod = mods[i][2]
        name = f"{mod}/{_short(e.name)}" if mod else _short(e.name)
        ops.append(Op(name, e.start_ns, e.start_ns + e.duration_ns,
                      plane.name))
    return ops


def device_ops(pd) -> List[Op]:
    ops: List[Op] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops += _tpu_ops(plane)
    if ops:
        return ops
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                st = _stats(e)
                if "hlo_op" in st:
                    ops.append(Op(f"{st.get('hlo_module', '')}/{e.name}",
                                  e.start_ns, e.start_ns + e.duration_ns,
                                  "cpu"))
    return ops


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(ops: Sequence[Op], lo: float, hi: float) -> List[Tuple[float,
                                                              float]]:
    return [(max(o.start, lo), min(o.end, hi)) for o in ops
            if o.end > lo and o.start < hi]


def matches(op: Op, pattern: str) -> bool:
    return pattern in op.name


def op_time_s(ops: Sequence[Op], pattern: str, lo: float,
              hi: float) -> float:
    """Device seconds of the operations matching ``pattern`` in [lo, hi)."""
    return sum(e - s for s, e in clip([o for o in ops if matches(o,
                                                                pattern)],
                                      lo, hi)) / 1e9


def label_gap(mid: float, spans: Sequence[Tuple[str, float, float]]) -> str:
    """The shortest host span covering ``mid``, or "no span"."""
    best = None
    for name, s, e in spans:
        if s <= mid < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "no span"


def reduce(pd, lo: float, hi: float,
           spans: Sequence[Tuple[str, float, float]] = (),
           top: int = 10) -> Dict:
    """The device summary of the window [lo, hi) (trace nanoseconds):
    ``window_s``, ``busy_s`` (mean over devices that ran an operation),
    ``idle_share``, ``device_ops`` (the ``top`` operation names by device
    seconds), ``idle_gaps`` (the ``top`` longest gaps, labelled), and
    ``ops`` (the operations that overlap the window, for kernel
    readers)."""
    ops = [o for o in device_ops(pd) if o.end > lo and o.start < hi]
    by_dev: Dict[str, List] = defaultdict(list)
    for o in ops:
        by_dev[o.device].append(o)
    busy = []
    gaps: List[Tuple[float, float]] = []
    for dev_ops in by_dev.values():
        merged = union(clip(dev_ops, lo, hi))
        busy.append(sum(e - s for s, e in merged))
        edge = lo
        for s, e in merged:
            if s > edge:
                gaps.append((edge, s))
            edge = e
        if hi > edge:
            gaps.append((edge, hi))
    window_s = (hi - lo) / 1e9
    busy_s = (sum(busy) / len(busy) / 1e9) if busy else 0.0
    per_name: Dict[str, float] = defaultdict(float)
    for o in ops:
        per_name[o.name] += (min(o.end, hi) - max(o.start, lo)) / 1e9
    top_ops = sorted(per_name.items(), key=lambda kv: -kv[1])[:top]
    gaps.sort(key=lambda g: -(g[1] - g[0]))
    idle = [[label_gap(0.5 * (s + e), spans), (e - s) / 1e9]
            for s, e in gaps[:top]]
    return {"window_s": window_s, "busy_s": busy_s,
            "idle_share": (1.0 - busy_s / window_s) if window_s > 0
            else None,
            "device_ops": [[k, v] for k, v in top_ops],
            "idle_gaps": idle, "ops": ops, "lo": lo, "hi": hi,
            "n_devices": len(by_dev)}


def module_time_s(summary: Dict, pattern: str) -> Optional[float]:
    """Device seconds of a program's operations (by module or op name) in
    the window, or None when nothing in the trace carries the pattern."""
    hit = [o for o in summary["ops"] if matches(o, pattern)]
    if not hit:
        return None
    return op_time_s(hit, pattern, summary["lo"], summary["hi"])
