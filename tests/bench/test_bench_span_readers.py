"""The span-tree readers on small hand-built contexts: known spans and
device operations in, the metric's value out; a program without the
spans reads nothing."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run as bench_run  # noqa: E402
from bench.trace_reduce import Op  # noqa: E402

MS = 1e-3


def sp(name, t0, dur, sid, parent):
    return {"name": name, "t0": t0, "dur_s": dur, "id": sid,
            "parent": parent}


def ctx_of(spans=None, records=None, device=None, windows=()):
    return {"spans": spans or {}, "records": records or [],
            "device": device, "windows": list(windows)}


@pytest.fixture
def two_requests():
    """Two requests a and b, served in one window whose fit and first
    device round they share (one span id each on both traces)."""
    shared = [sp("fit", 0.10, 12 * MS, 5, 1),
              sp("device_round", 0.12, 20 * MS, 6, 1),
              sp("dispatch", 0.12, 10 * MS, 7, 6)]
    a = [sp("request", 0.0, 0.3, 1, None),
         sp("http_read", 0.0, 1 * MS, 2, 1),
         sp("queue", 0.001, 60 * MS, 3, 1),
         sp("window_wait", 0.002, 50 * MS, 4, 3),
         *shared,
         sp("handoff", 0.2, 0.5 * MS, 8, 1),
         sp("http_encode", 0.201, 0.5 * MS, 9, 1)]
    b = [sp("request", 0.0, 0.3, 11, None),
         sp("http_read", 0.0, 1 * MS, 12, 11),
         sp("queue", 0.015, 45 * MS, 13, 11),
         sp("window_wait", 0.015, 40 * MS, 14, 13),
         *shared,
         sp("device_round", 0.14, 5 * MS, 16, 11),
         sp("dispatch", 0.14, 4 * MS, 17, 16),
         sp("handoff", 0.2, 1 * MS, 18, 11),
         sp("http_encode", 0.202, 2 * MS, 19, 11)]
    return {"a": {"spans": a}, "b": {"spans": b}}


def read(name, ctx):
    return bench_run.metric_reader(name)(ctx)


def test_wire_ms_is_the_mean_wire_time_per_request(two_requests):
    # a: 1 + 0.5 + 0.5 ms, b: 1 + 1 + 2 ms
    assert read("wire_ms", ctx_of(two_requests)) == pytest.approx(3.0)


def test_window_wait_ms_is_the_mean_window_wait(two_requests):
    assert read("window_wait_ms", ctx_of(two_requests)) == \
        pytest.approx(45.0)


def test_dispatch_ms_counts_a_shared_span_once(two_requests):
    recs = [{"ok": True}, {"ok": True}, {"ok": False}]
    # the window's dispatch (10 ms, on both traces) + b's own 4 ms,
    # over the two answered queries
    assert read("dispatch_ms", ctx_of(two_requests, recs)) == \
        pytest.approx(7.0)


def _device(ops, lo=0.0, hi=1e9):
    return {"ops": ops, "lo": lo, "hi": hi,
            "to_trace_ns": lambda t: t * 1e9}


def test_score_device_ms_reads_the_named_score_programs():
    ops = [Op("jit_score_segmented_dense/fusion.2", 0, 100e6, "d0"),
           Op("jit_accumulate_scores/fusion", 100e6, 130e6, "d0"),
           Op("jit_fused_query/box_scan_seg_pallas", 130e6, 150e6, "d0"),
           Op("_rank_topk_compose/sort.1", 150e6, 160e6, "d0"),
           Op("jit_fn/fusion.1", 160e6, 170e6, "d0"),
           # outside every device window: not counted
           Op("jit_score_segmented_dense/fusion.2", 500e6, 600e6, "d0")]
    wins = [{"t0": 0.0, "t1": 0.2, "size": 2}]
    ctx = ctx_of(device=_device(ops), windows=wins)
    assert read("score_device_ms", ctx) == pytest.approx(75.0)
    assert read("score_device_ms", ctx_of(windows=wins)) is None


def test_idle_unlabelled_share_is_idle_no_program_span_covers():
    # busy 0.1-0.2 s and 0.5-0.6 s of a 1 s window: 0.8 s idle, of
    # which queue (0-0.05) and fit (0.2-0.45) cover 0.3 s; the root
    # alone covers the rest of it and does not count
    ops = [Op("jit_score_x/f", 0.1e9, 0.2e9, "d0"),
           Op("jit_score_x/f", 0.5e9, 0.6e9, "d0")]
    spans = {"a": {"spans": [sp("request", 0.0, 0.9, 1, None),
                             sp("queue", 0.0, 0.05, 2, 1),
                             sp("fit", 0.2, 0.25, 3, 1)]}}
    ctx = ctx_of(spans, device=_device(ops))
    assert read("idle_unlabelled_share", ctx) == pytest.approx(62.5)


@pytest.mark.parametrize("name", ["wire_ms", "window_wait_ms",
                                  "dispatch_ms", "idle_unlabelled_share"])
def test_a_program_without_the_tree_reads_nothing(name):
    # the spans a program before the span tree recorded: no ids, no
    # root, no wire or window children
    spans = {"a": {"spans": [{"name": "queue", "t0": 0.0, "dur_s": 0.05},
                             {"name": "fit", "t0": 0.05, "dur_s": 0.01},
                             {"name": "device_round", "t0": 0.06,
                              "dur_s": 0.02}]}}
    ops = [Op("jit_fn/fusion.2", 0.06e9, 0.08e9, "d0")]
    ctx = ctx_of(spans, [{"ok": True}], _device(ops))
    assert read(name, ctx) is None


def test_the_five_metrics_are_read_in_both_cells():
    bench = bench_run.load_benchmark()
    new = ("wire_ms", "window_wait_ms", "dispatch_ms", "score_device_ms",
           "idle_unlabelled_share")
    for w in bench["workloads"]:
        got = {m["name"] for m in bench_run.cell_metrics(bench, w, True)}
        assert set(new) <= got, w["name"]


def test_a_traced_run_on_the_cpu_reports_the_five():
    """A whole ``--trace 1`` run at a small size (the look for a chip
    skipped): the line carries the five metrics, and no program runs
    under the anonymous ``jit_fn`` name any more."""
    bench = bench_run.load_benchmark()
    cell, _, cfg, mix = bench_run.resolve(bench, "rapidearth-1m.closed-1")
    cfg = {**cfg, "catalog": dict(cfg["catalog"], rows=3000, dim=48,
                                  n_clusters=12)}
    cfg["engine"] = dict(cfg["engine"], n_subsets=8, block=256)
    mix = dict(mix, warmup={"sizes": [1], "rounds": 1, "http_s": 1.0},
               check_sample=4)
    out = bench_run.run(bench, cell, cfg, mix, 2 ** 31 + 13, 2.0, True,
                        require_tpu=False, log=lambda m: None)
    assert out["correct"] is True
    m = out["metrics"]
    for name in ("wire_ms", "window_wait_ms", "dispatch_ms",
                 "score_device_ms", "idle_unlabelled_share"):
        assert name in m and m[name]["value"] >= 0.0, name
    assert 0.0 <= m["idle_unlabelled_share"]["value"] <= 100.0
    # the server's window is 50 ms and one caller never fills it
    assert m["window_wait_ms"]["value"] == pytest.approx(
        cfg["server"]["batch_window_s"] * 1e3, rel=0.2)
    assert not any(name.startswith("jit_fn/")
                   for name, _ in out["breakdown"]["device_ops"])
