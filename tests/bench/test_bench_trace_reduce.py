"""The profiler reduction, on a small trace recorded on the CPU."""
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce  # noqa: E402
from bench.roofline import box_scan_bytes, least_time_s, peaks_for  # noqa: E402


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        return jnp.sort(x * 2.0 + 1.0)

    x = jnp.ones((1 << 18,), jnp.float32)
    step(x).block_until_ready()
    out = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        for _ in range(3):
            step(x).block_until_ready()
            time.sleep(0.05)
    jax.profiler.stop_trace()
    pd = trace_reduce.load(trace_reduce.find_xplane(str(out)))
    return pd


def test_window_and_busy_share(recorded):
    lo, hi = trace_reduce.window_bounds(recorded)
    assert hi - lo > 0.15e9          # three sleeps of 50 ms inside it
    s = trace_reduce.reduce(recorded, lo, hi)
    assert s["n_devices"] >= 1
    assert 0.0 < s["busy_s"] < s["window_s"]
    assert 0.0 < s["idle_share"] < 1.0
    # the sleeps are idle, so the longest gap is at least most of one
    assert s["idle_gaps"][0][1] > 0.03
    assert s["idle_gaps"][0][0] == "no span"
    assert s["device_ops"] and all(v > 0 for _, v in s["device_ops"])
    assert len(s["device_ops"]) <= 10 and len(s["idle_gaps"]) <= 10


def test_program_time_by_module_name(recorded):
    lo, hi = trace_reduce.window_bounds(recorded)
    s = trace_reduce.reduce(recorded, lo, hi)
    t = trace_reduce.module_time_s(s, "jit_step")
    assert t is not None and 0.0 < t <= s["busy_s"] + 1e-9
    assert trace_reduce.module_time_s(s, "no_such_program") is None


def test_gaps_are_labelled_by_the_covering_span(recorded):
    lo, hi = trace_reduce.window_bounds(recorded)
    s0 = trace_reduce.reduce(recorded, lo, hi)
    spans = [("outer", lo, hi), ("sleep", lo, hi - 1.0)]
    s = trace_reduce.reduce(recorded, lo, hi, spans)
    # the shortest covering span wins
    assert {name for name, _ in s["idle_gaps"]} <= {"outer", "sleep"}
    assert [g[1] for g in s["idle_gaps"]] == [g[1] for g in s0["idle_gaps"]]


@pytest.mark.parametrize("intervals,want", [
    ([(0, 2), (1, 3), (5, 6)], [[0, 3], [5, 6]]),
    ([(5, 6), (0, 1)], [[0, 1], [5, 6]]),
    ([(0, 4), (1, 2)], [[0, 4]]),
    ([(3, 3), (1, 2)], [[1, 2]]),
])
def test_union(intervals, want):
    assert trace_reduce.union(intervals) == want


def test_peaks_table_and_roofline_counting():
    v5e = peaks_for("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("TPU v99")
    # 10 blocks of 1024 rows on 6 real dims, 3 boxes: f32 values only
    n = box_scan_bytes(10, 1024, 6, 3)
    assert n == (10 * 1024 * 6 + 2 * 3 * 6) * 4
    assert least_time_s(n, v5e) == pytest.approx(n / 819e9)
