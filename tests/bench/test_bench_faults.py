"""A whole run of the harness on the CPU at a small size (the look for a
chip skipped): sound, it reads correct; with the timed path broken
underneath, or the control in the program's place, it does not."""
import sys
from pathlib import Path

import ml_dtypes
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import control as bench_control  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench.traffic import load_mix  # noqa: E402

SECONDS = 3.0


def small(bench, workload, mix_name=None, **mix_kw):
    cell, _, cfg, mix = bench_run.resolve(bench, workload)
    if mix_name:
        mix = load_mix(mix_name)
    cfg = {**cfg, "catalog": dict(cfg["catalog"], rows=3000, dim=48,
                                  n_clusters=12)}
    cfg["engine"] = dict(cfg["engine"], n_subsets=8, block=256)
    mix = dict(mix, warmup={"sizes": [4], "rounds": 1, "http_s": 1.0},
               check_sample=6, **mix_kw)
    return cell, cfg, mix


def go(bench, cell, cfg, mix, seed=2 ** 31 + 11):
    return bench_run.run(bench, cell, cfg, mix, seed, SECONDS, False,
                         require_tpu=False, log=lambda m: None)


@pytest.fixture(scope="module")
def bench():
    return bench_run.load_benchmark()


def test_sound_run_is_correct_and_reports_its_metrics(bench):
    cell, cfg, mix = small(bench, "rapidearth-1m.closed-1", clients=4)
    out = go(bench, cell, cfg, mix)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"queries_per_s", "query_p90_ms",
                                   "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert out["checks"]["answers_wrong"] == {"value": 0, "limit": 0}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(out["device"])


def _alter_answers(monkeypatch):
    from repro.core.engine import SearchEngine
    rank = SearchEngine._rank_device

    def altered(self, *a, **kw):
        out, hb = rank(self, *a, **kw)
        return [(ids[::-1].copy(), sc) for ids, sc in out], hb
    monkeypatch.setattr(SearchEngine, "_rank_device", altered)


def _drop_half_the_batch(monkeypatch):
    from repro.core.engine import SearchEngine
    scores = SearchEngine._device_scores

    def half(self, jobs, nq, view, deadline_s=None):
        sc, agg = scores(self, jobs, nq, view, deadline_s=deadline_s)
        keep = -(-nq // 2)
        return sc.at[:, keep:].set(0) if nq > 1 else sc * 0, agg
    monkeypatch.setattr(SearchEngine, "_device_scores", half)


@pytest.mark.parametrize("fault", [_alter_answers, _drop_half_the_batch],
                         ids=["answer-altered", "half-the-batch-left-out"])
def test_a_broken_timed_path_reads_not_correct(bench, monkeypatch, fault):
    # every answer checked, so each window's left-out half is among them
    cell, cfg, mix = small(bench, "rapidearth-1m.closed-1", clients=4)
    mix["check_sample"] = 1000
    fault(monkeypatch)
    out = go(bench, cell, cfg, mix)
    assert out["correct"] is False
    assert out["checks"]["answers_wrong"]["value"] >= 1


def test_control_reads_not_correct(bench):
    _, cfg, mix = small(bench, "rapidearth-1m.closed-1")
    cfg["catalog"]["rows"] = 20000
    out = bench_control.control(cfg, mix, 2 ** 31 + 3, 20.0,
                                mirror_dtype=ml_dtypes.bfloat16)
    assert out["sampled"] == mix["check_sample"]
    assert out["answers_wrong"] >= 1


def test_sample_holds_the_largest_label_set():
    recs = [{"body": {"pos_ids": [1] * k, "neg_ids": [2] * 20}}
            for k in (4, 30, 9, 12, 5, 7, 8)]
    pick = bench_run.sample(recs, 3, 123)
    assert pick[0] == 1 and len(pick) == 3 == len(set(pick))
    assert pick == bench_run.sample(recs, 3, 123)
    assert np.all(np.asarray(pick) < len(recs))
