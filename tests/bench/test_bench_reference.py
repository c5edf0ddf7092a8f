"""The plain reference agrees with the engine bitwise at a small size, and
its bfloat16-mirror control does not."""
import sys
from pathlib import Path

import ml_dtypes
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import reference  # noqa: E402
from bench.traffic.labels import LabelSets  # noqa: E402

MIX = {"positives": [4, 32], "negatives": [20, 100],
       "models": {"dbranch": 1, "dbens": 1}, "max_results": 100}


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(11)
    n, d, k = 6000, 48, 24
    centers = rng.normal(0, 5.0, (k, d)).astype(np.float32)
    cl = rng.integers(0, k, n).astype(np.int32)
    x = (centers[cl] + np.float32(0.3) * rng.standard_normal(
        (n, d), dtype=np.float32)).astype(np.float32)
    reqs = LabelSets(cl, MIX, np.random.default_rng(12)).draw(10)
    return x, reqs


def test_subsets_follow_the_engine_layout(data):
    from repro.core.subsets import make_subsets
    got = reference.make_subsets(384, 32, 6, 0)
    assert np.array_equal(got, make_subsets(384, 32, 6, seed=0))
    assert got.shape == (32, 6)


def test_membership_counts_match_brute_force(data):
    x, _ = data
    ref = reference.Reference(x, n_subsets=4, subset_dim=6, subset_seed=0)
    rng = np.random.default_rng(0)
    models = []
    for k in range(3):
        dims = ref.subsets[k]
        lo = rng.normal(-3, 2, (2, 6)).astype(np.float32)
        hi = lo + rng.uniform(1, 8, (2, 6)).astype(np.float32)
        models.append((dims, lo, hi))
    want = np.zeros(len(x), np.int64)
    for dims, lo, hi in models:
        xs = x[:, dims]
        want += ((xs[:, None] > lo[None]) & (xs[:, None] <= hi[None])
                 ).all(-1).sum(-1)
    assert np.array_equal(ref.counts(models), want)


@pytest.mark.parametrize("engine_kw", [
    {"live": True, "score_mode": "dense"},
    {"live": False, "score_mode": "dense"},
    {"live": True},
], ids=["live-dense", "static-dense", "live-sparse"])
def test_engine_answers_equal_the_reference(data, engine_kw):
    from repro.core.engine import SearchEngine

    x, reqs = data
    eng = SearchEngine(x, n_subsets=8, subset_dim=6, block=256, seed=0,
                       **engine_kw)
    got = eng.query_batch(reqs)
    ref = reference.Reference(x, n_subsets=8, subset_dim=6, subset_seed=0)
    want = [ref.answer(r) for r in reqs]
    assert all(not isinstance(g, Exception) for g in got)
    assert reference.compare([(g.ids, g.scores) for g in got], want) == \
        {"answers_wrong": 0, "answers_missing": 0}
    assert any(len(w[0]) == 100 for w in want)


def test_control_fails_and_missing_answers_count(data):
    x, reqs = data
    kw = dict(n_subsets=8, subset_dim=6, subset_seed=0)
    want = [reference.Reference(x, **kw).answer(r) for r in reqs]
    low = reference.Reference(x, mirror_dtype=ml_dtypes.bfloat16, **kw)
    got = [low.answer(r) for r in reqs]
    assert reference.compare(got, want)["answers_wrong"] >= 1
    assert reference.compare([None] + want[1:], want) == \
        {"answers_wrong": 0, "answers_missing": 1}
    bad = [(want[0][0][::-1], want[0][1])] + want[1:]
    assert reference.compare(bad, want)["answers_wrong"] == 1


def test_reference_rejects_other_models(data):
    x, reqs = data
    ref = reference.Reference(x, n_subsets=8, subset_dim=6, subset_seed=0)
    with pytest.raises(ValueError):
        ref.answer(dict(reqs[0], model="knn"))
