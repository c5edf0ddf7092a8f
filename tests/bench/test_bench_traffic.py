"""Load generators: seeded label sets and arrivals, percentile arithmetic."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.traffic import client, closed_loop, driver, load_mix, open_loop  # noqa: E402
from bench.traffic.labels import LabelSets, stratified  # noqa: E402

MIX = {"kind": "open_loop", "rate_qps": 3.0, "positives": [4, 32],
       "negatives": [20, 100], "models": {"dbranch": 1, "dbens": 1},
       "max_results": 100, "check_sample": 4}


def clusters(n=20000, k=40, seed=0):
    return np.random.default_rng(seed).integers(0, k, n).astype(np.int32)


def rng(seed):
    return np.random.default_rng(seed)


def sizes(reqs):
    return [(len(r["pos_ids"]), len(r["neg_ids"]), r["model"])
            for r in reqs]


def test_same_seed_same_label_sets_and_arrivals():
    cl = clusters()
    big = 2 ** 31 + 99
    a = LabelSets(cl, MIX, rng([big, 0])).draw(30)
    b = LabelSets(cl, MIX, rng([big, 0])).draw(30)
    assert a == b
    assert np.array_equal(open_loop.schedule(MIX, 20.0, rng(big)),
                          open_loop.schedule(MIX, 20.0, rng(big)))


def test_seeds_draw_their_own_sizes_and_arrivals_over_the_whole_range():
    cl = clusters()
    a = sizes(LabelSets(cl, MIX, rng(1)).draw(56))
    b = sizes(LabelSets(cl, MIX, rng(2)).draw(56))
    assert a != b
    for s in (a, b):
        pos = np.array([x[0] for x in s])
        neg = np.array([x[1] for x in s])
        # one count from each of 56 equal slices of each range
        assert pos.min() <= 5 and pos.max() >= 31
        assert neg.min() <= 22 and neg.max() >= 98
        assert sum(x[2] == "dbens" for x in s) == 28
        # the order is drawn, not sorted
        assert not np.all(np.diff(pos) >= 0)
    t1 = open_loop.schedule(MIX, 20.0, rng(1))
    t2 = open_loop.schedule(MIX, 20.0, rng(2))
    assert len(t1) == len(t2) and not np.array_equal(t1, t2)


@pytest.mark.parametrize("lo,hi,n", [(4, 32, 56), (20, 100, 7),
                                     (4, 32, 1), (20, 100, 300)])
def test_stratified_counts_take_one_from_each_slice(lo, hi, n):
    v = stratified(lo, hi, n, rng(n))
    assert len(v) == n and v.min() >= lo and v.max() <= hi
    # one uniform draw u from each slice [i/n, (i+1)/n), count = lo +
    # floor(u * W): so below every whole number m, as many counts as
    # slices below m / W, give or take the slice m / W falls in
    w = hi - lo + 1
    for m in range(w + 1):
        below = int(np.sum(v - lo < m))
        assert np.floor(m * n / w) <= below <= np.ceil(m * n / w)


def test_label_sets_follow_the_mix():
    cl = clusters()
    reqs = LabelSets(cl, MIX, np.random.default_rng(3)).draw(200)
    keys = set()
    for r in reqs:
        pos, neg = np.asarray(r["pos_ids"]), np.asarray(r["neg_ids"])
        assert 4 <= len(pos) <= 32 and 20 <= len(neg) <= 100
        assert len(np.unique(pos)) == len(pos)
        assert len(np.unique(neg)) == len(neg)
        assert len(set(cl[pos])) == 1                 # one cluster
        assert cl[pos[0]] not in set(cl[neg])         # negatives elsewhere
        assert r["max_results"] == 100
        assert ("seed" in r) == (r["model"] == "dbens")
        keys.add((tuple(pos), tuple(neg)))
    assert len(keys) == len(reqs)                     # no repeats


@pytest.mark.parametrize("rate,seconds", [(2.4, 45.0), (5.0, 45.0),
                                          (0.5, 7.0)])
def test_open_schedule_is_poisson_quantiles_filling_the_window(rate,
                                                               seconds):
    mix = dict(MIX, rate_qps=rate)
    t = open_loop.schedule(mix, seconds, rng(int(rate * 10)))
    assert len(t) == round(rate * seconds) == open_loop.needed(mix, seconds)
    assert t[0] == 0.0 and np.all(np.diff(t) > 0) and t[-1] < seconds
    gaps = np.append(np.diff(t), seconds - t[-1])
    assert gaps.sum() == pytest.approx(seconds)
    # exponential: the coefficient of variation is near 1 (where there
    # are enough gaps to tell)
    if len(gaps) >= 50:
        assert 0.8 < gaps.std() / gaps.mean() < 1.2


def test_percentile_arithmetic():
    xs = [0.001 * i for i in range(1, 101)]          # 1..100 ms
    assert client.percentile_ms(xs, 50) == pytest.approx(50.5)
    assert client.percentile_ms(xs, 90) == pytest.approx(90.1)
    assert client.percentile_ms([], 50) is None
    ok = {"ok": True, "due": 1.0, "done": 1.25}
    assert client.latency_s(ok) == pytest.approx(0.25)
    # a failure misses any limit
    assert client.latency_s({"ok": False, "due": 1.0, "done": 1.01}) == \
        client.TIMEOUT_S


def test_mixes_resolve_to_drivers():
    for name in ("closed-1",):
        mix = load_mix(name)
        drv = driver(mix["kind"])
        assert drv.needed(mix, 45.0) > 0
    assert closed_loop.needed({"clients": 32, "max_qps": 40}, 10) == 433
