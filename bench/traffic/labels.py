"""Label sets for ``POST /query``, drawn from the seed.

Each request labels positives from one cluster and negatives from the
others. Sizes and models are drawn as analysts send them: a positive
count uniform over the mix's range, a negative count uniform over its
own, a model by the mix's weights, each request independently of the
others. The draws are stratified (Latin hypercube): a batch of ``n``
requests takes one count from each of ``n`` equal slices of each range,
at a place in the slice and in an order that the seed draws, so that
every seed sends work spread over the whole range and no seed a lopsided
share of large or small requests. The seed also draws the clusters, the
rows and each ``dbens`` request's bootstrap seed. No label set repeats
within a stream.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def stratified(lo: int, hi: int, n: int,
               rng: np.random.Generator) -> np.ndarray:
    """``n`` whole numbers uniform over [lo, hi], one from each of ``n``
    equal slices of the range, in an order drawn from ``rng``."""
    u = (np.arange(n) + rng.random(n)) / n
    v = lo + np.floor(u * (hi - lo + 1)).astype(np.int64)
    return rng.permutation(np.minimum(v, hi))


class LabelSets:
    def __init__(self, cluster: np.ndarray, mix: Dict,
                 rng: np.random.Generator):
        self.cluster = np.asarray(cluster)
        self.rng = rng
        order = np.argsort(self.cluster, kind="stable")
        counts = np.bincount(self.cluster)
        self.members = np.split(order, np.cumsum(counts)[:-1])
        self.eligible = np.flatnonzero(counts >= mix["positives"][1])
        self.mix = mix
        self.models = [k for k, w in mix["models"].items()
                       for _ in range(int(w))]
        self.seen = set()

    def draw(self, n: int) -> List[Dict]:
        """The next ``n`` requests of this stream."""
        if n <= 0:
            return []
        n_pos = stratified(*self.mix["positives"], n, self.rng)
        n_neg = stratified(*self.mix["negatives"], n, self.rng)
        models = self.rng.permutation(np.resize(np.asarray(self.models), n))
        return [self.one(int(p), int(q), str(m))
                for p, q, m in zip(n_pos, n_neg, models)]

    def one(self, n_pos: int, n_neg: int, model: str) -> Dict:
        """The next request of this stream with these counts and model."""
        while True:
            body = self._one(n_pos, n_neg, model)
            key = tuple(body["pos_ids"]) + (-1,) + tuple(body["neg_ids"])
            if key not in self.seen:
                self.seen.add(key)
                return body

    def _one(self, n_pos: int, n_neg: int, model: str) -> Dict:
        c = int(self.rng.choice(self.eligible))
        pos = np.sort(self.rng.choice(self.members[c], n_pos, replace=False))
        neg = np.empty(0, np.int64)
        while len(neg) < n_neg:
            cand = self.rng.choice(len(self.cluster), 2 * n_neg,
                                   replace=False)
            cand = cand[self.cluster[cand] != c]
            neg = np.unique(np.concatenate([neg, cand]))
        neg = np.sort(self.rng.permutation(neg)[:n_neg])
        body = {"pos_ids": pos.tolist(), "neg_ids": neg.tolist(),
                "model": model, "max_results": int(self.mix["max_results"])}
        if model == "dbens":
            body["seed"] = int(self.rng.integers(0, 2 ** 31 - 1))
        return body
