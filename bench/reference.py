"""Plain reference for a served query: fit, box membership, ranking.

Written from the published algorithm (RapidEarth, arXiv:2309.15617 §2, and
the decision branches it cites) in straightforward numpy float32. It imports
nothing of the engine and takes nothing the engine made: the feature
subsets, the feature range, the bootstrap draws, the boxes, the counts and
the ranking are all computed here from the catalog rows and the request.

Semantics, as the engine states them for ``model`` dbranch / dbens:

* the index holds ``n_subsets`` feature subsets of ``subset_dim`` dims,
  drawn from a seeded permutation pool (each pool is used up before a new
  one is drawn), each subset sorted;
* a decision branch grows a CART tree on one subset: at a node, the
  negatives outside the positives' bounding box (lower face widened by
  1e-6) are dropped; a node with none left, or at ``max_depth``, emits a
  leaf; otherwise the split with the highest ``pl²/nl + pr²/nr`` (float32,
  midpoint thresholds between distinct values, lowest dim then lowest
  threshold on ties) is taken if it beats the parent's ``p²/n``, and the
  children keep every negative of the node's region;
* a leaf's box is the positives' bounding box with the lower face nudged
  down by ``1e-6 * (|lo| + 1)`` (boxes are half-open, ``lo < x <= hi``);
  each face j in turn is then pushed halfway towards the nearest negative
  of the node that the box contains on the other dims, bounded by the
  node region and the catalog's feature range;
* dbranch tries every subset and keeps the model that misses the fewest
  training positives, then has the fewest boxes, then comes first;
* dbens fits ``n_models`` such models on bootstrap draws of the labels,
  each over 5 subsets drawn without replacement, with numpy's PCG64 from
  the request's ``seed``;
* a row's score is the number of boxes (over all models) containing it;
  the answer is the rows with a positive score, training ids left out,
  by descending score then ascending id, cut to ``max_results``.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

F32 = np.float32
DBENS_CANDIDATES = 5


def make_subsets(n_features: int, n_subsets: int, subset_dim: int,
                 seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    pool = rng.permutation(n_features)
    used = 0
    out = []
    for _ in range(n_subsets):
        if used + subset_dim > len(pool):
            pool = rng.permutation(n_features)
            used = 0
        out.append(np.sort(pool[used:used + subset_dim]))
        used += subset_dim
    return np.stack(out).astype(np.int64)


def _best_split(x: np.ndarray, y: np.ndarray) -> Optional[Tuple[int, F32]]:
    n, d = x.shape
    if n < 2:
        return None
    p = F32(y.sum(dtype=F32))
    total = F32(n)
    parent = p * p / total
    nl = np.arange(1, n, dtype=F32)
    nr = total - nl
    best = None
    for j in range(d):
        order = np.argsort(x[:, j], kind="stable")
        xv = x[order, j]
        pl = np.cumsum(y[order], dtype=F32)[:-1]
        pr = p - pl
        h = pl * pl / nl + pr * pr / nr
        h = np.where(xv[1:] > xv[:-1], h, F32(-np.inf))
        i = int(np.argmax(h))
        if np.isfinite(h[i]) and (best is None or h[i] > best[0]):
            best = (h[i], j, F32(0.5) * (xv[i] + xv[i + 1]))
    if best is None or not best[0] > parent:
        return None
    return best[1], best[2]


def _leaf_box(p, n, rlo, rhi, flo, fhi):
    plo = p.min(0)
    phi = p.max(0)
    plo = plo - F32(1e-6) * (np.abs(plo) + F32(1.0))
    lo, hi = plo.copy(), phi.copy()
    d = len(plo)
    for j in range(d):
        b, a = F32(-np.inf), F32(np.inf)
        if len(n):
            inside = (n > lo) & (n <= hi)
            others = np.delete(inside, j, axis=1).all(1)
            below = n[others & (n[:, j] <= plo[j]), j]
            above = n[others & (n[:, j] > phi[j]), j]
            if len(below):
                b = below.max()
            if len(above):
                a = above.min()
        lo_lim = max(b, rlo[j], flo[j])
        hi_lim = min(a, rhi[j], fhi[j])
        if np.isfinite(lo_lim):
            lo[j] = F32(0.5) * (plo[j] + F32(lo_lim))
        if np.isfinite(hi_lim):
            hi[j] = F32(0.5) * (phi[j] + F32(hi_lim))
    return lo, hi


def grow_boxes(xp: np.ndarray, xn: np.ndarray, flo: np.ndarray,
               fhi: np.ndarray, max_depth: int) -> Tuple[np.ndarray,
                                                         np.ndarray]:
    """One decision branch on one subset's columns: (lo [B, d], hi)."""
    d = xp.shape[1]
    los: List[np.ndarray] = []
    his: List[np.ndarray] = []
    work = [(xp, xn, np.full(d, -np.inf, F32), np.full(d, np.inf, F32), 0)]
    while work:
        p, n, rlo, rhi, depth = work.pop()
        if len(p) == 0:
            continue
        plo, phi = p.min(0), p.max(0)
        n_in = n[((n > plo - F32(1e-6)) & (n <= phi)).all(1)] if len(n) \
            else n
        split = None
        if len(n_in) and depth < max_depth:
            split = _best_split(
                np.concatenate([p, n_in]),
                np.concatenate([np.ones(len(p), F32),
                                np.zeros(len(n_in), F32)]))
        if split is None:
            lo, hi = _leaf_box(p, n, rlo, rhi, flo, fhi)
            los.append(lo)
            his.append(hi)
            continue
        j, t = split
        lhi = rhi.copy()
        lhi[j] = min(lhi[j], t)
        rlo2 = rlo.copy()
        rlo2[j] = max(rlo2[j], t)
        pl, nl = p[:, j] <= t, n[:, j] <= t
        work.append((p[~pl], n[~nl], rlo2, rhi.copy(), depth + 1))
        work.append((p[pl], n[nl], rlo.copy(), lhi, depth + 1))
    return np.stack(los), np.stack(his)


def _best_model(xp, xn, subsets, cand, flo, fhi, max_depth):
    best, best_key = None, None
    for k in cand:
        dims = subsets[k]
        lo, hi = grow_boxes(xp[:, dims], xn[:, dims], flo[dims], fhi[dims],
                            max_depth)
        inside = ((xp[:, None, dims] > lo[None]) & (xp[:, None, dims]
                                                     <= hi[None])).all(-1)
        key = (int((~inside.any(1)).sum()), len(lo))
        if best_key is None or key < best_key:
            best, best_key = (dims, lo, hi), key
    return best


def fit(model: str, xp: np.ndarray, xn: np.ndarray, subsets: np.ndarray,
        flo: np.ndarray, fhi: np.ndarray, *, max_depth: int = 12,
        n_models: int = 25, seed: int = 0):
    """[(dims, lo [B, d], hi [B, d])], one entry per fitted model."""
    if model == "dbranch":
        return [_best_model(xp, xn, subsets, range(len(subsets)), flo, fhi,
                            max_depth)]
    if model != "dbens":
        raise ValueError(f"the reference covers dbranch and dbens, not "
                         f"{model!r}")
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_models):
        ip = rng.integers(0, len(xp), len(xp))
        ineg = (rng.integers(0, len(xn), len(xn)) if len(xn)
                else np.zeros(0, np.int64))
        cand = rng.choice(len(subsets), size=min(DBENS_CANDIDATES,
                                                 len(subsets)),
                          replace=False)
        out.append(_best_model(xp[ip], xn[ineg], subsets, cand, flo, fhi,
                               max_depth))
    return out


class Reference:
    """Answers queries over one catalog, as the semantics above say.

    ``x`` is the catalog as served ([N, D] float32). ``mirror_dtype``
    rounds the rows that membership is tested on (not the labels the fit
    reads) to a narrower type: the control, a bfloat16 mirror."""

    def __init__(self, x: np.ndarray, *, n_subsets: int, subset_dim: int,
                 subset_seed: int, mirror_dtype=None):
        self.x = np.asarray(x, F32)
        self.subsets = make_subsets(self.x.shape[1], n_subsets, subset_dim,
                                    subset_seed)
        self.flo = self.x.min(0)
        self.fhi = self.x.max(0)
        self.mirror_dtype = mirror_dtype
        self._cols: Dict[int, np.ndarray] = {}

    def _col(self, j: int) -> np.ndarray:
        c = self._cols.get(j)
        if c is None:
            c = np.ascontiguousarray(self.x[:, j])
            if self.mirror_dtype is not None:
                c = c.astype(self.mirror_dtype).astype(F32)
            self._cols[j] = c
        return c

    def counts(self, models) -> np.ndarray:
        counts = np.zeros(len(self.x), np.int32)
        for dims, lo, hi in models:
            for b in range(len(lo)):
                c = self._col(int(dims[0]))
                idx = np.flatnonzero((c > lo[b, 0]) & (c <= hi[b, 0]))
                for j in range(1, len(dims)):
                    v = self._col(int(dims[j]))[idx]
                    idx = idx[(v > lo[b, j]) & (v <= hi[b, j])]
                counts[idx] += 1
        return counts

    def answer(self, req: Dict) -> Tuple[np.ndarray, np.ndarray]:
        """(ids int64, scores int64) for one request body as posted."""
        pos = np.asarray(req["pos_ids"], np.int64)
        neg = np.asarray(req["neg_ids"], np.int64)
        models = fit(req.get("model", "dbranch"), self.x[pos], self.x[neg],
                     self.subsets, self.flo, self.fhi,
                     max_depth=req.get("max_depth", 12),
                     n_models=req.get("n_models", 25),
                     seed=req.get("seed", 0))
        counts = self.counts(models)
        found = np.flatnonzero(counts > 0)
        if not req.get("include_training", False):
            found = found[~np.isin(found, np.concatenate([pos, neg]))]
        order = np.argsort(-counts[found], kind="stable")
        ids = found[order]
        k = req.get("max_results")
        if k is not None:
            ids = ids[:k]
        return ids.astype(np.int64), counts[ids].astype(np.int64)


def compare(got: Sequence, want: Sequence) -> Dict[str, int]:
    """Count answers that differ. ``got`` holds (ids, scores) or None for
    an answer that never came; ``want`` the reference's (ids, scores)."""
    wrong = missing = 0
    for g, w in zip(got, want):
        if g is None:
            missing += 1
        elif not (np.array_equal(np.asarray(g[0], np.int64), w[0])
                  and np.array_equal(np.asarray(g[1], np.int64), w[1])):
            wrong += 1
    return {"answers_wrong": wrong, "answers_missing": missing}
