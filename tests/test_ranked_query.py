"""Device-resident scoring + ranking (ISSUE 2 / DESIGN.md §9).

Contracts pinned here:
  * kops.rank_topk reproduces the host ranking oracle SearchEngine._rank
    EXACTLY — descending score, ascending id on ties — on both the
    id-composed top_k path and the two-key sort fallback;
  * the ranked engine path (max_results=k) returns the exact k-prefix of
    the host oracle, ties included, for sequential and batched queries;
  * overflow handling is deferred to ONE batched sync and retries ONLY
    the overflowed subsets, with results bitwise-identical to the
    query_index host path;
  * batch-wide aggregates are namespaced batch_*; per-request stats carry
    that request's own n_boxes.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.boxes import BoxSet
from repro.core.engine import SearchEngine
from repro.core.index import (build_index, build_sharded_index, morton_code,
                              query_index)
from repro.core.segments import SegmentedZoneMapIndex, mask_tombstones
from repro.kernels import ops as kops


def _host_rank(counts, train_ids):
    """The oracle, standalone: stable argsort of -counts over found rows."""
    found = np.nonzero(counts > 0)[0]
    found = found[~np.isin(found, train_ids)]
    order = np.argsort(-counts[found], kind="stable")
    return found[order], counts[found][order]


# ----------------------------------------------------------------------
# kops.rank_topk against the host oracle
# ----------------------------------------------------------------------

@pytest.mark.parametrize("seed,nq,n,smax", [(0, 1, 500, 4), (1, 3, 1000, 2),
                                            (2, 5, 257, 9)])
@pytest.mark.parametrize("method", ["topk", "sort", "threshold"])
def test_rank_topk_matches_host_oracle(seed, nq, n, smax, method):
    """Low smax forces heavy score ties — the id tie-break must match the
    host stable sort on ALL THREE implementations."""
    rng = np.random.default_rng(seed)
    scores = rng.integers(0, smax + 1, (nq, n)).astype(np.int32)
    tids = np.full((nq, 8), n, np.int32)
    for q in range(nq):
        tids[q, :4] = rng.choice(n, 4, replace=False)
    ids_k, scores_k, n_valid = kops.rank_topk(
        jnp.asarray(scores), jnp.asarray(tids), k=n, score_bound=smax,
        method=method)
    ids_k, scores_k = np.asarray(ids_k), np.asarray(scores_k)
    n_valid = np.asarray(n_valid)
    for q in range(nq):
        want_ids, want_scores = _host_rank(scores[q], tids[q, :4])
        nv = int(n_valid[q])
        assert nv == len(want_ids)
        np.testing.assert_array_equal(ids_k[q, :nv], want_ids)
        np.testing.assert_array_equal(scores_k[q, :nv], want_scores)
        # past the valid prefix: sentinel ids
        assert (ids_k[q, nv:] == -1).all()


def test_rank_topk_truncates_exact_prefix():
    """k < n_found must return exactly the first k of the full host
    ranking — including ties straddling the k boundary (id-ascending)."""
    rng = np.random.default_rng(7)
    n = 400
    scores = rng.integers(0, 3, (1, n)).astype(np.int32)   # massive ties
    empty = np.full((1, 1), n, np.int32)
    want_ids, _ = _host_rank(scores[0], np.empty(0, np.int64))
    for method in ("topk", "sort", "threshold"):
        for k in (1, 7, 50):
            ids_k, _, n_valid = kops.rank_topk(
                jnp.asarray(scores), jnp.asarray(empty), k=k, score_bound=2,
                method=method)
            np.testing.assert_array_equal(
                np.asarray(ids_k)[0, :min(int(n_valid[0]), k)],
                want_ids[:k])


def test_rank_topk_methods_agree():
    rng = np.random.default_rng(11)
    scores = rng.integers(0, 6, (4, 777)).astype(np.int32)
    tids = np.full((4, 1), 777, np.int32)
    a = kops.rank_topk(jnp.asarray(scores), jnp.asarray(tids), k=64,
                       score_bound=5, method="topk")
    for method in ("sort", "threshold"):
        b = kops.rank_topk(jnp.asarray(scores), jnp.asarray(tids), k=64,
                           score_bound=5, method=method)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ----------------------------------------------------------------------
# accumulate_scores
# ----------------------------------------------------------------------

def test_accumulate_scores_matches_host_scatter():
    """Device scatter-add over gathered blocks == query_index counts in
    original row order, summed across subsets."""
    rng = np.random.default_rng(3)
    x = rng.normal(0, 1, (1000, 4)).astype(np.float32)   # padded tail block
    idx = build_index(x, np.arange(4), block=128)
    centers = x[rng.integers(0, len(x), 3)]
    bs = BoxSet((centers - 0.4).astype(np.float32),
                (centers + 0.4).astype(np.float32), np.arange(4))
    want, _ = query_index(idx, bs)

    rows3, zlo, zhi = idx.device_arrays()
    onehot = jnp.ones((3, 1), jnp.float32)
    counts, cand, n_hit = kops.fused_query(
        rows3, zlo, zhi, jnp.asarray(bs.lo), jnp.asarray(bs.hi), onehot,
        capacity=idx.n_blocks)
    scores = jnp.zeros((idx.n_rows, 1), jnp.int32)
    scores = kops.accumulate_scores(scores, counts, cand, n_hit,
                                    idx.device_gids())
    # accumulation is additive: a second pass doubles every count
    twice = kops.accumulate_scores(scores, counts, cand, n_hit,
                                   idx.device_gids())
    np.testing.assert_array_equal(np.asarray(scores)[:, 0], want)
    np.testing.assert_array_equal(np.asarray(twice)[:, 0], 2 * want)


def _accumulate_case(case, rng):
    """(buffer rows, [NB, block] grid of buffer rows with -1 padding,
    live-row mask or None) for one kind of index."""
    x = rng.normal(0, 1, (700, 3)).astype(np.float32)
    dims = np.arange(3)
    if case in ("static", "fill_slots", "full_capacity", "overflow"):
        idx = build_index(x, dims, block=64)               # ragged tail
        return idx.n_rows, np.asarray(idx.device_gids()), None
    if case == "segmented":
        # a base and two delta segments over global ids, tombstones on
        segs = [build_index(x[a:b], dims, block=64)
                for a, b in ((0, 500), (500, 620), (620, 700))]
        segx = SegmentedZoneMapIndex(dims, segs,
                                     np.array([0, 500, 620, 700]), 64)
        valid = (rng.random(700) > 0.2).astype(np.int32)
        return 700, np.asarray(segx.device_gids()), valid
    # flat sharded: 5 shards over 7 rows -> 2, 2, 2, 1 (ragged), 0 (empty)
    sx = build_sharded_index(x[:7], dims, 5, block=2)
    assert list(sx.shard_rows) == [2, 2, 2, 1, 0]
    nlm = sx.n_loc_max
    # the ceil-split puts every non-empty shard's first global id at
    # shard * Nloc_max: a global id IS its row of the flat buffer
    occupied = sx.shard_rows > 0
    np.testing.assert_array_equal(sx.offsets[:-1][occupied],
                                  (np.arange(5) * nlm)[occupied])
    g = np.asarray(sx.device_gids())
    return 5 * nlm, g.reshape(-1, g.shape[-1]), None


ACCUMULATE_CASES = ["static", "segmented", "flat_sharded", "fill_slots",
                    "full_capacity", "overflow"]


@pytest.mark.parametrize("case", ACCUMULATE_CASES)
def test_accumulate_scores_matches_add_at_oracle(case):
    """The survivor-sized scatter-add equals np.add.at by row id, over
    two subsets into one buffer: fill slots past n_live (block 0 again,
    as nonzero pads) and grid padding add nothing, n_live == 0 (an
    overflowed attempt) adds nothing, and capacity == n_blocks works."""
    rng = np.random.default_rng(ACCUMULATE_CASES.index(case))
    n, grid, valid = _accumulate_case(case, rng)
    nb, block = grid.shape
    q = 2
    want = np.zeros((n, q), np.int64)
    scores = jnp.zeros((n, q), jnp.int32)
    for _ in range(2):
        if case == "full_capacity":
            c = n_live = nb
        else:
            c = max(nb // 2, 1)
            n_live = c - 3 if case == "fill_slots" else c
        hit = np.sort(rng.choice(nb, n_live, replace=False))
        cand = np.concatenate([hit, np.zeros(c - n_live, np.int64)])
        counts = rng.integers(0, 5, (c, block, q)).astype(np.int32)
        if case == "overflow":
            n_live = 0
        scores = kops.accumulate_scores(
            scores, jnp.asarray(counts), jnp.asarray(cand, jnp.int32),
            jnp.int32(n_live), jnp.asarray(grid))
        ids = grid[cand[:n_live]]                          # [n_live, block]
        real = ids >= 0
        np.add.at(want, ids[real], counts[:n_live][real])
    got = np.asarray(scores)
    if valid is not None:
        got = np.asarray(mask_tombstones(scores, jnp.asarray(valid)))
        want = want * valid[:, None]
    np.testing.assert_array_equal(got, want)
    if case == "overflow":
        assert not got.any()


def _index_ops(jaxpr):
    """(primitive name, number of indices) of every gather and scatter,
    sub-jaxprs included."""
    out = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "gather" or name.startswith("scatter"):
            shape = eqn.invars[1].aval.shape
            out.append((name, int(np.prod(shape[:-1]))))
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)          # ClosedJaxpr
                if hasattr(sub, "eqns"):
                    out.extend(_index_ops(sub))
    return out


def test_accumulate_cost_follows_capacity():
    """No gather or scatter of the accumulate carries more than C * block
    indices: at N = 65,536 rows and C = 8 blocks of 1024 the work is the
    8 grid rows and the 8,192 gathered rows, not the catalog."""
    n, block, c, q = 65_536, 1024, 8, 1
    jaxpr = jax.make_jaxpr(kops.accumulate_scores)(
        jnp.zeros((n, q), jnp.int32), jnp.zeros((c, block, q), jnp.int32),
        jnp.zeros((c,), jnp.int32), jnp.int32(c),
        jnp.zeros((n // block, block), jnp.int32))
    ops = _index_ops(jaxpr.jaxpr)
    assert ops and max(k for _, k in ops) <= c * block, ops
    assert ("scatter-add", c * block) in ops, ops


@pytest.mark.parametrize("kind", ["static", "live", "sharded"])
def test_dense_engine_counts_accumulate_rows(catalog, kind):
    """``accumulate_rows`` counts C * block rows per accepted subset —
    the gathered blocks' rows, so blocks_gathered * block when nothing
    retried — and ``accumulate_share`` puts them over n rows a subset."""
    feats, labels = catalog
    kw = {"live": kind == "live", "n_shards": 2 if kind == "sharded" else 1}
    eng = SearchEngine(feats, n_subsets=6, subset_dim=6, block=64, seed=0,
                       score_mode="dense", capacity_frac=1.0, **kw)
    pos, neg = _query_sets(labels, 1)
    st = eng.query(pos, neg, model="dbranch", max_results=20).stats
    assert st["retried_subsets"] == 0
    assert st["accumulate_rows"] == st["blocks_gathered"] * 64 > 0
    # every subset's index has the same blocks: blocks_total counts them
    # once per subset the query ran
    ix = eng._view().indexes[0]
    n_sub = st["blocks_total"] // (ix.total_blocks if kind == "sharded"
                                   else ix.n_blocks)
    assert n_sub >= 1
    assert st["accumulate_share"] == pytest.approx(
        st["accumulate_rows"] / (eng.n * n_sub))


# ----------------------------------------------------------------------
# engine: ranked path == host oracle; overflow retry; tie-breaks
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine_and_labels(catalog):
    feats, labels = catalog
    eng = SearchEngine(feats, n_subsets=10, subset_dim=6, block=128, seed=0)
    return eng, labels


def _query_sets(labels, cls, n_pos=12, n_neg=50, seed=0):
    rng = np.random.default_rng(seed)
    pos = rng.choice(np.nonzero(labels == cls)[0], n_pos, replace=False)
    neg = rng.choice(np.nonzero(labels != cls)[0], n_neg, replace=False)
    return pos, neg


@pytest.mark.parametrize("model,seed", [("dbranch", 0), ("dbranch", 1),
                                        ("dbens", 2)])
def test_engine_ranked_equals_host_oracle(engine_and_labels, model, seed):
    """max_results >= n_found: device ranking returns the IDENTICAL id and
    score sequence as the host _rank oracle (ties included)."""
    eng, labels = engine_and_labels
    pos, neg = _query_sets(labels, 2, seed=seed)
    kw = dict(n_models=5) if model == "dbens" else {}
    host = eng.query(pos, neg, model=model, **kw)
    dev = eng.query(pos, neg, model=model, max_results=eng.n, **kw)
    np.testing.assert_array_equal(dev.ids, host.ids)
    np.testing.assert_array_equal(dev.scores, host.scores)
    # and the truncated variant is the exact prefix
    k = max(1, host.n_found // 2)
    trunc = eng.query(pos, neg, model=model, max_results=k, **kw)
    np.testing.assert_array_equal(trunc.ids, host.ids[:k])
    np.testing.assert_array_equal(trunc.scores, host.scores[:k])


def test_engine_ranked_tie_break_with_duplicate_rows():
    """Duplicate feature rows => identical scores for whole row groups;
    device top-k order must still equal the host stable sort exactly."""
    rng = np.random.default_rng(5)
    base = rng.normal(0, 1, (40, 12)).astype(np.float32)
    x = np.tile(base, (25, 1))                      # 1000 rows, 25x ties
    eng = SearchEngine(x, n_subsets=6, subset_dim=4, block=64, seed=1)
    pos, neg = list(range(5)), list(range(600, 640))
    host = eng.query(pos, neg, model="dbranch")
    dev = eng.query(pos, neg, model="dbranch", max_results=eng.n)
    assert host.n_found > 0
    np.testing.assert_array_equal(dev.ids, host.ids)
    np.testing.assert_array_equal(dev.scores, host.scores)


def test_engine_overflow_retry_is_exact_and_minimal(catalog):
    """capacity_frac small enough to overflow: the deferred-sync path must
    (a) return counts/ids bitwise-identical to the query_index host path,
    (b) retry ONLY the subsets whose survivors exceeded their capacity,
    (c) resolve in one extra round (one extra host sync)."""
    feats, labels = catalog
    eng = SearchEngine(feats, n_subsets=8, subset_dim=6, block=128, seed=0,
                       capacity_frac=0.01)          # cap = 1 block
    pos, neg = _query_sets(labels, 2, seed=4)
    # snapshot the cold-start capacities BEFORE querying: the deferred
    # sync feeds survivor hints back into _initial_capacity afterwards
    cold_caps = {ix.subset_id: eng._initial_capacity(ix)
                 for ix in eng.indexes}
    res = eng.query(pos, neg, model="dbens", n_models=6)

    # oracle: same boxes through the host query_index path
    boxsets = eng._fit_boxes("dbens", eng.x[pos], eng.x[neg],
                             max_depth=12, n_models=6, seed=0)
    jobs, _ = eng._make_jobs([(bs, 0) for bs in boxsets], 1)
    counts = np.zeros(eng.n, np.int64)
    expected_overflows = 0
    for sid, merged, _ in jobs:
        c, st = query_index(eng.indexes[sid], merged)
        counts += c
        if st["blocks_touched"] > cold_caps[sid]:
            expected_overflows += 1
    assert expected_overflows > 0, "test needs at least one overflow"
    want_ids, want_scores = _host_rank(
        counts, np.concatenate([pos, neg]))
    np.testing.assert_array_equal(res.ids, want_ids)
    np.testing.assert_array_equal(res.scores, want_scores)
    # only the overflowed subsets were re-run, in one extra round
    assert res.stats["retried_subsets"] == expected_overflows
    assert res.stats["n_host_syncs"] == 2

    # no overflow => exactly ONE deferred sync for the whole query
    eng_big = SearchEngine(feats, n_subsets=8, subset_dim=6, block=128,
                           seed=0, capacity_frac=1.0)
    res_big = eng_big.query(pos, neg, model="dbens", n_models=6)
    assert res_big.stats["n_host_syncs"] == 1
    assert res_big.stats["retried_subsets"] == 0
    np.testing.assert_array_equal(res_big.ids, want_ids)


def test_query_batch_stats_are_batch_namespaced(engine_and_labels):
    eng, labels = engine_and_labels
    reqs = []
    for i in range(3):
        pos, neg = _query_sets(labels, 2, seed=20 + i)
        reqs.append({"pos_ids": pos, "neg_ids": neg, "model": "dbranch"})
    outs = eng.query_batch(reqs)
    for o in outs:
        # batch-wide aggregates are namespaced; none leak un-prefixed
        for key in ("bytes_touched", "blocks_touched", "bytes_saved_frac",
                    "n_range_queries", "host_bytes_transferred"):
            assert key not in o.stats
            assert f"batch_{key}" in o.stats
        assert o.stats["path"] == "index"
        assert o.stats["batch_size"] == 3
        assert o.stats["n_boxes"] >= 1          # per-request figure
    # batch aggregates identical across the batch (shared device phase)
    assert outs[0].stats["batch_bytes_touched"] == \
        outs[1].stats["batch_bytes_touched"]


def test_query_batch_ranked_matches_sequential_ranked(engine_and_labels):
    eng, labels = engine_and_labels
    reqs = []
    for i in range(3):
        pos, neg = _query_sets(labels, 2, seed=30 + i)
        reqs.append({"pos_ids": pos, "neg_ids": neg, "model": "dbranch",
                     "max_results": 25})
    outs = eng.query_batch(reqs)
    for o, r in zip(outs, reqs):
        seq = eng.query(r["pos_ids"], r["neg_ids"], model="dbranch",
                        max_results=25)
        np.testing.assert_array_equal(o.ids, seq.ids)
        np.testing.assert_array_equal(o.scores, seq.scores)
        assert o.n_found <= 25
    # ranked batch moves O(k), not O(N): well under one score vector
    assert outs[0].stats["batch_host_bytes_transferred"] < 4 * eng.n


def test_server_plumbs_max_results(engine_and_labels):
    from repro.serve.engine import QueryRequest, QueryServer
    eng, labels = engine_and_labels
    srv = QueryServer(eng, max_results=10)
    pos, neg = _query_sets(labels, 2, seed=40)
    resp = srv.handle(QueryRequest(0, pos, neg, "dbranch"))
    assert resp.ok and resp.result.n_found <= 10
    full = eng.query(pos, neg, model="dbranch")
    np.testing.assert_array_equal(resp.result.ids, full.ids[:10])
    # per-request kwargs override the serving default
    resp3 = srv.handle(QueryRequest(1, pos, neg, "dbranch",
                                    kwargs={"max_results": 3}))
    assert resp3.result.n_found <= 3
    assert srv.stats["host_bytes"] > 0
    # batched window: ranked end to end, host_bytes counted once
    before = srv.stats["host_bytes"]
    reqs = [QueryRequest(i, *_query_sets(labels, 2, seed=50 + i), "dbranch")
            for i in range(3)]
    resps = srv.handle_batch(reqs)
    assert all(r.ok and r.result.n_found <= 10 for r in resps)
    batch_bytes = resps[0].result.stats["batch_host_bytes_transferred"]
    assert srv.stats["host_bytes"] == before + batch_bytes


# ----------------------------------------------------------------------
# morton_code: single argsort + inverse == the old double argsort
# ----------------------------------------------------------------------

@pytest.mark.parametrize("seed,n,d", [(0, 1000, 4), (1, 257, 7)])
def test_morton_single_argsort_matches_double(seed, n, d):
    rng = np.random.default_rng(seed)
    x = rng.normal(0, 1, (n, d)).astype(np.float32)
    x[rng.integers(0, n, n // 4)] = x[0]            # ties exercise stability

    def morton_double_argsort(x, nbits=8):
        from repro.core.index import _part_bits
        n, d = x.shape
        nbits = min(nbits, 64 // max(d, 1))
        code = np.zeros(n, np.uint64)
        levels = 1 << nbits
        for j in range(d):
            ranks = np.argsort(np.argsort(x[:, j], kind="stable"),
                               kind="stable")
            q = (ranks * levels // max(n, 1)).astype(np.uint64)
            code |= _part_bits(q, d, nbits) << j
        return code

    np.testing.assert_array_equal(morton_code(x),
                                  morton_double_argsort(x))
