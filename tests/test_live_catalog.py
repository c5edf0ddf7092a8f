"""Live catalog ingestion (ISSUE 5 / DESIGN.md §12).

Contracts pinned here:
  * MONOLITHIC PARITY: at every point of an append/delete/compact
    schedule, ranked ids AND scores of the segmented engine are bitwise
    those of a fresh monolithic ``build_index`` engine over the
    surviving rows (ids mapped through the — monotone — live-id list, so
    tie-breaks at the k-th score agree too), on both the device-ranked
    (max_results=k) and host-ranked (max_results=None) paths, including
    ragged tail segments and duplicate-row kth-score ties;
  * tombstoned rows NEVER surface: masked on the finished score buffer
    (the dense buffer's once-a-query mask), dead in knn, dead on
    the scan path;
  * global ids are append-ordered and stable forever — refine() across
    an append keeps referring to the same rows;
  * snapshot/epoch discipline: compaction swaps atomically, epochs tag
    capacity hints so nothing sized for one geometry leaks into the
    next;
  * honest stats: per-segment refined-block attribution partitions the
    global figure exactly (no double-count across the virtual block
    space), live/tombstone counts are reported, segment bytes sum;
  * the QueryServer ingest path interleaves with query windows and
    counts its traffic.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import knn as knn_mod
from repro.core.engine import SearchEngine
from repro.core.segments import SegmentedCatalog, mask_tombstones
from repro.kernels import ops as kops
from repro.serve.engine import IngestRequest, QueryRequest, QueryServer

ENG = dict(n_subsets=4, subset_dim=4, block=64)


def _data(n=700, extra=300, d=16, seed=0, ties=True):
    rng = np.random.default_rng(seed)
    x = rng.normal(0, 1, (n + extra, d)).astype(np.float32)
    if ties:
        x[50:60] = x[40:50]          # duplicate rows -> kth-score ties
    return x[:n], x[n:]


def _labels(n_pos=12, n_neg=60):
    return list(range(n_pos)), list(range(100, 100 + n_neg))


def _mono(x_all, live_ids, pos, neg, k, **kw):
    """The oracle: a fresh monolithic engine over ONLY the surviving
    rows; result ids mapped back to global through the live-id list."""
    eng = SearchEngine(x_all[live_ids], **ENG, **kw)
    pc = np.searchsorted(live_ids, pos)
    nc = np.searchsorted(live_ids, neg)
    res = eng.query(pc, nc, model="dbranch", max_results=k)
    return live_ids[res.ids], res.scores


def _live_ids(engine):
    return np.nonzero(engine._catalog.snapshot().valid_host)[0]


def _assert_parity(engine, x_all, pos, neg, k):
    live_ids = _live_ids(engine)
    res = engine.query(pos, neg, model="dbranch", max_results=k)
    ids_m, sc_m = _mono(x_all, live_ids, pos, neg, k)
    np.testing.assert_array_equal(res.ids, ids_m)
    np.testing.assert_array_equal(res.scores, sc_m)
    return res


# ----------------------------------------------------------------------
# lifecycle parity (seeded)
# ----------------------------------------------------------------------

def test_append_then_delete_then_compact_parity():
    base, extra = _data()
    x_all = np.concatenate([base, extra])
    pos, neg = _labels()
    eng = SearchEngine(base, **ENG, live=True)

    _assert_parity(eng, base, pos, neg, 50)

    ids = eng.append(extra)                      # ragged delta (300 % 64)
    assert ids[0] == len(base) and len(ids) == len(extra)
    assert eng.index_stats()["n_segments"] == 2
    res = _assert_parity(eng, x_all, pos, neg, 50)

    # tombstone top hits + a delta row; never a training id
    dele = [int(i) for i in res.ids[:5]] + [int(ids[3])]
    dele = [i for i in dele if i not in pos + neg]
    nd = eng.delete(dele)
    assert nd == len(set(dele))
    res = _assert_parity(eng, x_all, pos, neg, 50)
    assert not np.intersect1d(res.ids, dele).size

    st = eng.compact()
    assert not st["skipped"] and st["merged_segments"] == 2
    assert eng.index_stats()["n_segments"] == 1
    res2 = _assert_parity(eng, x_all, pos, neg, 50)
    np.testing.assert_array_equal(res.ids, res2.ids)
    np.testing.assert_array_equal(res.scores, res2.scores)


def test_host_rank_path_and_oracle_engine_parity():
    """max_results=None (host ranking from one buffer transfer) and the
    all-oracle engine (use_fused=False -> per-segment query_index) agree
    with the fused device path after an append + delete."""
    base, extra = _data(ties=False)
    x_all = np.concatenate([base, extra])
    pos, neg = _labels()
    eng = SearchEngine(base, **ENG, live=True)
    eng.append(extra)
    eng.delete([500, 710, 711])
    dev = eng.query(pos, neg, model="dbranch", max_results=80)
    host = eng.query(pos, neg, model="dbranch", max_results=None)
    np.testing.assert_array_equal(dev.ids, host.ids[:80])
    oracle = SearchEngine(base, **ENG, live=True, use_fused=False,
                          use_jax_fit=False)
    oracle.append(extra)
    oracle.delete([500, 710, 711])
    ores = oracle.query(pos, neg, model="dbranch", max_results=None)
    np.testing.assert_array_equal(host.ids, ores.ids)
    np.testing.assert_array_equal(host.scores, ores.scores)


def test_query_batch_parity_and_generation_tagged_hints():
    base, extra = _data(ties=False)
    x_all = np.concatenate([base, extra])
    eng = SearchEngine(base, **ENG, live=True)
    reqs = [{"pos_ids": list(range(i, i + 10)),
             "neg_ids": list(range(200, 260)),
             "model": "dbranch", "max_results": 40} for i in (0, 20)]
    eng.query_batch(reqs)            # warm + populate generation-0 hints
    gen0_keys = set(eng._cap_hints)
    assert gen0_keys and all(k[0] == 0 for k in gen0_keys)
    eng.append(extra)
    # appends/deletes only EXTEND/overlay the geometry: hints survive
    # (a steady ingest workload must not re-pay cold-start capacities)
    assert gen0_keys <= set(eng._cap_hints)
    eng.delete([650])
    assert gen0_keys <= set(eng._cap_hints)
    outs = eng.query_batch(reqs)
    live_ids = _live_ids(eng)
    for req, out in zip(reqs, outs):
        ids_m, sc_m = _mono(x_all, live_ids, req["pos_ids"],
                            req["neg_ids"], 40)
        np.testing.assert_array_equal(out.ids, ids_m)
        np.testing.assert_array_equal(out.scores, sc_m)
    # compaction REPLACES the geometry: generation-0 hints are void and
    # pruned — no leakage into the re-sorted block space
    eng.compact()
    assert all(k[0] == 1 for k in eng._cap_hints)
    eng.query_batch(reqs)
    assert any(k[0] == 1 for k in eng._cap_hints)


def test_hint_pruning_across_generations_at_large_delta_fraction():
    """Capacity-hint pruning under heavy ingest (ISSUE 6 satellite): a
    catalog whose deltas dominate the base (delta fraction > 50%) run
    through TWO compaction generations. Hints must be (re)learned per
    generation, pruned the moment their geometry dies, and the table
    must never accumulate keys from dead generations — while ranked
    parity with the monolithic oracle holds at every step."""
    rng = np.random.default_rng(9)
    base = rng.normal(0, 1, (400, 16)).astype(np.float32)
    d1 = rng.normal(0, 1, (500, 16)).astype(np.float32)
    d2 = rng.normal(0, 1, (400, 16)).astype(np.float32)
    x_all = np.concatenate([base, d1, d2])
    pos, neg = _labels()
    eng = SearchEngine(base, **ENG, live=True)
    eng.query(pos, neg, model="dbranch", max_results=40)

    eng.append(d1)                       # delta fraction 500/900
    eng.delete([700, 705])
    eng.query(pos, neg, model="dbranch", max_results=40)
    keys_g0 = set(eng._cap_hints)
    assert keys_g0 and all(k[0] == 0 for k in keys_g0)
    _assert_parity(eng, np.concatenate([base, d1]), pos, neg, 40)

    eng.compact()                        # generation 1: gen-0 keys die
    assert all(k[0] == 1 for k in eng._cap_hints)
    eng.append(d2)                       # delta fraction 400/1300 on gen 1
    eng.query(pos, neg, model="dbranch", max_results=40)
    assert eng._cap_hints and all(k[0] == 1 for k in eng._cap_hints)
    _assert_parity(eng, x_all, pos, neg, 40)

    eng.compact()                        # generation 2: gen-1 keys die
    assert all(k[0] == 2 for k in eng._cap_hints)
    eng.query(pos, neg, model="dbranch", max_results=40)
    assert eng._cap_hints and all(k[0] == 2 for k in eng._cap_hints)
    # the table holds exactly ONE live generation — no leakage, bounded
    # growth on a long-running server
    assert len({k[0] for k in eng._cap_hints}) == 1
    _assert_parity(eng, x_all, pos, neg, 40)


def test_refine_id_stability_across_append():
    """Paper §5 refinement across an ingest: extra labels found BEFORE an
    append keep identifying the same rows after it (global ids are
    append-ordered and stable), and the refined result equals the
    monolithic engine over the grown catalog."""
    base, extra = _data(ties=False)
    x_all = np.concatenate([base, extra])
    pos, neg = _labels()
    eng = SearchEngine(base, **ENG, live=True)
    first = eng.query(pos, neg, model="dbranch", max_results=30)
    extra_pos = [int(first.ids[0])]
    extra_neg = [int(first.ids[-1])]
    eng.append(extra)
    ref = eng.refine(first, extra_pos, extra_neg, pos, neg, max_results=30)
    ids_m, sc_m = _mono(x_all, np.arange(len(x_all)), pos + extra_pos,
                        neg + extra_neg, 30)
    np.testing.assert_array_equal(ref.ids, ids_m)
    np.testing.assert_array_equal(ref.scores, sc_m)


def test_scan_and_knn_paths_respect_tombstones():
    base, extra = _data(ties=False)
    pos, neg = _labels()
    eng = SearchEngine(base, **ENG, live=True)
    ids = eng.append(extra)
    probe = eng.query(pos, neg, model="dtree", max_results=None)
    dele = [int(i) for i in probe.ids[:3]] + [int(ids[0])]
    eng.delete(dele)
    for model in ("dtree", "knn"):
        res = eng.query(pos, neg, model=model, max_results=None)
        assert not np.intersect1d(res.ids, dele).size, model


def test_knn_segmented_matches_bruteforce_over_live_rows():
    base, extra = _data(ties=False)
    x_all = np.concatenate([base, extra])
    eng = SearchEngine(base, **ENG, live=True)
    eng.append(extra)
    eng.delete(list(range(60, 90)) + [701, 702])
    snap = eng._catalog.snapshot()
    live_ids = np.nonzero(snap.valid_host)[0]
    queries = x_all[[5, 300, 720]]
    k = 25
    ids_k, d_k = knn_mod.knn_subset(snap.indexes[0], queries, k=k,
                                    live=snap.valid_host)
    dims = snap.indexes[0].dims
    xl = x_all[live_ids][:, dims]
    qd = ((xl[None, :, :] - queries[:, None, dims]) ** 2).sum(-1)
    order = np.lexsort(
        (np.broadcast_to(live_ids, qd.shape), qd), axis=1)[:, :k]
    np.testing.assert_array_equal(ids_k, live_ids[order])


# ----------------------------------------------------------------------
# parity under ARBITRARY schedules (seeded always; hypothesis when
# available widens the net)
# ----------------------------------------------------------------------

def _run_schedule(seed: int, n0: int, ops):
    """Drive one append/delete/compact schedule and assert monolithic
    parity (ids AND scores, device-ranked path) after EVERY op."""
    rng = np.random.default_rng(seed)
    d = 10
    x_all = rng.normal(0, 1, (n0 + 4 * 80, d)).astype(np.float32)
    x_all[30:36] = x_all[24:30]            # kth-score tie fodder
    pos = list(rng.choice(n0 // 2, 8, replace=False))
    neg = [int(v) for v in
           rng.choice(np.arange(n0 // 2, n0), 30, replace=False)]
    eng = SearchEngine(x_all[:n0], **ENG, live=True)
    cursor = n0
    for op in ops:
        if op == "append":
            m = int(rng.integers(1, 80))   # ragged tails (m % 64)
            eng.append(x_all[cursor:cursor + m])
            cursor += m
        elif op == "delete":
            snap = eng._catalog.snapshot()
            cand = np.nonzero(snap.valid_host)[0]
            cand = cand[~np.isin(cand, pos + neg)]
            if len(cand) > 20:
                eng.delete(rng.choice(cand, 15, replace=False))
        else:
            eng.compact()
        live_ids = _live_ids(eng)
        res = eng.query(pos, neg, model="dbranch", max_results=25)
        ids_m, sc_m = _mono(x_all[:cursor], live_ids, pos, neg, 25)
        np.testing.assert_array_equal(res.ids, ids_m)
        np.testing.assert_array_equal(res.scores, sc_m)


@pytest.mark.parametrize("seed,ops", [
    (1, ["append", "delete", "append", "compact"]),
    (2, ["delete", "compact", "append"]),
    (3, ["append", "append", "append", "delete", "compact", "delete"]),
])
def test_schedule_parity_seeded(seed, ops):
    _run_schedule(seed, 200 + 13 * seed, ops)


def test_schedule_parity_hypothesis():
    pytest.importorskip(
        "hypothesis",
        reason="dev dependency (pip install -r requirements-dev.txt)")
    from hypothesis import given, settings, strategies as st

    @st.composite
    def schedules(draw):
        seed = draw(st.integers(0, 2 ** 31 - 1))
        n0 = draw(st.integers(150, 300))
        ops = draw(st.lists(
            st.sampled_from(["append", "delete", "compact"]),
            min_size=1, max_size=4))
        return seed, n0, ops

    @settings(max_examples=8, deadline=None)
    @given(schedules())
    def run(sched):
        _run_schedule(*sched)

    run()


# ----------------------------------------------------------------------
# honest stats + masked kernels
# ----------------------------------------------------------------------

def test_segment_stats_honest_accounting():
    base, extra = _data(ties=False)
    pos, neg = _labels()
    eng = SearchEngine(base, **ENG, live=True)
    ids = eng.append(extra)
    eng.delete(ids[:10])
    st = eng.index_stats()
    assert st["live"] and st["n_segments"] == 2
    assert st["rows_live"] == len(base) + len(extra) - 10
    assert st["rows_tombstoned"] == 10
    segs = st["segments"]
    # per-segment rows/bytes partition the catalog exactly
    assert sum(s["rows"] for s in segs) == st["rows"]
    assert sum(s["rows_tombstoned"] for s in segs) == 10
    assert sum(s["bytes"] for s in segs) == st["index_bytes"]
    # fused stats: per-segment refined blocks partition the global
    # figure over the virtual block space — no double-count
    res = eng.query(pos, neg, model="dbranch", max_results=40)
    qs = res.stats
    assert qs["n_segments"] == 2
    assert qs["rows_live"] == st["rows_live"]
    assert qs["rows_tombstoned"] == 10
    per_seg = qs["per_segment_blocks_touched"]
    assert len(per_seg) == 2 and sum(per_seg) == qs["blocks_touched"]
    assert qs["blocks_gathered"] >= qs["blocks_touched"]


def test_masked_accumulate_and_rank_under_tombstones():
    """Kernel-level: the scatter-add by row id, then the once-a-query
    tombstone mask (mask_tombstones), zeroes exactly the tombstoned
    rows' scores, and rank_topk with the query's score_bound stays exact
    down to the all-dead edge (n_valid == 0)."""
    rng = np.random.default_rng(0)
    n, block, nb, q = 256, 32, 8, 3
    counts = jnp.asarray(rng.integers(0, 5, (nb, block, q)), jnp.int32)
    cand = jnp.arange(nb, dtype=jnp.int32)
    grid = rng.permutation(n).astype(np.int32).reshape(nb, block)
    valid = rng.integers(0, 2, n).astype(np.int32)
    base = np.asarray(kops.accumulate_scores(
        jnp.zeros((n, q), jnp.int32), counts, cand, jnp.int32(nb),
        jnp.asarray(grid)))
    want = np.zeros((n, q), np.int32)
    want[grid.reshape(-1)] = np.asarray(counts).reshape(n, q)
    np.testing.assert_array_equal(base, want)
    masked = np.asarray(mask_tombstones(jnp.asarray(base),
                                        jnp.asarray(valid)))
    np.testing.assert_array_equal(masked, base * valid[:, None])
    # ranking the masked buffer never surfaces a dead row, for every
    # rank method, with the true score bound
    bound = int(base.max())
    tids = jnp.full((q, 4), n, jnp.int32)
    for method in ("threshold", "sort", "topk"):
        ids_k, sc_k, nv = kops.rank_topk(
            jnp.asarray(masked.T), tids, k=16, score_bound=bound,
            method=method)
        ids_k = np.asarray(ids_k)
        assert not np.isin(ids_k[ids_k >= 0],
                           np.nonzero(valid == 0)[0]).any(), method
    # all-dead edge: every query comes back empty, no crash
    ids_k, sc_k, nv = kops.rank_topk(
        jnp.zeros((q, n), jnp.int32), tids, k=16, score_bound=bound)
    assert (np.asarray(nv) == 0).all() and (np.asarray(ids_k) == -1).all()


# ----------------------------------------------------------------------
# composition + lifecycle edges
# ----------------------------------------------------------------------

def test_live_with_shards_flat_fallback_parity():
    """n_shards > 1 composition (flat fallback): the base is ceil-split
    into per-shard segments, deltas land on per-shard tails, and results
    stay bitwise the monolithic oracle's."""
    base, extra = _data(ties=False)
    x_all = np.concatenate([base, extra])
    pos, neg = _labels()
    eng = SearchEngine(base, **ENG, live=True, n_shards=2)
    assert eng.index_stats()["n_segments"] == 2      # ceil-split base
    eng.append(extra[:100])
    eng.append(extra[100:])
    shards = [s["shard"] for s in eng.index_stats()["segments"]]
    assert sorted(set(shards)) == [0, 1]             # per-shard tails
    _assert_parity(eng, x_all, pos, neg, 50)


def test_background_compact_swaps_atomically():
    base, extra = _data(ties=False)
    x_all = np.concatenate([base, extra])
    pos, neg = _labels()
    eng = SearchEngine(base, **ENG, live=True)
    eng.append(extra)
    before = eng.query(pos, neg, model="dbranch", max_results=50)
    t = eng.compact(background=True)
    t.join(timeout=30)
    assert not t.is_alive()
    assert eng.index_stats()["n_segments"] == 1
    after = _assert_parity(eng, x_all, pos, neg, 50)
    np.testing.assert_array_equal(before.ids, after.ids)


def test_lifecycle_guards():
    base, extra = _data(ties=False)
    static = SearchEngine(base, **ENG)
    with pytest.raises(RuntimeError, match="live=True"):
        static.append(extra)
    eng = SearchEngine(base, **ENG, live=True)
    with pytest.raises(ValueError, match="width"):
        eng.append(extra[:, :4])
    with pytest.raises(ValueError, match="range"):
        eng.delete([len(base) + 5])
    assert eng.append(extra[:0]).size == 0           # no-op, no epoch
    assert eng.index_stats()["epoch"] == 0
    assert eng.delete([]) == 0
    assert eng.delete([3, 3, 3]) == 1                # idempotent dedup
    assert eng.delete([3]) == 0
    assert eng.compact()["skipped"]                  # single segment


def test_catalog_snapshot_isolation():
    """An in-flight reader's snapshot is untouched by later mutations —
    the epoch discipline at the SegmentedCatalog level."""
    base, extra = _data(ties=False)
    cat = SegmentedCatalog(base, SearchEngine(base, **ENG).subsets,
                           block=64)
    snap0 = cat.snapshot()
    cat.append(extra)
    cat.delete([0, 1])
    cat.compact()
    assert snap0.epoch == 0 and snap0.n == len(base)
    assert snap0.valid_host.all()
    assert len(snap0.segments) == 1
    assert cat.snapshot().epoch == 3
    assert cat.snapshot().n == len(base) + len(extra)


# ----------------------------------------------------------------------
# serving: ingest interleaves with query windows
# ----------------------------------------------------------------------

def test_server_ingest_interleaves_with_queries():
    base, extra = _data(ties=False)
    x_all = np.concatenate([base, extra])
    pos, neg = _labels()
    eng = SearchEngine(base, **ENG, live=True)
    server = QueryServer(eng, max_batch=4, batch_window_s=0.01,
                         max_results=40)
    server.start()
    try:
        q0 = server.submit(QueryRequest(0, pos, neg))
        a1 = server.submit(IngestRequest(1, "append", features=extra))
        q2 = server.submit(QueryRequest(2, pos, neg))
        r0, ra, r2 = q0.get(timeout=30), a1.get(timeout=30), \
            q2.get(timeout=30)
        assert r0.ok and ra.ok and r2.ok
        assert ra.info["op"] == "append" and ra.info["rows"] == len(extra)
        # the post-ingest query sees the grown catalog
        ids_m, _ = _mono(x_all, np.arange(len(x_all)), pos, neg, 40)
        np.testing.assert_array_equal(r2.result.ids, ids_m)
        rd = server.submit(IngestRequest(3, "delete",
                                         ids=[int(ids_m[0])])).get(30)
        assert rd.ok and rd.info["rows"] == 1
        # compaction is dispatched OFF the serving loop (queries keep
        # flowing on the old snapshot) — the ack returns immediately and
        # the swap lands when the background merge finishes
        rc = server.submit(IngestRequest(4, "compact")).get(30)
        assert rc.ok and rc.info["background"]
        server._compact_thread.join(timeout=30)
        assert eng.index_stats()["n_segments"] == 1
        bad = server.submit(IngestRequest(5, "garble")).get(30)
        assert not bad.ok
        s = server.summary()
        assert s["ingests"] == 4 and s["ingest_errors"] == 1
        assert s["rows_appended"] == len(extra)
        assert s["rows_deleted"] == 1 and s["compactions"] == 1
        assert s["live"] and s["epoch"] == 3
        assert s["served"] == 2 and s["errors"] == 0
    finally:
        server.close()
