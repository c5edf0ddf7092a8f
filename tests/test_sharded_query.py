"""Sharded catalog serving (ISSUE 4 / DESIGN.md §11).

Contracts pinned here:
  * SHARD-COUNT INVARIANCE: ranked ids AND scores are bitwise-identical
    for n_shards in {1, 2, 4, 8} — including ragged last shards, empty
    tail shards, and boxes whose row matches straddle shard boundaries —
    and identical to the single-device path and the host oracle;
  * the device-side cross-shard merge (kernels/ops.shard_local_topk +
    merge_topk) reproduces the host oracle merge_shard_results EXACTLY,
    including ties at the global k-th score (descending score, ascending
    GLOBAL id);
  * global ids survive the local->global id remap for any partition
    (hypothesis property);
  * ranked host traffic stays FLAT as shards grow (O(k), not O(S));
  * the deferred overflow retry stays exact on the sharded path.

The suite runs on any device count: with >= n_shards devices the engine
shard_maps across a "shards" mesh, otherwise it runs the same per-shard
program under vmap — both modes must (and do) return the same bits. The
CI tier-1 leg re-runs everything under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the mesh mode
is exercised for real.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.boxes import BoxSet, boxes_contain
from repro.core.engine import QueryResult, SearchEngine
from repro.core.index import (build_index, build_sharded_index,
                              query_index, query_index_sharded,
                              shard_offsets)
from repro.kernels import ops as kops
from repro.serve.engine import merge_shard_results

SHARD_COUNTS = (1, 2, 4, 8)


def _query_sets(labels, cls, n_pos=12, n_neg=50, seed=0):
    rng = np.random.default_rng(seed)
    pos = rng.choice(np.nonzero(labels == cls)[0], n_pos, replace=False)
    neg = rng.choice(np.nonzero(labels != cls)[0], n_neg, replace=False)
    return pos, neg


def _host_rank(counts, train_ids):
    found = np.nonzero(counts > 0)[0]
    found = found[~np.isin(found, train_ids)]
    order = np.argsort(-counts[found], kind="stable")
    return found[order], counts[found][order]


# ----------------------------------------------------------------------
# partition + sharded index build
# ----------------------------------------------------------------------

def test_shard_offsets_partition_is_ragged_and_total():
    offs = shard_offsets(1500, 8)
    sizes = np.diff(offs)
    assert offs[0] == 0 and offs[-1] == 1500
    assert sizes.sum() == 1500
    assert sizes[-1] < sizes[0], "last shard must be the ragged one"
    # pathological tiny catalog: trailing shards go EMPTY, not illegal
    offs_tiny = shard_offsets(10, 8)
    assert offs_tiny[-1] == 10 and (np.diff(offs_tiny) == 0).any()


@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
def test_sharded_counts_equal_unsharded_and_scan(n_shards):
    """query_index_sharded == query_index == full scan, with boxes
    centred on rows AT the shard boundaries (their matching neighbours
    live on both sides of a cut, so every merge path is exercised)."""
    rng = np.random.default_rng(0)
    n, d = 1000, 5
    x = rng.normal(0, 1, (n, d)).astype(np.float32)
    dims = np.arange(d)
    offs = shard_offsets(n, n_shards)
    centers = np.concatenate([x[offs[:-1]],              # boundary rows
                              x[rng.integers(0, n, 4)]])
    lo = (centers - 0.5).astype(np.float32)
    hi = (centers + 0.5).astype(np.float32)
    bs = BoxSet(lo, hi, dims)
    sidx = build_sharded_index(x, dims, n_shards, block=64)
    got, st = query_index_sharded(sidx, bs)
    want, _ = query_index(build_index(x, dims, block=64), bs)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, boxes_contain(x, lo, hi))
    assert st["n_shards"] == n_shards
    # the partition really is the id map: per-shard rows are the global
    # slice, so the local->global remap is offset arithmetic only
    assert [sh.n_rows for sh in sidx.shards] == np.diff(offs).tolist()


def test_sharded_counts_with_empty_tail_shards():
    """n < useful shard count: trailing shards are empty but inert."""
    rng = np.random.default_rng(1)
    x = rng.normal(0, 1, (10, 3)).astype(np.float32)
    dims = np.arange(3)
    sidx = build_sharded_index(x, dims, 8, block=4)
    assert any(sh.n_rows == 0 for sh in sidx.shards)
    lo = (x[3] - 1.0)[None].astype(np.float32)
    hi = (x[3] + 1.0)[None].astype(np.float32)
    got, _ = query_index_sharded(sidx, BoxSet(lo, hi, dims))
    np.testing.assert_array_equal(got, boxes_contain(x, lo, hi))


# ----------------------------------------------------------------------
# the tentpole invariant: shard-count invariance of the ranked engine
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def sharded_engines(catalog):
    feats, labels = catalog
    engines = {s: SearchEngine(feats, n_subsets=8, subset_dim=5, block=64,
                               seed=0, n_shards=s)
               for s in SHARD_COUNTS}
    return engines, labels


@pytest.mark.parametrize("model,seed", [("dbranch", 0), ("dbranch", 1),
                                        ("dbens", 2)])
def test_shard_count_invariance_ranked(sharded_engines, model, seed):
    """ids AND scores bitwise-identical for n_shards in {1, 2, 4, 8},
    equal to the single-device path and the host ranking oracle. The
    catalog (1500 rows) splits raggedly at every one of these counts,
    and DBranch boxes select rows wherever they live — straddling every
    shard cut."""
    engines, labels = sharded_engines
    pos, neg = _query_sets(labels, 2, seed=seed)
    kw = dict(n_models=6) if model == "dbens" else {}
    single = engines[1]
    host = single.query(pos, neg, model=model, **kw)   # host-rank oracle
    assert host.n_found > 0
    k = max(1, host.n_found // 2)
    for s, eng in engines.items():
        full = eng.query(pos, neg, model=model, max_results=eng.n, **kw)
        np.testing.assert_array_equal(full.ids, host.ids, err_msg=f"S={s}")
        np.testing.assert_array_equal(full.scores, host.scores,
                                      err_msg=f"S={s}")
        trunc = eng.query(pos, neg, model=model, max_results=k, **kw)
        np.testing.assert_array_equal(trunc.ids, host.ids[:k])
        np.testing.assert_array_equal(trunc.scores, host.scores[:k])
        if s > 1:
            # the unranked sharded path reassembles the same full list
            nores = eng.query(pos, neg, model=model, **kw)
            np.testing.assert_array_equal(nores.ids, host.ids)
            assert full.stats["n_shards"] == s


def test_shard_count_invariance_batched(sharded_engines):
    """query_batch over a sharded engine == sequential single-device."""
    engines, labels = sharded_engines
    reqs = []
    for i in range(3):
        pos, neg = _query_sets(labels, 2, seed=60 + i)
        reqs.append({"pos_ids": pos, "neg_ids": neg, "model": "dbranch",
                     "max_results": 25})
    want = [engines[1].query(r["pos_ids"], r["neg_ids"], model="dbranch",
                             max_results=25) for r in reqs]
    for s in (2, 4, 8):
        outs = engines[s].query_batch(reqs)
        for o, w in zip(outs, want):
            np.testing.assert_array_equal(o.ids, w.ids, err_msg=f"S={s}")
            np.testing.assert_array_equal(o.scores, w.scores)
        assert outs[0].stats["batch_n_shards"] == s


def test_merged_topk_ties_at_global_kth_score():
    """Duplicate feature rows force whole score-tie groups that straddle
    the global k-th position; every shard count must cut the tie group
    at the same ascending-global-id boundary the host oracle uses."""
    rng = np.random.default_rng(5)
    base = rng.normal(0, 1, (40, 12)).astype(np.float32)
    x = np.tile(base, (25, 1))                    # 1000 rows, 25x ties
    pos, neg = list(range(5)), list(range(600, 640))
    host = SearchEngine(x, n_subsets=6, subset_dim=4, block=64,
                        seed=1).query(pos, neg, model="dbranch")
    assert host.n_found > 0
    # a k INSIDE a tie group: find one straddling position
    ks = [k for k in range(1, host.n_found)
          if host.scores[k - 1] == host.scores[k]]
    assert ks, "catalog must produce a tie straddling some k"
    for s in (2, 4, 8):
        eng = SearchEngine(x, n_subsets=6, subset_dim=4, block=64, seed=1,
                           n_shards=s)
        for k in (ks[0], ks[-1], host.n_found):
            res = eng.query(pos, neg, model="dbranch", max_results=k)
            np.testing.assert_array_equal(res.ids, host.ids[:k],
                                          err_msg=f"S={s} k={k}")
            np.testing.assert_array_equal(res.scores, host.scores[:k])


# ----------------------------------------------------------------------
# merge vs the host oracle (merge_shard_results), ties included
# ----------------------------------------------------------------------

def _shard_scores(scores_qn: np.ndarray, offs: np.ndarray) -> np.ndarray:
    """[Q, N] global scores -> [S, Nloc_max, Q] stacked shard buffers."""
    s = len(offs) - 1
    nl = np.diff(offs)
    out = np.zeros((s, max(nl.max(), 1), scores_qn.shape[0]),
                   scores_qn.dtype)
    for i in range(s):
        out[i, :nl[i]] = scores_qn[:, offs[i]:offs[i + 1]].T
    return out


def _ops_shard_rank(scores_qn, tids, offs, *, k, smax):
    """The device sharded ranking, straight through the kernel ops:
    vmapped shard_local_topk (local rank + global remap) -> merge_topk."""
    local = functools.partial(kops.shard_local_topk, k=k, score_bound=smax)
    gids, sc, _ = jax.vmap(local, in_axes=(0, None, 0, 0))(
        jnp.asarray(_shard_scores(scores_qn, offs)), jnp.asarray(tids),
        jnp.asarray(offs[:-1], jnp.int32),
        jnp.asarray(np.diff(offs), jnp.int32))
    return kops.merge_topk(gids, sc, k=k)


@pytest.mark.parametrize("seed,nq,n,smax,n_shards", [
    (0, 1, 500, 3, 4), (1, 3, 997, 2, 8), (2, 2, 64, 1, 2)])
def test_merge_topk_matches_host_oracle_merge(seed, nq, n, smax, n_shards):
    """Low smax => massive cross-shard score ties. The device merge must
    equal (a) global rank_topk over the unsharded scores and (b) the
    host oracle merge_shard_results fed each shard's own ranking."""
    rng = np.random.default_rng(seed)
    scores = rng.integers(0, smax + 1, (nq, n)).astype(np.int32)
    tids = np.full((nq, 8), n, np.int32)
    for q in range(nq):
        tids[q, :4] = rng.choice(n, 4, replace=False)
    offs = shard_offsets(n, n_shards)
    ids_m, sc_m, nv_m = (np.asarray(a) for a in _ops_shard_rank(
        scores, tids, offs, k=n, smax=smax))
    ids_g, sc_g, nv_g = (np.asarray(a) for a in kops.rank_topk(
        jnp.asarray(scores), jnp.asarray(tids), k=n, score_bound=smax))
    for q in range(nq):
        nv = int(nv_g[q])
        assert int(nv_m[q]) == nv
        np.testing.assert_array_equal(ids_m[q, :nv], ids_g[q, :nv])
        np.testing.assert_array_equal(sc_m[q, :nv], sc_g[q, :nv])
        assert (ids_m[q, nv:] == -1).all()
        # host oracle: per-shard host ranking, merged by the front end
        per_shard = []
        for s in range(n_shards):
            lt = tids[q][(tids[q] >= offs[s]) & (tids[q] < offs[s + 1])]
            i_s, c_s = _host_rank(scores[q, offs[s]:offs[s + 1]],
                                  lt - offs[s])
            per_shard.append(QueryResult("dbranch", i_s, c_s, 0, 0))
        o_ids, o_sc = merge_shard_results(per_shard, offs[:-1].tolist())
        np.testing.assert_array_equal(ids_m[q, :nv], o_ids)
        np.testing.assert_array_equal(sc_m[q, :nv], o_sc)


def test_merge_shard_results_pins_ascending_id_tie_break():
    """Equal scores across shards: the oracle must order by GLOBAL id,
    not by shard arrival order (shards given out of offset order)."""
    r_hi = QueryResult("dbranch", np.asarray([2, 0]),
                       np.asarray([5.0, 5.0]), 0, 0)      # global 102, 100
    r_lo = QueryResult("dbranch", np.asarray([1, 3]),
                       np.asarray([5.0, 1.0]), 0, 0)      # global 1, 3
    ids, scores = merge_shard_results([r_hi, r_lo], [100, 0])
    np.testing.assert_array_equal(ids, [1, 100, 102, 3])
    np.testing.assert_array_equal(scores, [5.0, 5.0, 5.0, 1.0])


# ----------------------------------------------------------------------
# hypothesis: global ids survive the local->global remap, any partition
# ----------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                       # dev dependency
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1), st.integers(9, 300),
           st.integers(1, 8), st.integers(1, 32), st.integers(1, 6))
    def test_global_ids_survive_remap_property(seed, n, n_shards, k, smax):
        """For ANY catalog size, shard count, k and score range: the
        sharded rank+merge returns exactly the global ranking — every
        returned id is a GLOBAL id (the remap inverted the partition)
        and the (score, id) sequences agree element-wise."""
        rng = np.random.default_rng(seed)
        scores = rng.integers(0, smax + 1, (1, n)).astype(np.int32)
        tids = np.full((1, 4), n, np.int32)
        tids[0, :2] = rng.choice(n, 2, replace=False)
        offs = shard_offsets(n, n_shards)
        ids_m, sc_m, nv_m = (np.asarray(a) for a in _ops_shard_rank(
            scores, tids, offs, k=k, smax=smax))
        want_ids, want_sc = _host_rank(scores[0], tids[0, :2])
        nv = min(k, len(want_ids))
        assert int(nv_m[0]) == nv
        np.testing.assert_array_equal(ids_m[0, :nv], want_ids[:nv])
        np.testing.assert_array_equal(sc_m[0, :nv], want_sc[:nv])


# ----------------------------------------------------------------------
# host traffic + overflow semantics
# ----------------------------------------------------------------------

def test_host_bytes_flat_in_shard_count(sharded_engines):
    """Ranked per-query host traffic must not grow with the shard count:
    the survivor sync is reduced to [3] ints per subset ON DEVICE and
    the merge returns [Q, k] — O(k) whatever S is. capacity_frac=1.0
    removes retries so the figure is deterministic."""
    engines, labels = sharded_engines
    feats = engines[1].x
    pos, neg = _query_sets(labels, 2, seed=9)
    seen = {}
    for s in (2, 4, 8):
        eng = SearchEngine(feats, n_subsets=8, subset_dim=5, block=64,
                           seed=0, n_shards=s, capacity_frac=1.0)
        res = eng.query(pos, neg, model="dbranch", max_results=50)
        seen[s] = res.stats["host_bytes_transferred"]
        assert res.stats["n_host_syncs"] == 1
    assert len(set(seen.values())) == 1, f"host bytes grew with S: {seen}"
    # and it is O(k)-sized, nowhere near one score vector
    assert seen[2] < 4 * engines[1].n


def test_sharded_overflow_retry_is_exact(catalog):
    """A tiny per-shard capacity forces overflow; the deferred batched
    retry must still produce the host oracle's exact ranking and retry
    only the overflowed subsets in one extra round."""
    feats, labels = catalog
    # block=16 -> ~24 blocks/shard, so the 8-block sharded capacity
    # floor (the bucket quantum) sits well below the survivor counts
    eng = SearchEngine(feats, n_subsets=8, subset_dim=5, block=16, seed=0,
                       n_shards=4, capacity_frac=0.01)
    pos, neg = _query_sets(labels, 2, seed=4)
    res = eng.query(pos, neg, model="dbens", n_models=6, max_results=eng.n)
    host = SearchEngine(feats, n_subsets=8, subset_dim=5, block=16,
                        seed=0).query(pos, neg, model="dbens", n_models=6)
    np.testing.assert_array_equal(res.ids, host.ids)
    np.testing.assert_array_equal(res.scores, host.scores)
    assert res.stats["retried_subsets"] > 0
    assert res.stats["n_host_syncs"] == 2


def test_sharded_engine_reports_shard_stats(sharded_engines):
    engines, labels = sharded_engines
    pos, neg = _query_sets(labels, 2, seed=3)
    res = engines[4].query(pos, neg, model="dbranch", max_results=20)
    st = res.stats
    assert st["n_shards"] == 4
    assert st["path"] == "index"
    # gather accounting prices the capacity-sized reads actually made
    assert 0 < st["blocks_touched"] <= st["blocks_gathered"]
    assert engines[4].index_stats()["n_shards"] == 4


# ----------------------------------------------------------------------
# mesh mode for real: 8 virtual devices in a subprocess
# ----------------------------------------------------------------------

@pytest.mark.slow
def test_shard_map_mesh_mode_matches_vmap_and_oracle():
    import json
    import os
    import subprocess
    import sys
    import textwrap
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import numpy as np
        import jax
        assert len(jax.devices()) == 8
        from repro.core.engine import SearchEngine
        from repro.data.synthetic import (PatchDatasetConfig,
                                          generate_patches,
                                          handcrafted_features)
        data = generate_patches(PatchDatasetConfig(n_patches=900, seed=3))
        feats = handcrafted_features(data["images"])
        labels = data["labels"]
        pos = np.nonzero(labels == 2)[0][:10]
        neg = np.nonzero(labels != 2)[0][:40]
        host = SearchEngine(feats, n_subsets=6, subset_dim=5, block=64,
                            seed=0).query(pos, neg, model="dbranch")
        em = SearchEngine(feats, n_subsets=6, subset_dim=5, block=64,
                          seed=0, n_shards=8)
        ev = SearchEngine(feats, n_subsets=6, subset_dim=5, block=64,
                          seed=0, n_shards=8, shard_mesh=False)
        rm = em.query(pos, neg, model="dbranch", max_results=em.n)
        rv = ev.query(pos, neg, model="dbranch", max_results=ev.n)
        print("RESULT:" + json.dumps({
            "used_mesh": em.shard_mesh is not None,
            "mesh_eq_oracle": bool(np.array_equal(rm.ids, host.ids)
                                   and np.array_equal(rm.scores,
                                                      host.scores)),
            "mesh_eq_vmap": bool(np.array_equal(rm.ids, rv.ids)
                                 and np.array_equal(rm.scores, rv.scores)),
        }))
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    line = next(l for l in out.stdout.splitlines()
                if l.startswith("RESULT:"))
    r = json.loads(line[len("RESULT:"):])
    assert r["used_mesh"], "8 devices available but the mesh was not used"
    assert r["mesh_eq_oracle"] and r["mesh_eq_vmap"], r


def test_shard_map_mesh_dense_accumulate_matches_oracle():
    """The dense score form on a real 4-device mesh: each shard scatters
    its gathered rows by shard-local id (global id minus shard *
    Nloc_max), and a subset that overflowed on ANY shard adds nothing on
    every shard — a tiny capacity forces that retry — so the ranking is
    bitwise the host oracle's."""
    import json
    import os
    import subprocess
    import sys
    import textwrap
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import json
        import numpy as np
        import jax
        assert len(jax.devices()) == 4
        from repro.core.engine import SearchEngine
        from repro.data.synthetic import (PatchDatasetConfig,
                                          generate_patches,
                                          handcrafted_features)
        data = generate_patches(PatchDatasetConfig(n_patches=1001, seed=3))
        feats = handcrafted_features(data["images"])
        labels = data["labels"]
        pos = np.nonzero(labels == 2)[0][:10]
        neg = np.nonzero(labels != 2)[0][:40]
        kw = dict(n_subsets=6, subset_dim=5, block=16, seed=0)
        host = SearchEngine(feats, **kw).query(pos, neg, model="dbens",
                                                n_models=6)
        em = SearchEngine(feats, n_shards=4, score_mode="dense",
                          capacity_frac=0.01, **kw)
        rm = em.query(pos, neg, model="dbens", n_models=6,
                      max_results=em.n)
        print("RESULT:" + json.dumps({
            "used_mesh": em.shard_mesh is not None,
            "retried": int(rm.stats["retried_subsets"]),
            "eq_oracle": bool(np.array_equal(rm.ids, host.ids)
                              and np.array_equal(rm.scores, host.scores)),
        }))
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    line = next(l for l in out.stdout.splitlines()
                if l.startswith("RESULT:"))
    r = json.loads(line[len("RESULT:"):])
    assert r["used_mesh"], "4 devices available but the mesh was not used"
    assert r["retried"] > 0, r
    assert r["eq_oracle"], r
