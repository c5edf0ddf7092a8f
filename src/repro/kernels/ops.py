"""jit'd public wrappers around the Pallas kernels.

Handles TPU-shape hygiene (row-tile padding, lane-multiple feature
padding with open bounds). Backend dispatch (``interpret=None``): on TPU
the compiled Pallas kernel runs; on any other backend the wrapper routes
to the jit'd pure-jnp oracle from ref.py — interpret-mode Pallas is a
KERNEL-DEBUGGING tool (it emulates the kernel ~25x slower than the jnp
graph on CPU) and is only used when a caller explicitly passes
``interpret=True`` (the kernel test-suite does, to verify the Pallas
implementations against the oracles everywhere).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as kref
from repro.kernels.box_scan import box_scan_pallas, box_scan_seg_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.l2dist import l2dist_pallas
from repro.kernels.zone_prune import zone_prune_pallas

_BIG = jnp.float32(3.4e38)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# jit'd oracle fallbacks — the off-TPU serving path
_box_scan_ref_jit = jax.jit(kref.box_scan_ref)
_box_scan_seg_ref_jit = jax.jit(kref.box_scan_seg_ref)
_zone_prune_ref_jit = jax.jit(kref.zone_prune_ref)
_l2dist_ref_jit = jax.jit(kref.l2dist_ref)


def _pad_rows(a: jax.Array, mult: int, fill: float) -> jax.Array:
    n = a.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return a
    return jnp.concatenate(
        [a, jnp.full((pad,) + a.shape[1:], fill, a.dtype)], axis=0)


def _pad_dim(a: jax.Array, mult: int, fill: float) -> jax.Array:
    d = a.shape[-1]
    pad = (-d) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * (a.ndim - 1) + [(0, pad)]
    return jnp.pad(a, widths, constant_values=fill)


def box_scan(x: jax.Array, lo: jax.Array, hi: jax.Array,
             *, tile_n: int = 1024, interpret: bool | None = None) -> jax.Array:
    """Membership counts [N] for rows x against boxes (lo, hi].

    Feature padding uses (lo=-BIG, hi=+BIG) so padded dims always pass;
    row padding uses +2*BIG rows that can never be inside any box."""
    if interpret is None:
        if not _on_tpu():
            return _box_scan_ref_jit(x, lo, hi)
        interpret = False
    n = x.shape[0]
    xp = _pad_dim(_pad_rows(x, tile_n, float("inf")), 128, 0.0)
    lop = _pad_dim(lo, 128, -float("inf"))
    hip = _pad_dim(hi, 128, float("inf"))
    out = box_scan_pallas(xp, lop, hip, tile_n=tile_n, interpret=interpret)
    return out[:n]


def zone_prune(zlo: jax.Array, zhi: jax.Array, blo: jax.Array, bhi: jax.Array,
               *, tile_z: int = 512, interpret: bool | None = None) -> jax.Array:
    """Overlap mask [NZ, B]. Padded zones are empty intervals (lo > hi)
    that overlap nothing; padded dims are full intervals."""
    if interpret is None:
        if not _on_tpu():
            return _zone_prune_ref_jit(zlo, zhi, blo, bhi)
        interpret = False
    nz = zlo.shape[0]
    zlop = _pad_dim(_pad_rows(zlo, tile_z, float("inf")), 128, -float("inf"))
    zhip = _pad_dim(_pad_rows(zhi, tile_z, -float("inf")), 128, float("inf"))
    blop = _pad_dim(blo, 128, -float("inf"))
    bhip = _pad_dim(bhi, 128, float("inf"))
    out = zone_prune_pallas(zlop, zhip, blop, bhip,
                            tile_z=tile_z, interpret=interpret)
    return out[:nz]


def box_scan_seg(x: jax.Array, lo: jax.Array, hi: jax.Array,
                 onehot: jax.Array, *, tile_n: int = 1024,
                 interpret: bool | None = None) -> jax.Array:
    """Per-segment membership counts [N, Q]: counts[i, q] = number of
    boxes b with onehot[b, q] == 1 that contain row i.

    Same padding hygiene as box_scan, plus the segment axis padded to a
    lane multiple with all-zero columns (they count nothing)."""
    if interpret is None:
        if not _on_tpu():
            return _box_scan_seg_ref_jit(x, lo, hi,
                                         onehot.astype(jnp.float32))
        interpret = False
    n = x.shape[0]
    nq = onehot.shape[1]
    xp = _pad_dim(_pad_rows(x, tile_n, float("inf")), 128, 0.0)
    lop = _pad_dim(lo, 128, -float("inf"))
    hip = _pad_dim(hi, 128, float("inf"))
    ohp = _pad_dim(onehot.astype(jnp.float32), 128, 0.0)
    out = box_scan_seg_pallas(xp, lop, hip, ohp, tile_n=tile_n,
                              interpret=interpret)
    return out[:n, :nq]


@functools.partial(jax.jit,
                   static_argnames=("capacity", "use_pallas", "interpret"))
def fused_query(rows3: jax.Array, zlo: jax.Array, zhi: jax.Array,
                blo: jax.Array, bhi: jax.Array, onehot: jax.Array,
                *, capacity: int, use_pallas: bool = True,
                interpret: bool | None = None):
    """Device-resident prune -> gather -> segmented refine, ONE jit.

    rows3: [NB, block, d'] Morton-ordered index rows (resident on device —
    callers upload once via ZoneMapIndex.device_arrays); zlo/zhi: [NB, d']
    zone maps; blo/bhi: [B, d'] boxes; onehot: [B, Q] box->query ownership
    map (Q == 1 with an all-ones column collapses to single-query counts).

    ``capacity`` statically bounds the surviving-block gather
    (``jnp.nonzero(size=capacity)`` — the padded-result idiom, mirroring
    distributed_query_pruned): every quantity that leaves the device —
    the refined counts and the gathered-block ids — is sized by capacity,
    not catalog size, and shapes stay static so the whole pipeline
    compiles to one device program with zero host round-trips. Survivors
    beyond capacity are dropped; callers detect overflow via n_hit.

    Returns (counts [capacity, block, Q] int32 — per gathered block, slot
             i holding block cand[i]'s counts (slots >= n_hit zeroed),
             cand [capacity] int32 — gathered block ids (zone order,
             0-filled past n_hit),
             n_hit scalar int32 — TOTAL surviving blocks, pre-capacity).
    """
    nb, block, dd = rows3.shape
    if use_pallas:
        mask = zone_prune(zlo, zhi, blo, bhi, interpret=interpret)
    else:
        mask = kref.zone_prune_ref(zlo, zhi, blo, bhi)       # [NB, B]
    hit = mask.any(1)
    n_hit = hit.sum().astype(jnp.int32)
    cand, = jnp.nonzero(hit, size=capacity, fill_value=0)    # [C]
    valid = jnp.arange(capacity) < n_hit
    sel = rows3[cand]                                        # [C, block, d']
    flat = sel.reshape(capacity * block, dd)
    if use_pallas:
        counts = box_scan_seg(flat, blo, bhi, onehot, interpret=interpret)
    else:
        counts = kref.box_scan_seg_ref(flat, blo, bhi,
                                       onehot.astype(jnp.float32))
    counts = counts.reshape(capacity, block, -1) * valid[:, None, None]
    return counts, cand.astype(jnp.int32), n_hit


def batch_box_membership(x: jax.Array, lo: jax.Array, hi: jax.Array,
                         valid: jax.Array) -> jax.Array:
    """Per-set membership counts [T, N]: counts[t, i] = number of valid
    boxes of set t containing row i of sample batch t.

    x: [T, N, d']; lo/hi: [T, B, d'] half-open boxes; valid: [T, B] bool
    (invalid slots never match). The same membership predicate as
    box_scan, batched over T — the batched trainer's selection stage
    scores every candidate model on its own training samples with this,
    so subset selection stays on device (DESIGN.md §10). Designed to run
    INSIDE a caller's jit (not dispatched standalone)."""
    inside = ((x[:, :, None, :] > lo[:, None, :, :])
              & (x[:, :, None, :] <= hi[:, None, :, :]))     # [T, N, B, d']
    return (jnp.all(inside, -1) & valid[:, None, :]).sum(-1).astype(jnp.int32)


@jax.jit
def accumulate_scores(scores: jax.Array, counts: jax.Array, cand: jax.Array,
                      n_live: jax.Array, grid: jax.Array) -> jax.Array:
    """Add one subset's fused counts into the persistent per-query score
    buffer, ON DEVICE, by row id.

    scores: [N, Q] int32 running scores; counts: [C, block, Q] from
    fused_query; cand: [C] gathered block ids; n_live: int32 scalar, the
    leading slots of ``cand`` that carry counts (fused_query's n_hit; 0
    discards the whole subset, which is how the fused score programs
    drop an overflowed attempt without selecting between two buffers);
    grid: [NB, block] int32 buffer row per (block, slot), -1 on padding
    slots (the index's device_gids, or a shard's local view of it).

    The work follows the gathered capacity, not the catalog: one
    block-granular gather of C grid rows names the [C, block] target
    rows, and one scatter-add of C * block rows lands them. Fill slots
    past ``n_live`` (nonzero pads with block 0) and padding slots map
    to distinct rows past the buffer and drop, so every target row is
    unique within the subset. Nothing here touches the N rows by index:
    the buffer update stays in place. Tombstones are not masked here —
    the live catalog masks the finished buffer once a query. The scores
    are int32 counts, so any order of adding them gives the same bits."""
    c, block, q = counts.shape
    rows = jnp.take(grid, cand, axis=0)                      # [C, block]
    live = (jnp.arange(c) < n_live)[:, None] & (rows >= 0)
    past = scores.shape[0] + jnp.arange(c * block, dtype=jnp.int32)
    rows = jnp.where(live, rows, past.reshape(c, block))
    return scores.at[rows.reshape(c * block)].add(
        counts.reshape(c * block, q), mode="drop", unique_indices=True)


# ----------------------------------------------------------------------
# Survivor-sparse score tiles
# ----------------------------------------------------------------------
# The dense accumulate_scores above keeps an [N, Q] buffer alive for the
# whole query — O(catalog) device memory regardless of selectivity. The
# sparse formulation below keeps only the rows that can still score:
# fused_query's gathered counts are [C, block, Q] TILES keyed by block,
# and every row that survives any subset is emitted once per subset as a
# (global row id, [Q] counts) pair. Because the scores are int32 counts,
# addition is exactly associative: summing a row's per-subset
# contributions in ANY order is bitwise-identical to the dense
# accumulation, so ranking the merged tiles reproduces the dense result
# exactly while device memory scales with survivors, not catalog size.

TILE_INVALID = np.int32(2 ** 31 - 1)     # padding key; sorts past all ids


def tile_candidates(counts: jax.Array, cand: jax.Array,
                    gids_blocks: jax.Array,
                    valid: jax.Array | None = None):
    """Label fused_query's gathered tiles with global row ids and mark
    which rows can contribute score.

    counts: [C, block, Q] from fused_query (overflow slots zeroed);
    cand: [C] gathered block ids; gids_blocks: [NB, block] int32 global
    row id per (block, slot) — -1 on padding slots (the device mirror
    built from the index permutation); valid: optional [n] row-liveness
    mask in GLOBAL id space (tombstoned rows are dropped here, the
    sparse analogue of the dense buffer's tombstone mask).

    Returns (gids [C, block] int32, ok [C, block] bool). ``ok`` is True
    only for real, live rows with a nonzero count in at least one query
    — dropping all-zero rows is score-preserving (they add nothing) and
    is what makes the tiles survivor-sparse rather than block-dense.
    Pure jnp; safe to trace inside a caller's jit."""
    gids = jnp.take(gids_blocks, cand, axis=0)               # [C, block]
    ok = (counts != 0).any(-1) & (gids >= 0)
    if valid is not None:
        ok &= jnp.take(valid, gids, mode="fill",
                       fill_value=0).astype(bool)
    return gids, ok


@functools.partial(jax.jit, static_argnames=("row_capacity", "val_dtype"))
def survivor_tiles(counts: jax.Array, gids: jax.Array, ok: jax.Array,
                   *, row_capacity: int, val_dtype=jnp.int32):
    """Compact one subset's surviving rows into a fixed-size score tile.

    counts: [C, block, Q]; gids/ok: from tile_candidates;
    ``row_capacity`` statically bounds the compaction (the engine sizes
    it exactly from the same stats sync that drives overflow retry, so
    a correctly-sized call never truncates — n_rows reports the true
    survivor count for callers that want to assert that).

    Returns (keys [row_capacity] int32 global row ids, TILE_INVALID past
    the live prefix; vals [row_capacity, Q] counts in ``val_dtype``,
    zeroed past the live prefix; n_rows scalar int32 — true survivor
    count pre-capacity). val_dtype may be int16 when the caller bounds
    every count below 2**15 (see packed_survivor_tiles). Tiles from
    different subsets concatenate freely: duplicate keys are summed by
    sparse_topk (in int32, whatever the tile width), and int32 addition
    makes the sum order-free."""
    c, block, q = counts.shape
    okf = ok.reshape(c * block)
    idx, = jnp.nonzero(okf, size=row_capacity, fill_value=0)
    n_rows = okf.sum().astype(jnp.int32)
    live = jnp.arange(row_capacity) < n_rows
    keys = jnp.where(live, gids.reshape(-1)[idx], TILE_INVALID)
    vals = (counts.reshape(c * block, q)[idx]
            * live[:, None]).astype(val_dtype)
    return keys.astype(jnp.int32), vals, n_rows


@functools.partial(jax.jit, static_argnames=("row_capacities", "val_dtype"))
def packed_survivor_tiles(parts, *, row_capacities, val_dtype=jnp.int32):
    """Compact MANY subsets' survivors straight into one merged tile.

    parts: tuple of (counts [Ci, block, Q], gids [Ci, block],
    ok [Ci, block]) triples, one per subset; row_capacities: matching
    tuple of static per-subset row capacities (sized exactly from the
    same stats sync as survivor_tiles). Each subset's compaction writes
    into its slice of a single preallocated [sum(row_capacities)] buffer
    via dynamic_update_slice — inside the one jit those updates are
    in-place, so the peak is the merged tile plus ONE subset's scratch,
    not the tiles-plus-concatenated-copy the per-subset path pays.

    val_dtype may be int16 when the caller can bound every per-row,
    per-query count below 2**15 (count <= the round's merged box count,
    which the engine knows on the host): the values are exact, merely
    narrower, and sparse_topk / the host export upcast to int32 before
    any summation — so the ranking stays bitwise while the value bytes
    halve. Layout and semantics of the output match a concatenation of
    survivor_tiles calls (TILE_INVALID keys / zero vals on padding)."""
    total = int(sum(row_capacities))
    q = parts[0][0].shape[-1]
    out_k = jnp.full((total,), TILE_INVALID, jnp.int32)
    out_v = jnp.zeros((total, q), val_dtype)
    off = 0
    for (counts, gids, ok), rcap in zip(parts, row_capacities):
        c, block, _ = counts.shape
        okf = ok.reshape(c * block)
        idx, = jnp.nonzero(okf, size=rcap, fill_value=0)
        live = jnp.arange(rcap) < okf.sum()
        keys = jnp.where(live, gids.reshape(-1)[idx],
                         TILE_INVALID).astype(jnp.int32)
        vals = (counts.reshape(c * block, q)[idx]
                * live[:, None]).astype(val_dtype)
        out_k = jax.lax.dynamic_update_slice(out_k, keys, (off,))
        out_v = jax.lax.dynamic_update_slice(out_v, vals, (off, 0))
        off += rcap
    return out_k, out_v


@functools.partial(jax.jit, static_argnames=("k",))
def sparse_topk(keys: jax.Array, vals: jax.Array, train_ids: jax.Array,
                *, k: int):
    """Rank survivor-sparse score tiles: merge duplicate keys, mask
    training rows, return the top-k — without ever materialising an
    [N, Q] buffer.

    keys: [R] int32 global row ids (TILE_INVALID on padding — sorts past
    every real id); vals: [R, Q] per-row counts (zero on padding) —
    int32, or int16 from a width-narrowed packed tile (upcast here
    BEFORE any summation, so duplicate-key merges accumulate in int32
    exactly as the dense path does); train_ids: [Q, T] GLOBAL ids to
    exclude (pad with the catalog size n, which is never a key); k:
    results per query.

    Pipeline, all O(R log R) on device: sort rows by key; segment-sum
    duplicate keys (a row surviving m subsets appears m times — int32
    addition reproduces the dense accumulation bitwise); binary-search
    each training id into the unique-key array and zero its scores; one
    2-key ``lax.sort`` over (-score, id) per query — the SAME tie-break
    contract as rank_topk / merge_topk / the host oracle: descending
    score, ascending global id, score <= 0 invalid (ids -1).

    The output is padded to a STATIC [Q, k] regardless of R, so
    device->host traffic is O(k)/query and does not vary with tile count
    (and therefore not with shard count or round structure).

    Returns (ids [Q, k] int32, scores [Q, k] int32, n_valid [Q] int32)
    — n_valid = min(k, #rows with positive masked score), matching
    rank_topk exactly (every positive row is guaranteed to be in some
    tile: the zone prune is conservative and overflow is retried)."""
    r, nq = vals.shape
    order = jnp.argsort(keys)
    sk = jnp.take(keys, order)                               # ascending
    sv = jnp.take(vals, order, axis=0).astype(jnp.int32)
    first = jnp.concatenate(
        [jnp.ones((1,), bool), sk[1:] != sk[:-1]])
    seg = jnp.cumsum(first) - 1                              # [R]
    # unique keys stay ascending (sk is sorted); tail keeps TILE_INVALID
    uk = jnp.full((r,), TILE_INVALID, jnp.int32).at[seg].set(sk)
    uv = jnp.zeros((r, nq), jnp.int32).at[seg].add(sv)
    # training mask: locate each train id among the unique keys
    pos = jnp.searchsorted(uk, train_ids)                    # [Q, T]
    hit = jnp.take(uk, pos, mode="fill",
                   fill_value=TILE_INVALID) == train_ids
    posx = jnp.where(hit, pos, r).astype(jnp.int32)
    qidx = jnp.arange(nq, dtype=jnp.int32)[:, None]
    sc = uv.T.at[qidx, posx].set(0, mode="drop")             # [Q, R]
    key_id = jnp.where(sc > 0, uk[None, :], TILE_INVALID)
    sneg, sids = jax.lax.sort((-sc, key_id), dimension=-1, num_keys=2)
    kk = min(int(k), r)
    out_scores = -sneg[:, :kk]
    out_ids = jnp.where(out_scores > 0, sids[:, :kk], -1)
    if kk < k:                                   # static pad to [Q, k]
        out_ids = jnp.pad(out_ids, ((0, 0), (0, k - kk)),
                          constant_values=-1)
        out_scores = jnp.pad(out_scores, ((0, 0), (0, k - kk)))
    return (out_ids.astype(jnp.int32), out_scores.astype(jnp.int32),
            (out_scores > 0).sum(1).astype(jnp.int32))


def rank_topk(scores: jax.Array, train_ids: jax.Array, *, k: int,
              score_bound: int | None = None, method: str | None = None,
              scores_transposed: bool = False):
    """Device ranking stage: mask training rows, take the top-k scoring
    rows, return only [Q, k] to the host — O(k) device->host traffic.

    scores: [Q, N] int32; train_ids: [Q, T] int32 rows to exclude per
    query (pad with N — out-of-bounds entries are dropped, so a query that
    keeps its training rows passes an all-N row); k: results per query;
    score_bound: a host-known upper bound on any score (e.g. the query's
    total box count) — picks the best strategy and sizes its search.

    Tie-break contract (must match the host oracle `SearchEngine._rank`,
    a stable sort of -score): descending score, ascending row id within
    equal scores — including ties that straddle the k boundary. Three
    implementations with identical documented ordering:

    * "topk": each row's key is ``score * N + (N - 1 - id)`` — the id
      composed into the low digits — and one ``lax.top_k`` over the int32
      keys returns the exact order (keys are unique, so backend tie-break
      behaviour never matters). Needs ``(score_bound + 1) * N < 2**31``.
      The TPU default: top_k runs in the sort unit at memory speed.
    * "sort": ``lax.sort`` with num_keys=2 over (-score, id) — documented
      lexicographic order — then slice the first k columns. The paper-
      scale TPU fallback when the composed key would overflow int32.
    * "threshold": the off-TPU default — XLA CPU sorts are scalar code,
      so instead binary-search the k-th largest score with ``sbits``
      vectorised count passes, extract rows above/at the threshold with
      cumsum+searchsorted compaction (ascending id, exactly the tie-break
      order), and run ONE tiny 2-key sort over the <= 2k candidates.
      O(N log(score_bound)) elementwise work, never a full-width sort.

    Rows with score <= 0 (incl. masked training rows) are invalid: their
    ids come back -1 and n_valid excludes them. Tombstoned rows of a live
    catalog arrive here already zeroed (the engine masks the dense
    buffer once a query; the sparse tiles drop dead rows), so they fall
    under the same rule — and because masking only LOWERS
    scores, any ``score_bound`` that was valid for the unmasked buffer
    (the per-query box count) stays valid under tombstones, down to the
    all-dead edge where every query simply yields n_valid == 0.

    ``scores_transposed=True`` accepts the engine's row-major [N, Q]
    buffer directly; the flip happens inside the jit where XLA fuses it
    into the first pass instead of materialising a transposed copy.

    Returns (ids [Q, k] int32 (-1 past the valid prefix),
             scores [Q, k] int32 (0 past the valid prefix),
             n_valid [Q] int32)."""
    n = scores.shape[0] if scores_transposed else scores.shape[1]
    k = min(int(k), n)
    if method is None:
        if not _on_tpu():
            method = "threshold"
        elif score_bound is not None and (score_bound + 1) * n < 2 ** 31:
            method = "topk"
        else:
            method = "sort"
    if method == "threshold":
        # 2**sbits must exceed any score; without a bound assume scores
        # fit 30 bits (they are box-membership counts, nowhere near 2^30)
        sbits = int(score_bound).bit_length() if score_bound else 30
        return _rank_threshold(scores, train_ids, k=k,
                               sbits=min(max(sbits, 1), 30),
                               tr=scores_transposed)
    if method == "topk":
        assert score_bound is not None and (score_bound + 1) * n < 2 ** 31, \
            "topk needs an int32-safe composed key; use sort/threshold"
        return _rank_topk_compose(scores, train_ids, k=k,
                                  tr=scores_transposed)
    assert method == "sort", f"unknown rank method {method!r}"
    return _rank_sort(scores, train_ids, k=k, tr=scores_transposed)


def _mask_training(scores: jax.Array, train_ids: jax.Array) -> jax.Array:
    nq = scores.shape[0]
    qidx = jnp.arange(nq, dtype=jnp.int32)[:, None]
    return scores.at[qidx, train_ids].set(0, mode="drop")


@functools.partial(jax.jit, static_argnames=("k", "tr"))
def _rank_topk_compose(scores, train_ids, *, k: int, tr: bool = False):
    if tr:
        scores = scores.T
    n = scores.shape[1]
    masked = _mask_training(scores, train_ids)
    ids = jnp.arange(n, dtype=jnp.int32)
    # score > 0  <=>  key >= n, so zero rows never rank as valid
    key = masked * n + (n - 1 - ids)[None, :]
    top, _ = jax.lax.top_k(key, k)                       # [Q, k]
    valid = top >= n
    out_scores = jnp.where(valid, top // n, 0)
    out_ids = jnp.where(valid, (n - 1) - top % n, -1)
    return (out_ids.astype(jnp.int32), out_scores.astype(jnp.int32),
            valid.sum(1).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("k", "tr"))
def _rank_sort(scores, train_ids, *, k: int, tr: bool = False):
    if tr:
        scores = scores.T
    n = scores.shape[1]
    masked = _mask_training(scores, train_ids)
    ids2 = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :],
                            masked.shape)
    sneg, sids = jax.lax.sort((-masked, ids2), dimension=-1, num_keys=2)
    out_scores, out_ids = -sneg[:, :k], sids[:, :k]
    valid = out_scores > 0
    out_ids = jnp.where(valid, out_ids, -1)
    return (out_ids.astype(jnp.int32), out_scores.astype(jnp.int32),
            valid.sum(1).astype(jnp.int32))


_RANK_CHUNK = 64     # rows per extraction chunk (see _first_k_set_rows)


def _first_k_set_rows(mask: jax.Array, k: int) -> jax.Array:
    """ids of the first k set rows of mask [Q, n], ascending; n where
    exhausted. Two-level: per-chunk counts (a parallel reduction) place
    each of the k targets in its chunk via a tiny binary search, then a
    short cumsum over ONLY the k gathered chunks finds the in-chunk
    offset — never a full-width sequential cumsum over n."""
    nq, n = mask.shape
    ch = _RANK_CHUNK
    g = -(-n // ch)
    mp = jnp.pad(mask, ((0, 0), (0, g * ch - n)))
    mc = mp.reshape(nq, g, ch)
    cnt = mc.sum(-1, dtype=jnp.int32)                       # [Q, g]
    cum = jnp.cumsum(cnt, -1)                               # [Q, g] tiny
    tgt = jnp.arange(1, k + 1, dtype=jnp.int32)             # [k]
    cj = jax.vmap(
        lambda c: jnp.searchsorted(c, tgt).astype(jnp.int32))(cum)
    prev = jnp.where(cj > 0,
                     jnp.take_along_axis(cum, jnp.maximum(cj - 1, 0), 1), 0)
    r = tgt[None] - prev                                    # rank in chunk
    sel = jnp.take_along_axis(mc, jnp.minimum(cj, g - 1)[..., None], 1)
    loc = jnp.argmax(jnp.cumsum(sel, -1) >= r[..., None], -1)
    return jnp.where(cj < g, cj * ch + loc.astype(jnp.int32), n)


@functools.partial(jax.jit, static_argnames=("k", "sbits", "tr"))
def _rank_threshold(scores, train_ids, *, k: int, sbits: int,
                    tr: bool = False):
    if tr:
        scores = scores.T
    nq, n = scores.shape
    masked = _mask_training(scores, train_ids)
    npos = (masked > 0).sum(1).astype(jnp.int32)
    kq = jnp.minimum(k, npos)                  # results this query yields
    # binary search the k-th largest positive score t:
    # invariant count(masked >= lo) >= kq > count(masked >= hi)
    lo = jnp.ones(nq, jnp.int32)
    hi = jnp.full(nq, jnp.int32(1 << sbits))

    def body(_, lh):
        lo, hi = lh
        mid = (lo + hi) // 2
        ok = (masked >= mid[:, None]).sum(1) >= kq
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    t, _ = jax.lax.fori_loop(0, sbits, body, (lo, hi))
    gt = masked > t[:, None]
    eq = masked == t[:, None]
    i_gt = _first_k_set_rows(gt, k)            # all above-threshold rows
    i_eq = _first_k_set_rows(eq, k)            # threshold ties, id order
    m_cnt = gt.sum(1).astype(jnp.int32)        # < kq by threshold choice
    keep_eq = jnp.arange(k, dtype=jnp.int32)[None, :] < (kq - m_cnt)[:, None]
    cand_ids = jnp.concatenate([i_gt, jnp.where(keep_eq, i_eq, n)], 1)
    valid = cand_ids < n
    cs = jnp.where(
        valid, jnp.take_along_axis(masked, jnp.minimum(cand_ids, n - 1), 1),
        -1)
    # one tiny 2-key sort orders the <= 2k survivors: (-score, id)
    sneg, sids = jax.lax.sort((-cs, jnp.where(valid, cand_ids, n)),
                              dimension=-1, num_keys=2)
    out_scores = jnp.maximum(-sneg[:, :k], 0)
    out_ids = jnp.where(out_scores > 0, sids[:, :k], -1)
    return out_ids.astype(jnp.int32), out_scores.astype(jnp.int32), kq


def shard_local_topk(scores: jax.Array, train_ids: jax.Array,
                     offset: jax.Array, n_local: jax.Array, *, k: int,
                     score_bound: int | None = None,
                     method: str | None = None):
    """Shard-local ranking stage of the sharded serving path: rank ONE
    shard's score buffer with rank_topk (same tie-break contract) and
    remap the winners into GLOBAL row ids.

    scores: [Nloc, Q] this shard's per-row scores in shard-local row
    order (row-major, like the engine's buffer; padded rows past
    ``n_local`` must carry score 0 — the sharded accumulate guarantees
    it); train_ids: [Q, T] GLOBAL training ids to exclude (pad with the
    catalog size); offset / n_local: this shard's global row offset and
    real row count (traced scalars — one program serves every shard
    under vmap or shard_map).

    Global ids in [offset, offset + n_local) map to local ids by
    subtraction; every other training id (another shard's rows, or the
    catalog-size pad) maps to Nloc, which rank_topk's mode="drop" mask
    discards. Returned ids are local winners + offset, so the cross-
    shard merge (merge_topk) orders by GLOBAL id on score ties — shards
    own disjoint ascending id ranges, making (descending score,
    ascending global id) a total order identical to the single-device
    ranking. Invalid slots stay -1."""
    nloc = scores.shape[0]
    t = jnp.where((train_ids >= offset) & (train_ids < offset + n_local),
                  train_ids - offset, nloc).astype(jnp.int32)
    ids, sc, nv = rank_topk(scores, t, k=k, score_bound=score_bound,
                            method=method, scores_transposed=True)
    gids = jnp.where(ids >= 0, ids + offset.astype(jnp.int32),
                     jnp.int32(-1))
    return gids.astype(jnp.int32), sc, nv


@functools.partial(jax.jit, static_argnames=("k",))
def merge_topk(ids: jax.Array, scores: jax.Array, *, k: int):
    """Cross-shard merge of per-shard top-k lists, ON DEVICE.

    ids: [S, Q, ks] int32 GLOBAL ids (-1 invalid); scores: [S, Q, ks]
    int32 (> 0 on valid slots, 0 on invalid — rank_topk's convention).
    Returns (ids [Q, k'] int32, scores [Q, k'] int32, n_valid [Q] int32)
    with k' = min(k, S * ks); only this O(k) result ever needs to cross
    to the host, independent of shard count.

    One 2-key ``lax.sort`` over the S*ks candidates per query pins the
    SAME tie-break contract as rank_topk / the host oracle: descending
    score, ascending global id within equal scores — including ties at
    the global k-th score, where the lowest global ids win regardless of
    which shards they came from. Invalid slots carry score 0 (every real
    score is >= 1) so they sort past every valid candidate; their ids
    come back -1. Because any global top-k row is necessarily within its
    own shard's top-k, merging per-shard top-k lists loses nothing."""
    s, q, ks = ids.shape
    fids = jnp.swapaxes(ids, 0, 1).reshape(q, s * ks)
    fsc = jnp.swapaxes(scores, 0, 1).reshape(q, s * ks)
    valid = fsc > 0
    # invalid ids (-1) would win ascending-id ties: push them to +inf-ish
    key_id = jnp.where(valid, fids, jnp.int32(2 ** 31 - 1))
    sneg, sids = jax.lax.sort((-fsc, key_id), dimension=-1, num_keys=2)
    kk = min(int(k), s * ks)
    out_scores = -sneg[:, :kk]
    out_ids = jnp.where(out_scores > 0, sids[:, :kk], -1)
    return (out_ids.astype(jnp.int32), out_scores.astype(jnp.int32),
            (out_scores > 0).sum(1).astype(jnp.int32))


def l2dist(x: jax.Array, q: jax.Array,
           *, tile_n: int = 1024, interpret: bool | None = None) -> jax.Array:
    """Squared L2 distance matrix [N, Q]."""
    if interpret is None:
        if not _on_tpu():
            return _l2dist_ref_jit(x, q)
        interpret = False
    n = x.shape[0]
    xp = _pad_dim(_pad_rows(x, tile_n, 0.0), 128, 0.0)
    qp = _pad_dim(q, 128, 0.0)
    out = l2dist_pallas(xp, qp, tile_n=tile_n, interpret=interpret)
    return out[:n]


def knn_topk(x: jax.Array, q: jax.Array, k: int,
             *, interpret: bool | None = None):
    """(distances [Q, k], indices [Q, k]) nearest rows of x per query."""
    d = l2dist(x, q, interpret=interpret)            # [N, Q]
    neg, idx = jax.lax.top_k(-d.T, k)                # [Q, k]
    return -neg, idx


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    *, causal: bool = True, q_chunk: int = 512,
                    kv_chunk: int = 512,
                    interpret: bool | None = None) -> jax.Array:
    """GQA flash attention in model layout: q [B,S,Hq,D]; k/v [B,S,Hkv,D].

    Repacks to the kernel's [B*Hkv, S, G, D] layout and back. Sequence
    must divide the chunk sizes (callers pad)."""
    if interpret is None:
        interpret = not _on_tpu()
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qc = min(q_chunk, s)
    kc = min(kv_chunk, s)
    qk = q.reshape(b, s, hkv, g, d).transpose(0, 2, 1, 3, 4)
    qk = qk.reshape(b * hkv, s, g, d)
    kk = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vk = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    out = flash_attention_pallas(qk, kk, vk, causal=causal, q_chunk=qc,
                                 kv_chunk=kc, interpret=interpret)
    out = out.reshape(b, hkv, s, g, d).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, s, hq, d)
