"""Blocked zone-map index — the TPU-native adaptation of the k-d tree.

Per feature subset: rows are ordered by a Morton (bit-interleaved) code
over the quantised subset dims, partitioned into fixed blocks, and each
block keeps per-dim [min, max] *zone maps*. A range query then runs two
dense stages (both Pallas kernels):

  prune : zone_prune(zones, boxes) -> surviving-block mask   (tiny)
  refine: box_scan(rows of surviving blocks, boxes) -> counts

Morton ordering makes a box query touch O(surface) blocks, replacing the
k-d tree's pointer-chased log factor with a *bytes* factor — the quantity
the TPU roofline actually prices (DESIGN.md §2). The same structure
shards trivially: rows are range-partitioned across the `data` axis and
each shard prunes/refines locally (distributed_query).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.boxes import BoxSet, concat_box_arrays
from repro.core.capacity import pow2above, quantum_bucket
from repro.kernels import ops as kops
from repro.kernels import ref as kref


# ----------------------------------------------------------------------
# Morton codes
# ----------------------------------------------------------------------

def _part_bits(v: np.ndarray, ndims: int, nbits: int) -> np.ndarray:
    """Spread the low ``nbits`` of v so consecutive bits are ndims apart."""
    out = np.zeros_like(v, dtype=np.uint64)
    for b in range(nbits):
        out |= ((v >> b) & 1).astype(np.uint64) << (b * ndims)
    return out


def morton_code(x: np.ndarray, nbits: int = 8) -> np.ndarray:
    """x: [N, d'] floats -> [N] uint64 Morton codes of per-dim quantiles.

    Quantile (rank) quantisation equalises bucket occupancy, which keeps
    zone maps tight even for skewed feature marginals."""
    n, d = x.shape
    nbits = min(nbits, 64 // max(d, 1))
    code = np.zeros(n, np.uint64)
    levels = 1 << nbits
    ranks = np.empty(n, np.int64)
    for j in range(d):
        # rank = inverse of the sort permutation; one argsort + scatter
        # instead of argsort(argsort(.)) halves the build-path sort work
        order = np.argsort(x[:, j], kind="stable")
        ranks[order] = np.arange(n, dtype=np.int64)
        q = (ranks * levels // max(n, 1)).astype(np.uint64)
        code |= _part_bits(q, d, nbits) << j
    return code


# ----------------------------------------------------------------------
# index
# ----------------------------------------------------------------------

@dataclass
class ZoneMapIndex:
    dims: np.ndarray              # [d'] feature ids this index covers
    perm: np.ndarray              # [Np] row permutation (Morton order, padded)
    rows: np.ndarray              # [Np, d'] permuted subset features (padded)
    zlo: np.ndarray               # [NB, d'] per-block min
    zhi: np.ndarray               # [NB, d'] per-block max
    block: int
    n_rows: int                   # real (unpadded) rows
    subset_id: int = -1
    # lazily-populated device mirror: (rows3 [NB, block, d'], zlo, zhi)
    _dev: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = field(
        default=None, repr=False, compare=False)
    # lazily-populated global-row-id mirror [NB, block] int32 (-1 padding)
    _dev_gids: Optional[jax.Array] = field(
        default=None, repr=False, compare=False)
    # lazily-populated quantized mirror (survivor-sparse serving):
    # (qrows3 int8, c0 f32, scale f32, zlo16 f16, zhi16 f16)
    _dev_quant: Optional[Tuple[jax.Array, ...]] = field(
        default=None, repr=False, compare=False)

    @property
    def n_blocks(self) -> int:
        return int(self.zlo.shape[0])

    def device_arrays(self) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """(rows3 [NB, block, d'], zlo [NB, d'], zhi [NB, d']) as jax
        arrays, uploaded ONCE and cached — every fused query reuses the
        same device buffers, so no index bytes cross host<->device on the
        online path (only the tiny boxes do)."""
        if self._dev is None:
            rows3 = jnp.asarray(self.rows).reshape(
                self.n_blocks, self.block, -1)
            self._dev = (rows3, jnp.asarray(self.zlo), jnp.asarray(self.zhi))
        return self._dev

    def device_gids(self) -> jax.Array:
        """[NB, block] int32 GLOBAL row id per (block, slot) — the
        permutation reshaped to the block grid, -1 on padding slots.
        The dense accumulate scatters gathered counts by it
        (kernels/ops.accumulate_scores) and the survivor-sparse path
        labels fused tiles with it (kernels/ops.tile_candidates);
        uploaded once and cached like the other mirrors. For a
        monolithic index global id == original row id;
        sharded/segmented wrappers add their own offsets."""
        if self._dev_gids is None:
            self._dev_gids = jnp.asarray(
                np.ascontiguousarray(self.perm.astype(np.int32).reshape(
                    self.n_blocks, self.block)))
        return self._dev_gids

    def device_quantized(self) -> Tuple[jax.Array, ...]:
        """Compressed device mirror for the quantized-prune serving path:
        (qrows3 [NB, block, d'] int8, c0 [d'] f32, scale [d'] f32,
         zlo16 [NB, d'] f16, zhi16 [NB, d'] f16).

        Rows are per-dim affine-quantized (the train/compression.py
        per-tensor int8 idiom, per-DIM here because subset dims have
        unrelated ranges): code t = round((x - c0) / scale) in [0, 254],
        stored as int8 t - 127, so |x - (c0 + t * scale)| <= scale / 2.
        Zone maps are cast to f16 WIDENED outward (zlo rounded down, zhi
        rounded up via nextafter) so the f16 zone prune keeps every block
        the f32 prune keeps. Both halves make the quantized prune
        CONSERVATIVE: it may keep false candidates, never drop a true
        survivor — the exact f32 re-check on the candidate set restores
        bitwise-exact counts (DESIGN.md §13). ~4.6x fewer resident bytes
        than the f32 mirror (int8 rows + f16 zones vs f32 both)."""
        if self._dev_quant is None:
            real = self.perm >= 0
            rows = self.rows
            rr = rows[real]
            if rr.size:
                c0 = rr.min(0).astype(np.float32)
                s = np.maximum((rr.max(0) - c0) / 254.0,
                               1e-12).astype(np.float32)
            else:
                c0 = np.zeros(rows.shape[1], np.float32)
                s = np.full(rows.shape[1], 1e-12, np.float32)
            t = np.full(rows.shape, 254.0, np.float32)   # padding: inert
            t[real] = np.clip(np.round((rr - c0) / s), 0.0, 254.0)
            q = (t - 127.0).astype(np.int8).reshape(
                self.n_blocks, self.block, -1)
            zlo16 = self.zlo.astype(np.float16)
            zhi16 = self.zhi.astype(np.float16)
            # widen outward where the nearest-even cast rounded inward
            zlo16 = np.where(zlo16.astype(np.float32) > self.zlo,
                             np.nextafter(zlo16, np.float16(-np.inf)),
                             zlo16)
            zhi16 = np.where(zhi16.astype(np.float32) < self.zhi,
                             np.nextafter(zhi16, np.float16(np.inf)),
                             zhi16)
            self._dev_quant = (jnp.asarray(q), jnp.asarray(c0),
                               jnp.asarray(s), jnp.asarray(zlo16),
                               jnp.asarray(zhi16))
        return self._dev_quant

    def device_bytes(self) -> dict:
        """Actual RESIDENT device-mirror bytes by kind (0 for mirrors not
        yet uploaded) — what index_stats aggregates so the memory claims
        are measurable rather than inferred."""
        out = {"rows": 0, "zones": 0, "gids": 0, "quantized": 0}
        if self._dev is not None:
            rows3, zlo, zhi = self._dev
            out["rows"] = int(rows3.nbytes)
            out["zones"] = int(zlo.nbytes) + int(zhi.nbytes)
        if self._dev_gids is not None:
            out["gids"] = int(self._dev_gids.nbytes)
        if self._dev_quant is not None:
            out["quantized"] = int(sum(a.nbytes for a in self._dev_quant))
        return out

    def stats(self) -> dict:
        return {"blocks": self.n_blocks, "block_rows": self.block,
                "rows": self.n_rows, "dims": self.dims.tolist(),
                "bytes": int(self.rows.nbytes)}


def build_index(x: np.ndarray, dims: np.ndarray, block: int = 1024,
                subset_id: int = -1) -> ZoneMapIndex:
    """x: [N, D] full features; dims: subset feature ids."""
    sub = np.ascontiguousarray(np.asarray(x, np.float32)[:, dims])
    n = sub.shape[0]
    code = morton_code(sub)
    perm = np.argsort(code, kind="stable")
    rows = sub[perm]
    pad = (-n) % block
    if pad:
        rows = np.concatenate(
            [rows, np.full((pad, rows.shape[1]), np.inf, np.float32)])
        perm = np.concatenate([perm, np.full(pad, -1, perm.dtype)])
    nb = rows.shape[0] // block
    # explicit trailing dim: -1 cannot be inferred for an EMPTY shard
    # (zero rows -> zero blocks), which sharded partitions may produce
    blocks = rows.reshape(nb, block, sub.shape[1])
    # zone maps over REAL rows only: padded +inf rows would otherwise leak
    # into the tail block's zhi, making it overlap every box and inflating
    # blocks_touched/bytes_touched (the tail block has >= 1 real row, so
    # the masked reductions are never empty)
    real = (np.arange(rows.shape[0]) < n).reshape(nb, block, 1)
    zlo = np.where(real, blocks, np.inf).min(1)
    zhi = np.where(real, blocks, -np.inf).max(1)
    return ZoneMapIndex(np.asarray(dims), perm, rows, zlo, zhi, block, n,
                        subset_id)


def query_index(index: ZoneMapIndex, boxes: BoxSet,
                use_pallas: bool = True) -> Tuple[np.ndarray, dict]:
    """Returns (counts [n_rows] int32 in ORIGINAL row order, stats).

    stats reports blocks_touched / rows_touched / bytes_touched — the
    quantities the paper's speedup comes from."""
    assert np.array_equal(index.dims, boxes.dims), "box subset != index subset"
    blo = jnp.asarray(boxes.lo)
    bhi = jnp.asarray(boxes.hi)
    zlo = jnp.asarray(index.zlo)
    zhi = jnp.asarray(index.zhi)
    if use_pallas:
        mask = np.asarray(kops.zone_prune(zlo, zhi, blo, bhi))     # [NB, B]
    else:
        mask = np.asarray(kref.zone_prune_ref(zlo, zhi, blo, bhi))
    hit = mask.any(1)
    hit_ids = np.nonzero(hit)[0]
    n_hit = len(hit_ids)
    counts = np.zeros(index.rows.shape[0], np.int32)
    if n_hit:
        rows = index.rows.reshape(index.n_blocks, index.block, -1)[hit_ids]
        rows = rows.reshape(-1, rows.shape[-1])
        if use_pallas:
            c = np.asarray(kops.box_scan(jnp.asarray(rows), blo, bhi))
        else:
            c = np.asarray(kref.box_scan_ref(jnp.asarray(rows), blo, bhi))
        for k, b in enumerate(hit_ids):
            counts[b * index.block:(b + 1) * index.block] = \
                c[k * index.block:(k + 1) * index.block]
    # back to original order
    out = np.zeros(index.n_rows, np.int32)
    valid = index.perm >= 0
    out[index.perm[valid]] = counts[valid]
    stats = {
        "blocks_touched": int(n_hit),
        "blocks_total": index.n_blocks,
        "rows_touched": int(n_hit * index.block),
        "bytes_touched": int(n_hit * index.block * index.rows.shape[1] * 4),
        "bytes_total": int(index.rows.nbytes),
        "prune_fraction": 1.0 - n_hit / max(index.n_blocks, 1),
    }
    return out, stats


# ----------------------------------------------------------------------
# fused device-resident query path
# ----------------------------------------------------------------------

_BOX_BUCKET = 8   # boxes padded to a multiple of this -> stable jit keys


def pad_boxes(lo: np.ndarray, hi: np.ndarray, owner: Optional[np.ndarray]):
    """Pad the box count to a _BOX_BUCKET multiple with impossible boxes
    (lo=+inf > hi=-inf): they survive no zone and contain no row, so
    results are unchanged while the fused jit cache stays hot across
    queries with varying box counts. Device-resident boxes (jax arrays,
    from the batched trainer) are padded on device; the owner map is
    always host-side."""
    b = lo.shape[0]
    pad = quantum_bucket(b, _BOX_BUCKET) - b
    if pad == 0:
        return lo, hi, owner
    d = lo.shape[1]
    lo = concat_box_arrays([lo, np.full((pad, d), np.inf, np.float32)])
    hi = concat_box_arrays([hi, np.full((pad, d), -np.inf, np.float32)])
    if owner is not None:
        owner = np.concatenate([owner, np.zeros(pad, owner.dtype)])
    return lo, hi, owner


def fused_stats(index: ZoneMapIndex, n_hit: int, capacity: int,
                n_boxes: int) -> dict:
    """blocks_touched counts surviving blocks actually refined (comparable
    to query_index); the bytes/rows figures price the CAPACITY-sized
    gather the device really performs — the fused path reads capacity
    blocks regardless of how few survive, which is exactly why callers
    size capacity just above the typical survivor count (DESIGN.md §6)."""
    touched = min(n_hit, capacity)
    return {
        "blocks_touched": touched,
        "blocks_gathered": capacity,
        "blocks_total": index.n_blocks,
        "rows_touched": int(capacity * index.block),
        "bytes_touched": int(capacity * index.block * index.rows.shape[1] * 4),
        "bytes_total": int(index.rows.nbytes),
        "prune_fraction": 1.0 - capacity / max(index.n_blocks, 1),
        "capacity": capacity,
        "survivors": n_hit,
        "overflowed": n_hit > capacity,
        "n_boxes": n_boxes,
    }


def _scatter_fused(index: ZoneMapIndex, counts: np.ndarray,
                   cand: np.ndarray, n_hit: int, capacity: int,
                   n_queries: int) -> np.ndarray:
    """Host-side de-mux of the fused result: counts [C, block, Q] for the
    gathered blocks -> [n_queries, n_rows] in ORIGINAL row order. Only the
    capacity-sized slice ever crosses device->host; all untouched blocks
    are zero by construction."""
    out = np.zeros((n_queries, index.n_rows), np.int32)
    k = min(n_hit, capacity)
    if k:
        perm_blocks = index.perm.reshape(index.n_blocks, index.block)[cand[:k]]
        flat_perm = perm_blocks.reshape(-1)                  # [k * block]
        flat_counts = counts[:k].reshape(k * index.block, -1)
        real = flat_perm >= 0
        out[:, flat_perm[real]] = flat_counts[real].T
    return out


def _resolve_capacity(index: ZoneMapIndex, capacity: Optional[int]) -> int:
    if capacity is None:
        capacity = index.n_blocks            # always-exact default
    return int(min(max(capacity, 1), index.n_blocks))


def query_index_fused(index: ZoneMapIndex, boxes: BoxSet, *,
                      capacity: Optional[int] = None,
                      use_pallas: bool = True) -> Tuple[np.ndarray, dict]:
    """Device-resident counterpart of query_index: zone-prune -> bounded
    block gather -> refine run as ONE jit'd device program (kops.
    fused_query) over the cached device mirror of the index. Identical
    counts to query_index whenever ``capacity`` covers the survivors
    (default: n_blocks, i.e. always); with a smaller capacity, survivors
    past the bound are dropped and stats["overflowed"] is set."""
    assert np.array_equal(index.dims, boxes.dims), "box subset != index subset"
    capacity = _resolve_capacity(index, capacity)
    rows3, zlo, zhi = index.device_arrays()
    lo, hi, _ = pad_boxes(boxes.lo, boxes.hi, None)
    onehot = jnp.ones((lo.shape[0], 1), jnp.float32)
    counts_dev, cand_dev, n_hit_dev = kops.fused_query(
        rows3, zlo, zhi, jnp.asarray(lo), jnp.asarray(hi), onehot,
        capacity=capacity, use_pallas=use_pallas)
    n_hit = int(n_hit_dev)
    out = _scatter_fused(index, np.asarray(counts_dev), np.asarray(cand_dev),
                         n_hit, capacity, 1)[0]
    return out, fused_stats(index, n_hit, capacity, boxes.n_boxes)


def query_index_fused_multi(index: ZoneMapIndex, boxes: BoxSet,
                            owner: np.ndarray, n_queries: int, *,
                            capacity: Optional[int] = None,
                            use_pallas: bool = True
                            ) -> Tuple[np.ndarray, dict]:
    """Answer MANY concurrent queries' boxes on one index with ONE fused
    device call. ``owner[b]`` maps box b to its query; the box->query
    one-hot rides into the refine kernel, which de-muxes membership into
    per-query counts on device (box_scan_seg). Returns
    (counts [n_queries, n_rows] int32 in ORIGINAL row order, stats).

    Each query's counts are bitwise-identical to running query_index on
    its own boxes, provided capacity covers the UNION's survivors."""
    assert np.array_equal(index.dims, boxes.dims), "box subset != index subset"
    assert owner.shape == (boxes.n_boxes,)
    capacity = _resolve_capacity(index, capacity)
    rows3, zlo, zhi = index.device_arrays()
    lo, hi, owner_p = pad_boxes(boxes.lo, boxes.hi,
                                np.asarray(owner, np.int32))
    # pad boxes are impossible (contain nothing), so their owner-0 rows in
    # the one-hot contribute zero counts
    onehot = jnp.asarray(
        (owner_p[:, None] == np.arange(n_queries)[None]).astype(np.float32))
    counts_dev, cand_dev, n_hit_dev = kops.fused_query(
        rows3, zlo, zhi, jnp.asarray(lo), jnp.asarray(hi), onehot,
        capacity=capacity, use_pallas=use_pallas)
    n_hit = int(n_hit_dev)
    out = _scatter_fused(index, np.asarray(counts_dev), np.asarray(cand_dev),
                         n_hit, capacity, n_queries)
    return out, fused_stats(index, n_hit, capacity, boxes.n_boxes)


def full_scan(x: np.ndarray, lo: np.ndarray, hi: np.ndarray,
              use_pallas: bool = True) -> np.ndarray:
    """Scan baseline over the FULL feature matrix (what DT/RF must do)."""
    if use_pallas:
        return np.asarray(kops.box_scan(jnp.asarray(np.asarray(x, np.float32)),
                                        jnp.asarray(lo), jnp.asarray(hi)))
    return np.asarray(kref.box_scan_ref(jnp.asarray(np.asarray(x, np.float32)),
                                        jnp.asarray(lo), jnp.asarray(hi)))


# ----------------------------------------------------------------------
# sharded index: the catalog row-space partitioned across devices
# ----------------------------------------------------------------------

def shard_offsets(n: int, n_shards: int) -> np.ndarray:
    """[S + 1] global row offsets of an even ceil-split partition: every
    shard owns ceil(n / S) rows except a RAGGED tail (the last occupied
    shard is short; pathological tiny catalogs may leave trailing shards
    empty — the stacked device mirrors make empty shards inert rather
    than illegal, so shard-count invariance holds all the way down)."""
    per = -(-max(int(n), 1) // n_shards)
    return np.minimum(np.arange(n_shards + 1, dtype=np.int64) * per, n)


@dataclass
class ShardedZoneMapIndex:
    """One feature subset's index, row-range-partitioned across shards.

    Shard s owns global rows [offsets[s], offsets[s+1]) and holds its OWN
    ZoneMapIndex over them (Morton order is shard-local; a row's global
    id is its shard offset + local id, so ids never need a lookup table).
    The device mirror stacks every shard to the SAME padded geometry —
    [S, NBmax, block, d'] rows, [S, NBmax, d'] zones, [S, NBmax, block]
    row-id grids — so one program (vmapped on a single device,
    shard_map'd across a mesh) serves every shard: padded zones are empty
    intervals that survive no prune, padded rows are +inf and inside no
    box, and padded grid slots hold -1, which accumulate_scores drops.
    The ceil-split makes every shard but a ragged tail exactly Nloc_max
    rows long, so a global id is also the row of the flattened [S *
    Nloc_max] score buffer. Query results are therefore
    bitwise-independent of the shard count (tests/test_sharded_query.py
    pins it)."""
    dims: np.ndarray
    shards: List[ZoneMapIndex]    # per-shard local indexes
    offsets: np.ndarray           # [S + 1] global row offsets
    block: int
    n_rows: int
    subset_id: int = -1
    _dev: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = field(
        default=None, repr=False, compare=False)
    _dev_gids: Optional[jax.Array] = field(
        default=None, repr=False, compare=False)
    # mesh the cached mirrors were committed for (device placement only —
    # the VALUES are identical however the arrays are laid out)
    _dev_mesh: object = field(default=None, repr=False, compare=False)

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    @property
    def nb_max(self) -> int:
        """Per-shard block-count bound — the stacked mirror's NBmax."""
        return max(max(sh.n_blocks for sh in self.shards), 1)

    @property
    def n_blocks(self) -> int:
        """PER-SHARD blocks (== nb_max): capacities bound the gather each
        shard performs, so capacity sizing reads the per-shard figure
        exactly like the single-device index exposes its own."""
        return self.nb_max

    @property
    def total_blocks(self) -> int:
        return sum(sh.n_blocks for sh in self.shards)

    @property
    def n_loc_max(self) -> int:
        """Rows of the widest shard — the stacked score-buffer width."""
        return max(max(sh.n_rows for sh in self.shards), 1)

    @property
    def shard_rows(self) -> np.ndarray:
        return np.asarray([sh.n_rows for sh in self.shards], np.int64)

    @property
    def rows_nbytes(self) -> int:
        return int(sum(sh.rows.nbytes for sh in self.shards))

    @staticmethod
    def _put(arr: np.ndarray, mesh) -> jax.Array:
        """Upload sharded over the mesh's "shards" axis (axis 0) so the
        per-call jit never pays a reshard — or plainly when no mesh."""
        if mesh is None:
            return jnp.asarray(arr)
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        return jax.device_put(arr, NamedSharding(mesh, P("shards")))

    def device_arrays(self, mesh=None) -> Tuple[jax.Array, jax.Array,
                                                jax.Array]:
        """(rows4 [S, NBmax, block, d'], zlo3, zhi3 [S, NBmax, d']),
        uploaded ONCE and cached — same contract as the single-device
        mirror, one stacked copy for the whole shard set, committed
        shard-per-device when a mesh is given."""
        if self._dev is None or self._dev_mesh is not mesh:
            s, nbm, d = self.n_shards, self.nb_max, len(self.dims)
            rows4 = np.full((s, nbm, self.block, d), np.inf, np.float32)
            zlo3 = np.full((s, nbm, d), np.inf, np.float32)
            zhi3 = np.full((s, nbm, d), -np.inf, np.float32)
            for i, sh in enumerate(self.shards):
                nb = sh.n_blocks
                rows4[i, :nb] = sh.rows.reshape(nb, self.block, d)
                zlo3[i, :nb] = sh.zlo
                zhi3[i, :nb] = sh.zhi
            self._dev = (self._put(rows4, mesh), self._put(zlo3, mesh),
                         self._put(zhi3, mesh))
            self._dev_mesh = mesh
            self._dev_gids = None          # re-commit alongside
        return self._dev

    def device_gids(self, mesh=None) -> jax.Array:
        """[S, NBmax, block] int32 GLOBAL row ids per (shard, block,
        slot), -1 on padding slots AND padding blocks. A shard's global
        id is its offset + local Morton permutation — the same content
        serves the mesh formulation (sharded per device) and the flat
        single-device fallback (reshaped to [S * NBmax, block] inside
        the jit), because global ids do not depend on placement."""
        if self._dev_gids is None or self._dev_mesh is not mesh:
            s, nbm = self.n_shards, self.nb_max
            g = np.full((s, nbm, self.block), -1, np.int32)
            for i, sh in enumerate(self.shards):
                if sh.n_rows:
                    loc = sh.perm.astype(np.int32).reshape(
                        sh.n_blocks, self.block)
                    g[i, :sh.n_blocks] = np.where(
                        loc >= 0, loc + np.int32(self.offsets[i]), -1)
            self.device_arrays(mesh)       # keep one mesh for the mirror
            self._dev_gids = self._put(g, mesh)
        return self._dev_gids

    def device_bytes(self) -> dict:
        """Resident device-mirror bytes by kind for the STACKED mirrors
        (the per-shard host indexes never upload their own)."""
        out = {"rows": 0, "zones": 0, "gids": 0, "quantized": 0}
        if self._dev is not None:
            rows4, zlo3, zhi3 = self._dev
            out["rows"] = int(rows4.nbytes)
            out["zones"] = int(zlo3.nbytes) + int(zhi3.nbytes)
        if self._dev_gids is not None:
            out["gids"] = int(self._dev_gids.nbytes)
        return out

    def stats(self) -> dict:
        return {"n_shards": self.n_shards, "blocks": self.total_blocks,
                "blocks_per_shard_max": self.nb_max,
                "block_rows": self.block, "rows": self.n_rows,
                "shard_rows": self.shard_rows.tolist(),
                "dims": self.dims.tolist(), "bytes": self.rows_nbytes}


def build_sharded_index(x: np.ndarray, dims: np.ndarray, n_shards: int,
                        block: int = 1024,
                        subset_id: int = -1) -> ShardedZoneMapIndex:
    """Partition the catalog row-space into ``n_shards`` contiguous
    ranges and build one ZoneMapIndex per range. Global ids are offset +
    local id, so the partition IS the id map."""
    n = np.asarray(x).shape[0]
    offs = shard_offsets(n, n_shards)
    shards = [build_index(np.asarray(x)[offs[s]:offs[s + 1]], dims,
                          block=block, subset_id=subset_id)
              for s in range(n_shards)]
    return ShardedZoneMapIndex(np.asarray(dims), shards, offs, block, n,
                               subset_id)


def query_index_sharded(sindex: ShardedZoneMapIndex, boxes: BoxSet,
                        use_pallas: bool = True) -> Tuple[np.ndarray, dict]:
    """Host-oracle counterpart of query_index for a sharded index:
    per-shard query_index, counts reassembled into GLOBAL row order.
    Counts are bitwise those of the unsharded index (membership is a
    per-row predicate — the partition only relocates rows)."""
    out = np.zeros(sindex.n_rows, np.int32)
    agg = {"blocks_touched": 0, "blocks_total": 0, "rows_touched": 0,
           "bytes_touched": 0, "bytes_total": 0}
    for sh, o0 in zip(sindex.shards, sindex.offsets[:-1]):
        if sh.n_rows == 0:
            continue
        c, st = query_index(sh, boxes, use_pallas=use_pallas)
        out[o0:o0 + sh.n_rows] = c
        for k in agg:
            agg[k] += st[k]
    agg["prune_fraction"] = 1.0 - agg["blocks_touched"] / max(
        agg["blocks_total"], 1)
    agg["n_shards"] = sindex.n_shards
    return out, agg


def _shard_call(local, mesh, n_sharded: int, n_repl: int):
    """Lift a per-shard ``local`` to a function over stacked [S, ...]
    arrays: vmap over the leading axis when ``mesh`` is None (the
    single-device fallback — same math, same bits), else jax.shard_map
    over the mesh's "shards" axis. ``local`` sees unbatched per-shard
    arrays either way; scalars come back as [S]. The first ``n_sharded``
    arguments are stacked/sharded, the rest replicated."""
    if mesh is None:
        return jax.vmap(local, in_axes=(0,) * n_sharded + (None,) * n_repl,
                        axis_name="shards")

    from jax.sharding import PartitionSpec as P

    def wrapped(*args):
        sh = [a[0] for a in args[:n_sharded]]     # strip the size-1 axis
        out = local(*sh, *args[n_sharded:])
        return tuple(jnp.asarray(o)[None] for o in out)

    return jax.shard_map(wrapped, mesh=mesh,
                         in_specs=(P("shards"),) * n_sharded
                         + (P(),) * n_repl,
                         out_specs=P("shards"), check_vma=False)


# the jit-builder caches are BOUNDED: their keys hold Mesh references,
# and a serving process that periodically rebuilds its engine (catalog
# refresh) must not retain every old mesh + compiled closure forever
@functools.lru_cache(maxsize=128)
def _flat_query_acc_fn(capacity: int, use_pallas: bool):
    """Single-device fallback scoring: the stacked shard mirrors run as
    ONE fused index over the [S * NBmax] virtual block space (padding
    blocks have empty zones and survive no prune), with the counts
    scattered by global id straight into the [S, Nloc_max, Q] buffer's
    flat view (a global id IS its flat row under the ceil-split). One
    device doing all shards' work pays the SINGLE-index cost — one
    global capacity, no per-shard rounding waste — while returning the
    same bits as the mesh formulation. ``capacity`` is GLOBAL here (the
    engine sizes it like the single-device path)."""

    def score_flat_dense(rows4, zlo3, zhi3, gids3, scores, lo, hi, oh):
        s, nbm, block, d = rows4.shape
        nlm, q = scores.shape[1], scores.shape[2]
        counts, cand, n_hit = kops.fused_query(
            rows4.reshape(s * nbm, block, d),
            zlo3.reshape(s * nbm, d), zhi3.reshape(s * nbm, d),
            lo, hi, oh, capacity=capacity, use_pallas=use_pallas)
        # an overflowed attempt adds nothing; the caller retries it
        n_live = jnp.where(n_hit <= capacity, n_hit, 0)
        acc = kops.accumulate_scores(scores.reshape(s * nlm, q), counts,
                                     cand, n_live,
                                     gids3.reshape(s * nbm, block))
        # same [3]-int stat contract as the mesh path, with the GLOBAL
        # survivor count in every slot (there is no per-shard max here)
        st3 = jnp.stack([n_hit, jnp.minimum(n_hit, capacity), n_hit])
        return acc.reshape(scores.shape), st3

    return jax.jit(score_flat_dense)


@functools.lru_cache(maxsize=128)
def _sharded_query_acc_fn(mesh, capacity: int, use_pallas: bool):
    """jit'd (and cached — eager shard_map re-traces per CALL, which is
    exactly the dispatch overhead the fused path exists to avoid) fused
    per-shard query + survivor-stat reduction + CONDITIONAL score
    accumulation, all as ONE device program per subset."""

    def local(rows3, zlo, zhi, gids, sc, lo, hi, oh):
        counts, cand, n_hit = kops.fused_query(
            rows3, zlo, zhi, lo, hi, oh, capacity=capacity,
            use_pallas=use_pallas)
        # keep the accumulation ONLY if no shard overflowed: an overflow
        # dropped survivors, so the whole subset re-runs at a bigger
        # capacity next round (speculating the common no-overflow case
        # saves a second dispatch per subset; an overflowed attempt's
        # increment is dropped, not added)
        ok = jax.lax.pmax(n_hit, "shards") <= capacity
        # shard-local rows: global id minus the shard's offset, which
        # the ceil-split puts at shard * Nloc_max; padding stays < 0
        base = jax.lax.axis_index("shards") * sc.shape[0]
        acc = kops.accumulate_scores(sc, counts, cand,
                                     jnp.where(ok, n_hit, 0), gids - base)
        return acc, n_hit

    inner = _shard_call(local, mesh, 5, 3)

    def score_sharded_dense(rows4, zlo3, zhi3, gids3, scores, lo, hi, oh):
        acc, n_hit = inner(rows4, zlo3, zhi3, gids3, scores, lo, hi, oh)
        # reduce the [S] survivor counts to THREE ints inside the program
        # (max -> retry capacity, sum-refined + sum -> stats): the one
        # batched host sync stays flat in shard count
        st3 = jnp.stack([n_hit.max(),
                         jnp.minimum(n_hit, capacity).sum(),
                         n_hit.sum()])
        return acc, st3

    return jax.jit(score_sharded_dense)


def sharded_query_accumulate(sindex: ShardedZoneMapIndex,
                             scores: jax.Array, blo: jax.Array,
                             bhi: jax.Array, onehot: jax.Array, *,
                             capacity: int, mesh=None,
                             use_pallas: bool = True):
    """One subset's boxes against every shard, ONE device program: each
    shard runs the SAME fused zone-prune -> bounded gather -> segmented
    box-scan (kernels/ops.fused_query) over its slice of the stacked
    device mirror and scatters its counts by row id into its [Nloc_max,
    Q] slice of the score buffer (kernels/ops.accumulate_scores; grid
    padding is -1 and drops). ``capacity`` bounds the gather PER SHARD;
    if ANY shard overflows the accumulation is discarded on device and
    the caller retries the subset.

    Returns (scores' [S, Nloc_max, Q],
             hit_stats [3] int32 device scalars =
                 (max n_hit, sum of min(n_hit, C), sum n_hit)) —
    nothing crosses to the host here.

    With ``mesh=None`` (single device) the shard set runs as ONE fused
    index over the virtual block space instead (_flat_query_acc_fn):
    identical bits, single-index cost — and ``capacity`` is then the
    GLOBAL gather bound, with the returned stats carrying the global
    survivor count in each slot."""
    rows4, zlo3, zhi3 = sindex.device_arrays(mesh)
    if mesh is None:
        fn = _flat_query_acc_fn(int(capacity), bool(use_pallas))
    else:
        fn = _sharded_query_acc_fn(mesh, int(capacity), bool(use_pallas))
    return fn(rows4, zlo3, zhi3, sindex.device_gids(mesh), scores,
              blo, bhi, onehot)


# ----------------------------------------------------------------------
# survivor-sparse scoring path (DESIGN.md §13)
# ----------------------------------------------------------------------
# Two-phase per subset: a PROBE jit (fused zone-prune -> bounded gather ->
# refine -> tile labelling, plus a fixed-size int stat vector) runs for
# every pending subset, then ONE batched host sync of the stacked stat
# vectors sizes the survivor-tile compaction EXACTLY (row_capacity =
# pow2ceil(n_match)), so the tile extraction never overflows and the
# host-sync count stays identical to the dense path. The probe's stat
# vector is a FIXED length per formulation — host traffic cannot vary
# with shard count or survivor population.

@functools.lru_cache(maxsize=128)
def _sparse_probe_fn(capacity: int, use_pallas: bool):
    """Monolithic sparse probe: fused_query + tile labelling.
    Returns (counts [C, block, Q], gids [C, block], ok [C, block],
             st [2] int32 = (n_hit, n_match))."""

    def score_sparse_probe(rows3, zlo, zhi, gids_b, lo, hi, oh):
        counts, cand, n_hit = kops.fused_query(
            rows3, zlo, zhi, lo, hi, oh, capacity=capacity,
            use_pallas=use_pallas)
        gids, ok = kops.tile_candidates(counts, cand, gids_b)
        st = jnp.stack([n_hit, ok.sum().astype(jnp.int32)])
        return counts, gids, ok, st

    return jax.jit(score_sparse_probe)


@functools.lru_cache(maxsize=128)
def _flat_sparse_probe_fn(capacity: int, use_pallas: bool):
    """Single-device sparse probe over the stacked shard mirrors run as
    ONE fused index on the virtual block space (the sparse analogue of
    _flat_query_acc_fn). ``capacity`` is GLOBAL. Returns flat tiles
    (counts [C, block, Q], gids/ok [C, block]) and the same [5] stat
    contract as the mesh probe — global figures in the per-shard slots."""

    def score_flat_sparse_probe(rows4, zlo3, zhi3, gids3, lo, hi, oh):
        s, nbm, block, d = rows4.shape
        counts, cand, n_hit = kops.fused_query(
            rows4.reshape(s * nbm, block, d),
            zlo3.reshape(s * nbm, d), zhi3.reshape(s * nbm, d),
            lo, hi, oh, capacity=capacity, use_pallas=use_pallas)
        gids, ok = kops.tile_candidates(counts, cand,
                                        gids3.reshape(s * nbm, block))
        nm = ok.sum().astype(jnp.int32)
        st = jnp.stack([n_hit, jnp.minimum(n_hit, capacity), n_hit,
                        nm, nm])
        return counts, gids, ok, st

    return jax.jit(score_flat_sparse_probe)


@functools.lru_cache(maxsize=128)
def _sharded_sparse_probe_fn(mesh, capacity: int, use_pallas: bool):
    """Mesh sparse probe: per-shard fused_query + tile labelling under
    shard_map, stats reduced to FIVE ints inside the program —
    (max n_hit, sum min(n_hit, C), sum n_hit, max n_match, sum n_match):
    max n_hit drives overflow retry exactly like the dense path, max
    n_match sizes the per-shard tile compaction, the sums feed stats.
    Returns sharded (counts [S, C, block, Q], gids/ok [S, C, block],
    st [5])."""

    def local(rows3, zlo, zhi, gids_b, lo, hi, oh):
        counts, cand, n_hit = kops.fused_query(
            rows3, zlo, zhi, lo, hi, oh, capacity=capacity,
            use_pallas=use_pallas)
        gids, ok = kops.tile_candidates(counts, cand, gids_b)
        return counts, gids, ok, n_hit, ok.sum().astype(jnp.int32)

    inner = _shard_call(local, mesh, 4, 3)

    def score_sharded_sparse_probe(rows4, zlo3, zhi3, gids3, lo, hi, oh):
        counts, gids, ok, n_hit, nm = inner(rows4, zlo3, zhi3, gids3,
                                            lo, hi, oh)
        st = jnp.stack([n_hit.max(), jnp.minimum(n_hit, capacity).sum(),
                        n_hit.sum(), nm.max(), nm.sum()])
        return counts, gids, ok, st

    return jax.jit(score_sharded_sparse_probe)


@functools.lru_cache(maxsize=128)
def _sharded_tiles_fn(mesh, row_capacity: int):
    """Per-shard survivor-tile compaction + replicate + flatten, one jit.
    ``row_capacity`` bounds rows PER SHARD (sized from the probe's max
    n_match, so exact). The tiny [S, rcap] tiles are replicated before
    flattening for the same reason _sharded_rank_fn replicates its
    candidate lists: without the constraint GSPMD would distribute the
    downstream merge sort. Keys carry GLOBAL ids, so flattening across
    shards needs no offset fixup and the merged tiles feed sparse_topk
    directly — no per-shard top-k or cross-shard merge stage at all."""

    def local(counts, gids, ok):
        keys, vals, _ = kops.survivor_tiles(counts, gids, ok,
                                            row_capacity=row_capacity)
        return keys, vals

    inner = _shard_call(local, mesh, 3, 0)

    def fn(counts, gids, ok):
        keys, vals = inner(counts, gids, ok)     # [S, rcap], [S, rcap, Q]
        if mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            rep = NamedSharding(mesh, P())
            keys = jax.lax.with_sharding_constraint(keys, rep)
            vals = jax.lax.with_sharding_constraint(vals, rep)
        s, rc = keys.shape
        return keys.reshape(s * rc), vals.reshape(s * rc, -1)

    return jax.jit(fn)


def sparse_probe(index: ZoneMapIndex, blo: jax.Array, bhi: jax.Array,
                 onehot: jax.Array, *, capacity: int,
                 use_pallas: bool = True):
    """Phase A of the monolithic survivor-sparse path (see the section
    comment above). The caller syncs st (batched across subsets), then
    compacts tiles via kernels/ops.survivor_tiles at an exact capacity."""
    rows3, zlo, zhi = index.device_arrays()
    fn = _sparse_probe_fn(int(capacity), bool(use_pallas))
    return fn(rows3, zlo, zhi, index.device_gids(), blo, bhi, onehot)


def sharded_sparse_probe(sindex: ShardedZoneMapIndex, blo: jax.Array,
                         bhi: jax.Array, onehot: jax.Array, *,
                         capacity: int, mesh=None,
                         use_pallas: bool = True):
    """Phase A of the sharded survivor-sparse path. ``mesh=None`` runs
    the flat single-device formulation (global capacity, flat tiles);
    with a mesh, per-shard tiles come back sharded and the caller
    compacts them via sharded_survivor_tiles. Both return the same [5]
    stat vector, so the one batched host sync is flat in shard count."""
    rows4, zlo3, zhi3 = sindex.device_arrays(mesh)
    gids3 = sindex.device_gids(mesh)
    if mesh is None:
        fn = _flat_sparse_probe_fn(int(capacity), bool(use_pallas))
    else:
        fn = _sharded_sparse_probe_fn(mesh, int(capacity),
                                      bool(use_pallas))
    return fn(rows4, zlo3, zhi3, gids3, blo, bhi, onehot)


def sharded_survivor_tiles(counts, gids, ok, *, row_capacity: int,
                           mesh=None):
    """Phase B of the mesh sharded sparse path: compact each shard's
    survivors and flatten to ([S * rcap] keys, [S * rcap, Q] vals)."""
    return _sharded_tiles_fn(mesh, int(row_capacity))(counts, gids, ok)


# ----------------------------------------------------------------------
# quantized-mirror probe (conservative prune + exact re-check)
# ----------------------------------------------------------------------

@functools.lru_cache(maxsize=128)
def _quant_probe_fn(capacity: int):
    """Quantized candidate probe: f16 widened-zone prune -> bounded int8
    block gather -> per-row code-space box test. The thresholds are
    computed in f32 code space: a row x inside box (lo, hi] has code
    t with |x - (c0 + t*s)| <= s/2, hence (lo - c0)/s - 0.5 < t <=
    (hi - c0)/s + 0.5; using TLO = floor((lo - c0)/s) - 1 and THI =
    ceil((hi - c0)/s) + 1 keeps a further >= 0.5-code margin on both
    sides, absorbing the rounding of the threshold arithmetic itself —
    the prune can only OVER-select (property-tested). +-inf box bounds
    (impossible pad boxes, open sides) propagate to +-inf thresholds
    with no NaN since scale >= 1e-12.

    Returns (gids [C, block] int32, cmask [C, block] bool,
             st [2] int32 = (n_hit, n_cand))."""

    def fn(qrows3, c0, scale, zlo16, zhi16, gids_b, lo, hi):
        mask = kref.zone_prune_ref(zlo16.astype(jnp.float32),
                                   zhi16.astype(jnp.float32), lo, hi)
        hit = mask.any(1)
        n_hit = hit.sum().astype(jnp.int32)
        cand, = jnp.nonzero(hit, size=capacity, fill_value=0)
        valid = jnp.arange(capacity) < n_hit
        q = qrows3[cand].astype(jnp.float32) + 127.0   # codes [0, 254]
        c, block, d = q.shape
        qf = q.reshape(c * block, d)
        tlo = jnp.floor((lo - c0[None]) / scale[None]) - 1.0   # [B, d']
        thi = jnp.ceil((hi - c0[None]) / scale[None]) + 1.0
        inside = ((qf[:, None, :] > tlo[None]) &
                  (qf[:, None, :] <= thi[None]))       # [C*block, B, d']
        m = jnp.all(inside, -1).any(-1).reshape(c, block)
        gids = jnp.take(gids_b, cand, axis=0)
        cmask = m & (gids >= 0) & valid[:, None]
        st = jnp.stack([n_hit, cmask.sum().astype(jnp.int32)])
        return gids, cmask, st

    return jax.jit(fn)


@functools.lru_cache(maxsize=128)
def _quant_compact_fn(row_capacity: int):
    """Compact the quantized candidate mask into a dense [rcap] global-id
    list (-1 past the live prefix) — the ONLY quantity that crosses to
    the host between prune and re-check, O(candidates) not O(N)."""

    def fn(gids, cmask):
        flat_ok = cmask.reshape(-1)
        idx, = jnp.nonzero(flat_ok, size=row_capacity, fill_value=0)
        nr = flat_ok.sum().astype(jnp.int32)
        live = jnp.arange(row_capacity) < nr
        cgids = jnp.where(live, gids.reshape(-1)[idx], -1)
        return cgids.astype(jnp.int32), nr

    return jax.jit(fn)


@jax.jit
def _quant_recheck_fn(xsub, cgids, lo, hi, oh):
    """Exact f32 re-check of the candidate rows: the same box predicate
    as the dense refine (box_scan_seg_ref over the SAME float inputs
    gives the same integer counts — membership is exact in f32), emitted
    directly as a survivor tile. Candidate rows the exact test rejects
    keep key validity but all-zero vals, which every downstream stage
    already treats as score-neutral."""
    counts = kref.box_scan_seg_ref(xsub, lo, hi, oh)
    live = cgids >= 0
    keys = jnp.where(live, cgids, kops.TILE_INVALID)
    vals = counts.astype(jnp.int32) * live[:, None]
    return keys, vals


def quantized_probe(index: ZoneMapIndex, blo: jax.Array, bhi: jax.Array,
                    *, capacity: int):
    """Phase A of the quantized path (monolithic static indexes)."""
    qrows3, c0, scale, zlo16, zhi16 = index.device_quantized()
    fn = _quant_probe_fn(int(capacity))
    return fn(qrows3, c0, scale, zlo16, zhi16, index.device_gids(),
              blo, bhi)


def quantized_compact(gids, cmask, *, row_capacity: int):
    return _quant_compact_fn(int(row_capacity))(gids, cmask)


def quantized_recheck(xsub: jax.Array, cgids: jax.Array, lo: jax.Array,
                      hi: jax.Array, onehot: jax.Array):
    return _quant_recheck_fn(xsub, cgids, lo, hi, onehot)


@functools.lru_cache(maxsize=128)
def _sharded_rank_fn(mesh, k: int, score_bound, method,
                     flat: bool = False):
    if mesh is None and flat:
        # single-device fallback: the ceil-split partition makes virtual
        # position (shard * Nloc_max + local) EQUAL the global row id
        # (offsets are Nloc_max multiples; tail/empty-shard padding rows
        # carry score 0 and sit past n, so they never rank and the
        # catalog-size training-id pad lands on them harmlessly) — so
        # one flat rank_topk over the reshaped buffer IS the per-shard
        # top-k + merge, minus S-1 extraction passes the one device
        # would run back to back
        def flat(scores, offs, nloc, tids):
            s, nlm, q = scores.shape
            return kops.rank_topk(scores.reshape(s * nlm, q), tids,
                                  k=min(k, s * nlm),
                                  score_bound=score_bound, method=method,
                                  scores_transposed=True)

        return jax.jit(flat)

    local = functools.partial(kops.shard_local_topk, k=k,
                              score_bound=score_bound, method=method)
    inner = _shard_call(lambda s, o, nl, t: local(s, t, o, nl), mesh, 3, 1)

    def fn(scores, offs, nloc, tids):
        gids, sc, _ = inner(scores, offs, nloc, tids)
        if mesh is not None:
            # replicate the tiny [S, Q, k] candidate lists BEFORE the
            # merge sort: without the constraint GSPMD partitions the
            # sort over the flattened shard axis and runs a distributed
            # sort — orders of magnitude more collective traffic than
            # the one small all-gather these lists actually need
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            rep = NamedSharding(mesh, P())
            gids = jax.lax.with_sharding_constraint(gids, rep)
            sc = jax.lax.with_sharding_constraint(sc, rep)
        return kops.merge_topk(gids, sc, k=k)

    return jax.jit(fn)


def sharded_rank_merge(sindex: ShardedZoneMapIndex, scores: jax.Array,
                       train_ids: jax.Array, *, k: int,
                       score_bound: Optional[int] = None, mesh=None,
                       method: Optional[str] = None):
    """Device-side per-shard top-k (kernels/ops.shard_local_topk: local
    rank_topk + local->global id remap) followed by the cross-shard
    merged global top-k (kernels/ops.merge_topk), as ONE cached jit.
    Honors the pinned tie-break contract end to end — descending score,
    ascending GLOBAL id — so the result is bitwise the single-device
    ranking, and only the merged [Q, k] ever needs to reach the host:
    per-query host traffic stays O(k) regardless of shard count.

    ``score_bound`` is pow2-bucketed before keying the jit cache — a
    LOOSER bound is always valid (it only sizes the threshold search /
    method choice), and bucketing keeps the cache from growing with
    every distinct per-query box count."""
    sb = None if score_bound is None else pow2above(score_bound)
    # the flat single-device shortcut needs virtual position == global
    # id, i.e. the standard ceil-split offsets; anything custom falls
    # back to the general per-shard + merge formulation
    nlm = sindex.n_loc_max
    flat = bool(np.array_equal(
        sindex.offsets[:-1],
        np.minimum(np.arange(sindex.n_shards, dtype=np.int64) * nlm,
                   sindex.n_rows)))
    fn = _sharded_rank_fn(mesh, int(k), sb, method, flat)
    return fn(scores, jnp.asarray(sindex.offsets[:-1], jnp.int32),
              jnp.asarray(sindex.shard_rows, jnp.int32), train_ids)


def sharded_fused_stats(sindex: ShardedZoneMapIndex, max_hit: int,
                        sum_min_hit: int, capacity: int, n_boxes: int,
                        flat: bool = False) -> dict:
    """fused_stats for the sharded path. The gather figures price what
    the devices really read — every shard gathers ``capacity`` blocks
    (``flat`` mode gathers ``capacity`` GLOBALLY — one device, one
    bound) — and ``survivors`` reports the quantity the retry capacity
    must cover (per-shard max, or the global count in flat mode), while
    ``blocks_touched`` sums the genuinely-refined survivor blocks
    (comparable to the host path)."""
    s, d = sindex.n_shards, len(sindex.dims)
    gathered = capacity if flat else s * capacity
    return {
        "blocks_touched": int(sum_min_hit),
        "blocks_gathered": gathered,
        "blocks_total": sindex.total_blocks,
        "rows_touched": int(gathered * sindex.block),
        "bytes_touched": int(gathered * sindex.block * d * 4),
        "bytes_total": sindex.rows_nbytes,
        "prune_fraction": 1.0 - gathered / max(sindex.total_blocks, 1),
        "capacity": capacity,
        "survivors": int(max_hit),
        "overflowed": int(max_hit) > capacity,
        "n_boxes": n_boxes,
        "n_shards": s,
    }


# ----------------------------------------------------------------------
# distributed query (shard_map over the data axis)
# ----------------------------------------------------------------------

def distributed_query(index_rows: jax.Array, zlo: jax.Array, zhi: jax.Array,
                      blo: jax.Array, bhi: jax.Array, mesh,
                      block: int) -> jax.Array:
    """Sharded prune+refine: rows/zones range-partitioned over `data`.

    index_rows: [NB, block, d'] global; zlo/zhi: [NB, d']; boxes are tiny
    and replicated. Returns [NB * block] counts (Morton order). Each shard
    prunes its own zones and refines only its shard's rows — no
    collectives until the caller gathers ids, exactly how the engine runs
    on a pod (queries fan out, id lists gather back)."""
    from jax.sharding import PartitionSpec as P

    def local(rows, lo_z, hi_z, lo_b, hi_b):
        m = kref.zone_prune_ref(lo_z, hi_z, lo_b, hi_b).any(1)     # [nb_local]
        flat = rows.reshape(-1, rows.shape[-1])
        counts = kref.box_scan_ref(flat, lo_b, hi_b)
        keep = jnp.repeat(m, block)
        return jnp.where(keep, counts, 0)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P(), P()),
        out_specs=P("data"),
        check_vma=False)
    return fn(index_rows, zlo, zhi, blo, bhi)


def pruned_local_step(block: int, capacity: int):
    """The production per-shard step of the pruned distributed query:
    zone-prune local zones, gather <= ``capacity`` surviving blocks
    (static shape — the padded-result idiom), refine only those, scatter
    counts back to block positions. Returns
    ``local(rows [nb_loc, block, d'], zlo, zhi, blo, bhi) -> [nb_loc *
    block] int32`` — the function distributed_query_pruned shard_maps AND
    the one launch/search_dryrun.py lowers at paper scale, so the HLO the
    dry-run prices is exactly the step the engine would run."""

    def local(rows, lo_z, hi_z, lo_b, hi_b):
        nb_loc = rows.shape[0]
        m = kref.zone_prune_ref(lo_z, hi_z, lo_b, hi_b).any(1)   # [nb_loc]
        cand, = jnp.nonzero(m, size=capacity, fill_value=0)      # [C]
        valid = jnp.arange(capacity) < m.sum()
        sel = rows[cand]                                         # [C, blk, d]
        counts = kref.box_scan_ref(sel.reshape(-1, sel.shape[-1]),
                                   lo_b, hi_b).reshape(capacity, block)
        counts = counts * valid[:, None]
        out = jnp.zeros((nb_loc, block), jnp.int32)
        out = out.at[cand].max(counts)     # cand may repeat at fill slots
        return out.reshape(-1)

    return local


def distributed_query_pruned(index_rows: jax.Array, zlo: jax.Array,
                             zhi: jax.Array, blo: jax.Array, bhi: jax.Array,
                             mesh, block: int, capacity: int) -> jax.Array:
    """The PERFORMANCE formulation: gather surviving blocks, refine only
    those. ``capacity`` bounds surviving blocks per shard (static shape —
    the padded-result idiom). Bytes touched scale with selectivity instead
    of catalog size: this is the k-d tree win in TPU currency (DESIGN.md
    §2). Overflowing shards fall back to correct-but-slower semantics only
    in the sense that extra matches beyond capacity blocks are dropped —
    callers size capacity from the zone-prune mask (or re-run with 2x).
    """
    from jax.sharding import PartitionSpec as P

    fn = jax.shard_map(
        pruned_local_step(block, capacity), mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P(), P()),
        out_specs=P("data"),
        check_vma=False)
    return fn(index_rows, zlo, zhi, blo, bhi)
