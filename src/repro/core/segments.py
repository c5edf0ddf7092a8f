"""Live catalog ingestion — a segmented LSM-style index (DESIGN.md §12).

The zone-map index froze the catalog at build time: absorbing one new
satellite pass meant a full ``build_index`` rebuild plus a fresh device
upload. This module wraps the existing machinery in an append / delete /
compact lifecycle so the engine can serve a catalog that GROWS:

  append   Morton-orders ONLY the new rows into a sealed delta segment
           (per feature subset). Global ids are append-ordered and
           stable forever: a segment starting at ``offset`` owns global
           rows [offset, offset + n_rows), exactly the shard id contract.
  delete   writes tombstones into a device-resident validity mask —
           geometry is untouched, dead rows simply end with score 0
           (the dense buffer is masked once a query, mask_tombstones) so
           ranked top-k never surfaces them.
  compact  merges every sealed segment into ONE re-sorted segment (one
           global Morton order again) off the serving thread and swaps
           it in atomically. Tombstoned rows stay physically present so
           every segment keeps covering a CONTIGUOUS id range (the
           offset + local-id contract the whole ranking path is built
           on); reclaiming their bytes would need an id-translation
           layer and is deliberately out of scope.

Queries run base + deltas as ONE fused device program by the same move
the sharded fallback used (DESIGN.md §11): every segment's blocks are
concatenated into a single RAGGED virtual block space ([NB_total, block,
d'] — no per-segment NBmax padding, segments are wildly different
sizes), the per-segment row-id grids are offset to global ids in the same
order, and the flat fused query + accumulate + rank_topk pipeline runs
exactly as it does for a monolithic index. Scores land in a [N_total, Q]
buffer whose row index IS the global id, so the accumulate scatters
straight by global id and ranking and training-id exclusion need no
remap at all.

Snapshot / epoch discipline: every mutation builds a NEW immutable
Snapshot and swaps one reference under a lock. A query binds the
snapshot once at entry and keeps it for the whole batch window — an
in-flight query always finishes on the index it started with, however
many appends/compactions land meanwhile. The monotonically increasing
``epoch`` tags jit-shape-sensitive host state (the engine's capacity
hints) so nothing sized for one geometry leaks into the next.

The correctness contract (tests/test_live_catalog.py): at EVERY point of
an append/delete/compact schedule, ranked ids and scores are bitwise
those of a monolithic ``build_index`` engine over the surviving rows
(ids mapped through the live-id list, which is monotone — so even
tie-breaks at the k-th score agree).

Durability (DESIGN.md §15): with ``persist_dir`` set, every effective
mutation is write-ahead-logged (checksummed, fsync policy per ``sync``)
BEFORE the snapshot swap, ``checkpoint()`` commits the sealed segment
set through a two-phase manifest flip, and ``SegmentedCatalog.open()``
recovers crash-consistently — the WAL tail replays through the real
append/delete paths above, so the recovered catalog inherits the same
bitwise contract (tests/test_durability.py pins it at every crash
point). The machinery lives in ``core/persist.py``.
"""
from __future__ import annotations

import copy
import functools
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import persist as persistmod
from repro.core.errors import PersistenceError, RecoveryError
from repro.core.index import ZoneMapIndex, build_index, shard_offsets
from repro.kernels import ops as kops


# ----------------------------------------------------------------------
# segments
# ----------------------------------------------------------------------

@dataclass
class Segment:
    """One sealed, immutable run of catalog rows: global ids
    [offset, offset + n_rows), one ZoneMapIndex per feature subset over
    exactly those rows (Morton order is segment-local). ``shard`` is the
    owning shard in an n_shards composition — bookkeeping the flat
    fallback carries so a mesh backend could place delta tails
    per-device; the flat execution itself is shard-agnostic."""
    offset: int
    n_rows: int
    shard: int
    indexes: List[ZoneMapIndex]        # aligned with the engine's subsets

    def stats(self, live_host: Optional[np.ndarray] = None) -> dict:
        live = (int(live_host[self.offset:self.offset + self.n_rows].sum())
                if live_host is not None else self.n_rows)
        return {"offset": self.offset, "rows": self.n_rows,
                "rows_live": live, "rows_tombstoned": self.n_rows - live,
                "shard": self.shard,
                "blocks": sum(ix.n_blocks for ix in self.indexes),
                "bytes": int(sum(ix.rows.nbytes for ix in self.indexes))}


@dataclass
class SegmentedZoneMapIndex:
    """One feature subset's view of every segment, concatenated into the
    flat virtual block space. Quacks like a ZoneMapIndex where the engine
    needs it to (device_arrays / n_blocks / block / subset_id), and its
    row-id grid (device_gids) names each virtual (block, slot)'s GLOBAL
    id, so one accumulate_scores call folds every segment's counts into
    the [N_total, Q] buffer in global id order. Pure geometry — validity
    (tombstones) lives on the Snapshot, so delete epochs share these
    objects and their cached device mirrors."""
    dims: np.ndarray
    segs: List[ZoneMapIndex]           # per-segment indexes, offset order
    offsets: np.ndarray                # [S + 1] global row offsets
    block: int
    subset_id: int = -1
    _dev: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = field(
        default=None, repr=False, compare=False)
    _seg_blocks_dev: Optional[jax.Array] = field(
        default=None, repr=False, compare=False)
    _gids_virt: Optional[jax.Array] = field(
        default=None, repr=False, compare=False)

    @property
    def n_segments(self) -> int:
        return len(self.segs)

    @property
    def n_rows(self) -> int:
        return int(self.offsets[-1])

    @functools.cached_property
    def seg_blocks(self) -> np.ndarray:
        """[S + 1] block offsets of each segment in the virtual space —
        RAGGED cumulative sums, not S * NBmax rectangles, so a tiny delta
        costs its own few blocks rather than a base-sized stripe."""
        return np.concatenate(
            [[0], np.cumsum([s.n_blocks for s in self.segs])]).astype(np.int64)

    @property
    def n_blocks(self) -> int:
        return int(self.seg_blocks[-1])

    @property
    def rows_nbytes(self) -> int:
        return int(sum(s.rows.nbytes for s in self.segs))

    def device_arrays(self) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """(rows3 [NB_total, block, d'], zlo, zhi [NB_total, d']) — the
        per-segment cached mirrors concatenated ON DEVICE, lazily. Old
        segments' mirrors are cached on their ZoneMapIndex objects and
        shared across epochs, so an append uploads only the new delta;
        the concat itself is a device-to-device copy."""
        if self._dev is None:
            if len(self.segs) == 1:
                self._dev = self.segs[0].device_arrays()
            else:
                parts = [s.device_arrays() for s in self.segs]
                self._dev = tuple(jnp.concatenate([p[i] for p in parts], 0)
                                  for i in range(3))
        return self._dev

    def device_seg_blocks(self) -> jax.Array:
        if self._seg_blocks_dev is None:
            self._seg_blocks_dev = jnp.asarray(self.seg_blocks, jnp.int32)
        return self._seg_blocks_dev

    def device_gids(self) -> jax.Array:
        """[NB_total, block] int32 GLOBAL row id per virtual (block,
        slot), -1 on padding slots: each segment's local permutation grid
        offset by the segment's global row offset, concatenated in the
        virtual block order. Built from the per-segment cached mirrors
        on device (an append re-offsets only the delta). The dense
        accumulate scatters by it and the survivor-sparse tiles are
        labelled with it, so ranking needs no virtual->global remap."""
        if self._gids_virt is None:
            parts = []
            for s, o in zip(self.segs, self.offsets[:-1]):
                g = s.device_gids()
                parts.append(jnp.where(g >= 0, g + jnp.int32(o), -1))
            self._gids_virt = (parts[0] if len(parts) == 1
                               else jnp.concatenate(parts))
        return self._gids_virt

    def device_bytes(self) -> dict:
        """Resident device-mirror bytes by kind: the per-segment cached
        mirrors plus this view's own concatenated copies (counted only
        when they are distinct arrays — a single-segment view shares the
        segment's mirror)."""
        out = {"rows": 0, "zones": 0, "gids": 0, "quantized": 0}
        for s in self.segs:
            for k, v in s.device_bytes().items():
                out[k] += v
        if self._dev is not None and len(self.segs) > 1:
            rows3, zlo, zhi = self._dev
            out["rows"] += int(rows3.nbytes)
            out["zones"] += int(zlo.nbytes) + int(zhi.nbytes)
        if self._gids_virt is not None:
            out["gids"] += int(self._gids_virt.nbytes)
        return out

    def stats(self) -> dict:
        return {"n_segments": self.n_segments, "blocks": self.n_blocks,
                "block_rows": self.block, "rows": self.n_rows,
                "dims": self.dims.tolist(), "bytes": self.rows_nbytes,
                "seg_blocks": self.seg_blocks.tolist()}


# ----------------------------------------------------------------------
# fused query + accumulate over the virtual block space
# ----------------------------------------------------------------------

@functools.lru_cache(maxsize=128)
def _seg_query_acc_fn(capacity: int, use_pallas: bool):
    """jit'd fused query over the concatenated segment blocks + score
    accumulation by global id + per-segment survivor attribution, one
    device program per subset (the segmented sibling of
    _flat_query_acc_fn).
    ``capacity`` bounds the gather GLOBALLY across all segments — one
    budget for the whole virtual space, no per-segment rounding waste."""

    def score_segmented_dense(rows3, zlo, zhi, gids_v, scores, lo, hi, oh,
                              seg_boff):
        counts, cand, n_hit = kops.fused_query(
            rows3, zlo, zhi, lo, hi, oh, capacity=capacity,
            use_pallas=use_pallas)
        # speculate the no-overflow case exactly like the sharded path:
        # an overflowed attempt adds nothing (no live slot), the caller
        # retries the subset at >= n_hit
        n_live = jnp.where(n_hit <= capacity, n_hit, 0)
        acc = kops.accumulate_scores(scores, counts, cand, n_live, gids_v)
        # attribute each REFINED block to its segment: cand partitions
        # into segments by the boundary table, fill slots past the
        # refined count masked out — so the per-segment figures sum to
        # exactly blocks_touched (no double-count across the virtual
        # space, pinned by tests)
        seg_of = jnp.searchsorted(seg_boff, cand, side="right") - 1
        refined = jnp.arange(capacity) < jnp.minimum(n_hit, capacity)
        per_seg = jnp.zeros((seg_boff.shape[0] - 1,), jnp.int32).at[
            seg_of].add(refined.astype(jnp.int32))
        return acc, jnp.concatenate([n_hit[None], per_seg])

    return jax.jit(score_segmented_dense)


@jax.jit
def mask_tombstones(scores: jax.Array, valid: jax.Array) -> jax.Array:
    """The finished [N_total, Q] dense buffer with every tombstoned row's
    scores zeroed (valid: [N_total] int32, 1 live), once a query: an
    elementwise pass, so a dead row can never rank (rank_topk treats
    score <= 0 as invalid). Applying it once to the sum equals masking
    every subset's increment, since a dead row's total is 0 either way."""
    return scores * valid[:, None]


def segmented_query_accumulate(segx: SegmentedZoneMapIndex,
                               scores: jax.Array, blo: jax.Array,
                               bhi: jax.Array, onehot: jax.Array, *,
                               capacity: int, use_pallas: bool = True):
    """One subset's boxes against EVERY segment as one fused device
    program: zone-prune + bounded gather + segmented box-scan over the
    concatenated virtual block space, counts scattered into the global
    [N_total, Q] score buffer by global id (the virtual row-id grid).
    Tombstones are the caller's to mask, once, on the finished buffer
    (mask_tombstones).

    Returns (scores', stvec [1 + S] int32 = (total survivors, refined
    blocks per segment)) — device values; callers batch the sync."""
    rows3, zlo, zhi = segx.device_arrays()
    fn = _seg_query_acc_fn(int(capacity), bool(use_pallas))
    return fn(rows3, zlo, zhi, segx.device_gids(), scores, blo, bhi,
              onehot, segx.device_seg_blocks())


@functools.lru_cache(maxsize=128)
def _seg_sparse_probe_fn(capacity: int, use_pallas: bool):
    """Survivor-sparse probe over the virtual block space (the sparse
    sibling of _seg_query_acc_fn): fused query + tile labelling with the
    tombstone mask applied PER TILE ROW (tile_candidates drops dead rows
    where the dense path masks its finished buffer — same zeros, applied
    at the survivor granularity), plus the per-segment refined-block
    attribution the honest-accounting stats are pinned on.

    Returns (counts [C, block, Q], gids/ok [C, block],
             st [2 + S] int32 = (n_hit, n_match, per-segment refined))."""

    def score_segmented_sparse_probe(rows3, zlo, zhi, gids_v, valid, lo,
                                     hi, oh, seg_boff):
        counts, cand, n_hit = kops.fused_query(
            rows3, zlo, zhi, lo, hi, oh, capacity=capacity,
            use_pallas=use_pallas)
        gids, ok = kops.tile_candidates(counts, cand, gids_v, valid=valid)
        seg_of = jnp.searchsorted(seg_boff, cand, side="right") - 1
        refined = jnp.arange(capacity) < jnp.minimum(n_hit, capacity)
        per_seg = jnp.zeros((seg_boff.shape[0] - 1,), jnp.int32).at[
            seg_of].add(refined.astype(jnp.int32))
        st = jnp.concatenate([n_hit[None],
                              ok.sum().astype(jnp.int32)[None], per_seg])
        return counts, gids, ok, st

    return jax.jit(score_segmented_sparse_probe)


def segmented_sparse_probe(segx: SegmentedZoneMapIndex, blo: jax.Array,
                           bhi: jax.Array, onehot: jax.Array,
                           valid: jax.Array, *, capacity: int,
                           use_pallas: bool = True):
    """Phase A of the segmented survivor-sparse path; the caller batches
    the st sync, then compacts tiles via kernels/ops.survivor_tiles at
    row_capacity = pow2ceil(n_match) — exact, no tile overflow."""
    rows3, zlo, zhi = segx.device_arrays()
    fn = _seg_sparse_probe_fn(int(capacity), bool(use_pallas))
    return fn(rows3, zlo, zhi, segx.device_gids(), valid, blo, bhi,
              onehot, segx.device_seg_blocks())


def segmented_fused_stats(segx: SegmentedZoneMapIndex, n_hit: int,
                          per_seg: np.ndarray, capacity: int,
                          n_boxes: int, live_rows: int) -> dict:
    """fused_stats for the segmented path. The global figures price the
    ONE capacity-sized gather the device performs over the virtual block
    space (never per-segment capacities summed — that would double-count
    the shared budget); ``per_segment_blocks_touched`` partitions the
    genuinely refined blocks by segment and sums to ``blocks_touched``
    exactly. Live/tombstone row counts ride along so serving dashboards
    see how much of the priced byte traffic is dead weight."""
    d = len(segx.dims)
    nb = segx.n_blocks
    per_seg = [int(v) for v in per_seg]
    return {
        "blocks_touched": int(min(n_hit, capacity)),
        "blocks_gathered": capacity,
        "blocks_total": nb,
        "rows_touched": int(capacity * segx.block),
        "bytes_touched": int(capacity * segx.block * d * 4),
        "bytes_total": segx.rows_nbytes,
        "prune_fraction": 1.0 - capacity / max(nb, 1),
        "capacity": capacity,
        "survivors": int(n_hit),
        "overflowed": int(n_hit) > capacity,
        "n_boxes": n_boxes,
        "n_segments": segx.n_segments,
        "per_segment_blocks_touched": per_seg,
        "per_segment_bytes_touched": [v * segx.block * d * 4
                                      for v in per_seg],
        "rows_live": int(live_rows),
        "rows_tombstoned": segx.n_rows - int(live_rows),
    }


# ----------------------------------------------------------------------
# the catalog: snapshots + the append/delete/compact lifecycle
# ----------------------------------------------------------------------

@dataclass
class Snapshot:
    """One immutable epoch of the catalog. Everything a query binds:
    features (for fits), live feature range (box expansion must see the
    SURVIVING rows' spread — the monolithic-rebuild parity contract
    depends on it), per-subset segment views, and the validity mask
    (host bool view; the int32 device mirror uploads lazily on first
    use). Snapshots share structure: a delete reuses every index
    object, an append reuses every sealed segment, and ``x`` /
    ``valid_host`` are length-n views of the catalog's growable buffers
    (appends write PAST n, so older views never change)."""
    epoch: int
    x: np.ndarray
    frange: Tuple[np.ndarray, np.ndarray]
    segments: Tuple[Segment, ...]
    indexes: Tuple[SegmentedZoneMapIndex, ...]
    valid_host: np.ndarray             # [n] bool
    n: int
    live_rows: int
    # geometry GENERATION: bumped only when existing segments are
    # replaced (compaction) — appends/deletes extend or overlay the
    # geometry without invalidating what was learned about it, so
    # capacity hints key on this, not on the mutation epoch
    geom: int = 0
    _valid_dev: Optional[jax.Array] = field(default=None, repr=False)
    # the parent snapshot's ALREADY-BUILT device mask, when this epoch
    # only appended rows to it: valid_device() then extends it with ones
    # on device instead of re-uploading O(catalog) from the host
    _valid_base: Optional[jax.Array] = field(default=None, repr=False)

    def valid_device(self) -> jax.Array:
        """[n] int32 device mask (1 live, 0 tombstoned), built once per
        snapshot on first use: a device-side extension of the parent's
        cached mask after an append (O(delta)), a full upload otherwise
        (delete epochs, or a parent whose mask was never built)."""
        if self._valid_dev is None:
            base = self._valid_base
            if base is not None and base.shape[0] <= self.n:
                self._valid_dev = jnp.concatenate(
                    [base, jnp.ones(self.n - base.shape[0], jnp.int32)])
            else:
                self._valid_dev = jnp.asarray(
                    self.valid_host.astype(np.int32))
        return self._valid_dev


class SegmentedCatalog:
    """The mutable handle: owns the current Snapshot and the mutation
    lifecycle. All mutations serialise on one lock and swap the snapshot
    reference atomically; readers never lock — ``snapshot()`` is a plain
    attribute read, and whatever epoch a query grabbed stays fully
    functional for as long as the query holds it."""

    # extra buffer rows reserved beyond the current catalog size, as a
    # fraction (plus a floor): steady appends write into the spare tail
    # and almost never pay the O(catalog) regrow copy
    _HEADROOM_FRAC = 4      # 1/4 = 25%
    _HEADROOM_MIN = 4096

    def __init__(self, features: np.ndarray, subsets: np.ndarray, *,
                 block: int = 1024, n_shards: int = 1, faults=None,
                 persist_dir=None, sync: str = "batch"):
        x = np.ascontiguousarray(np.asarray(features, np.float32))
        self.subsets = np.asarray(subsets)
        self.block = int(block)
        self.n_shards = max(int(n_shards), 1)
        # duck-typed fault injector (repro.serve.faults.FaultInjector):
        # seams fire BEFORE any state change, so a fired fault leaves the
        # catalog bitwise untouched — core never imports serve
        self.faults = faults
        self._lock = threading.Lock()          # mutation serialisation
        self._compact_lock = threading.Lock()  # one compaction at a time
        self._ckpt_lock = threading.Lock()     # one checkpoint at a time
        self._geom = 0                         # compaction generation
        self._lsn = 0                          # last assigned WAL lsn
        self.recovery = None                   # RecoveryReport after open()
        self.persist = None
        if persist_dir is not None:
            if persistmod.has_state(persist_dir):
                raise PersistenceError(
                    f"{persist_dir} already holds a durable catalog — "
                    "use SegmentedCatalog.open() to recover it instead "
                    "of silently overwriting")
            self.persist = persistmod.Persistence(persist_dir, sync=sync,
                                                  faults=faults)
        # growable buffers: snapshots hold length-n VIEWS of these;
        # appends write past every live view's end, deletes replace the
        # validity buffer wholesale — existing views never change
        n = x.shape[0]
        cap = n + max(n // self._HEADROOM_FRAC, self._HEADROOM_MIN)
        self._xbuf = np.empty((cap, x.shape[1]), np.float32)
        self._xbuf[:n] = x
        self._vbuf = np.ones(cap, bool)
        # the base: one segment per shard (the ceil-split row partition,
        # so an n_shards composition starts from the sharded layout and
        # every later append lands on a per-shard delta tail)
        offs = shard_offsets(n, self.n_shards)
        segments = []
        for s in range(self.n_shards):
            o0, o1 = int(offs[s]), int(offs[s + 1])
            if o1 > o0:
                segments.append(self._build_segment(x[o0:o1], o0, shard=s))
        self._next_shard = len(segments) % self.n_shards
        frange = (x.min(0), x.max(0))
        self._make_snapshot(0, self._xbuf[:n], frange, tuple(segments),
                            self._vbuf[:n], n)
        # genesis checkpoint: the manifest carries the config recovery
        # needs (subsets, block, shards), so a durable catalog is
        # reopenable from its very first mutation onward
        if self.persist is not None:
            self.checkpoint()

    def _reserve(self, n_rows: int) -> None:
        """Grow the feature/validity buffers to hold ``n_rows`` (called
        under the mutation lock). Old snapshots keep their views of the
        previous buffers untouched."""
        if n_rows <= self._xbuf.shape[0]:
            return
        cur = self._snap.n
        cap = n_rows + max(n_rows // self._HEADROOM_FRAC,
                           self._HEADROOM_MIN)
        xb = np.empty((cap, self._xbuf.shape[1]), np.float32)
        xb[:cur] = self._xbuf[:cur]
        vb = np.ones(cap, bool)
        vb[:cur] = self._vbuf[:cur]
        self._xbuf, self._vbuf = xb, vb

    # ------------------------------------------------------------------
    def _build_segment(self, xseg: np.ndarray, offset: int,
                       shard: int) -> Segment:
        idxs = [build_index(xseg, dims, block=self.block, subset_id=k)
                for k, dims in enumerate(self.subsets)]
        return Segment(int(offset), int(xseg.shape[0]), int(shard), idxs)

    def _make_snapshot(self, epoch, x, frange, segments, valid_host,
                       live_rows, prev_indexes=None,
                       valid_base=None) -> Snapshot:
        """``prev_indexes`` is reused when geometry is unchanged (delete
        epochs) so cached device mirrors survive the swap;
        ``valid_base`` is the parent's cached device mask when this
        epoch only appends (valid_device extends it on device)."""
        if prev_indexes is None:
            n = x.shape[0]
            offsets = np.asarray([s.offset for s in segments] + [n],
                                 np.int64)
            prev_indexes = tuple(
                SegmentedZoneMapIndex(
                    dims=np.asarray(dims),
                    segs=[s.indexes[k] for s in segments],
                    offsets=offsets, block=self.block, subset_id=k)
                for k, dims in enumerate(self.subsets))
        snap = Snapshot(epoch, x, frange, tuple(segments), prev_indexes,
                        valid_host, x.shape[0], int(live_rows),
                        geom=self._geom, _valid_base=valid_base)
        self._snap = snap
        return snap

    # ------------------------------------------------------------------
    def _fault(self, site: str) -> None:
        if self.faults is not None:
            self.faults.check(site)

    def snapshot(self) -> Snapshot:
        return self._snap

    @property
    def epoch(self) -> int:
        return self._snap.epoch

    def durability_snapshot(self) -> Optional[dict]:
        """Consistent durability ledger: (lsn, WAL/checkpoint stats)
        captured under the mutation lock — appends/deletes assign the
        LSN and write the WAL record inside that lock, so reading both
        fields locked can never observe a torn pair (an lsn from after
        a mutation with stats from before it). None for non-durable
        catalogs. The serving layer publishes this in ``summary()``."""
        with self._lock:
            if self.persist is None:
                return None
            # deep copy under the lock: stats values are scalars today,
            # but the snapshot contract is "caller owns it" — a future
            # nested value must not hand out a live reference
            return {"sync": self.persist.sync, "lsn": self._lsn,
                    **copy.deepcopy(self.persist.stats)}

    def append(self, features: np.ndarray) -> np.ndarray:
        """Seal ``features`` into a new delta segment; returns the new
        rows' global ids (the tail range — append order IS id order).
        Cost is O(new rows): the segment index build plus a write into
        the growable buffers' spare tail — no existing segment is
        touched, re-sorted, re-copied or re-uploaded."""
        xnew = np.ascontiguousarray(np.asarray(features, np.float32))
        if xnew.ndim != 2:
            raise ValueError("append expects [m, D] features")
        self._fault("append")   # before any state change: atomic failure
        with self._lock:
            snap = self._snap
            if xnew.shape[1] != snap.x.shape[1]:
                raise ValueError(
                    f"append width {xnew.shape[1]} != catalog width "
                    f"{snap.x.shape[1]}")
            m = xnew.shape[0]
            if m == 0:
                return np.empty(0, np.int64)
            n = snap.n
            # durability first: the WAL record reaches disk (per the
            # sync policy) BEFORE any in-memory state changes, and a
            # failed/rolled-back log leaves the catalog bitwise
            # untouched. One record == one epoch bump, the invariant
            # recovery's epoch arithmetic rests on — which is why the
            # m == 0 no-op returns above, before consuming an LSN.
            self._lsn += 1
            if self.persist is not None:
                try:
                    self.persist.log_append(self._lsn, xnew)
                except Exception:
                    # the record was rolled back off the disk — release
                    # its LSN too, or the next record leaves a gap that
                    # recovery would (rightly) refuse to replay across
                    self._lsn -= 1
                    raise
                # kill-between-WAL-and-swap crash point: the record is
                # durable, the snapshot swap below never happens —
                # recovery must replay it to the exact post-swap state
                self._fault("wal_commit")
            seg = self._build_segment(xnew, n, shard=self._next_shard)
            self._next_shard = (self._next_shard + 1) % self.n_shards
            self._reserve(n + m)
            self._xbuf[n:n + m] = xnew
            self._vbuf[n:n + m] = True
            # appended rows are live: the live range only widens, so the
            # incremental elementwise min/max stays EXACT (parity with a
            # monolithic rebuild's full-column reduction)
            frange = (np.minimum(snap.frange[0], xnew.min(0)),
                      np.maximum(snap.frange[1], xnew.max(0)))
            self._make_snapshot(snap.epoch + 1, self._xbuf[:n + m], frange,
                                snap.segments + (seg,),
                                self._vbuf[:n + m], snap.live_rows + m,
                                valid_base=snap._valid_dev)
            return np.arange(n, n + m, dtype=np.int64)

    def delete(self, ids) -> int:
        """Tombstone global ids. Returns how many rows went from live to
        dead (re-deletes are idempotent). Geometry and device mirrors are
        untouched — only the validity mask changes, functionally, so
        in-flight snapshots keep their own mask."""
        ids = np.unique(np.asarray(list(ids), np.int64))
        self._fault("delete")   # before any state change: atomic failure
        with self._lock:
            snap = self._snap
            if len(ids) and (ids[0] < 0 or ids[-1] >= snap.n):
                raise ValueError(f"delete ids out of range [0, {snap.n})")
            newly = ids[snap.valid_host[ids]] if len(ids) else ids
            if len(newly) == 0:
                return 0
            # WAL before swap, and log only the EFFECTIVE deletions
            # (``newly``, computed above): replay re-applies exactly the
            # live->dead transitions, so idempotent re-deletes neither
            # consume LSNs nor perturb the record<->epoch invariant
            self._lsn += 1
            if self.persist is not None:
                try:
                    self.persist.log_delete(self._lsn, newly)
                except Exception:
                    self._lsn -= 1      # released with the rollback
                    raise
                self._fault("wal_commit")
            # replace the validity buffer wholesale: older snapshots
            # keep viewing the previous one, untouched
            vb = self._vbuf.copy()
            vb[newly] = False
            self._vbuf = vb
            valid_host = vb[:snap.n]
            live = snap.live_rows - len(newly)
            # a tombstoned row may have carried a column extreme: the
            # live range must then be recomputed over the survivors (fit
            # parity with a monolithic rebuild depends on it) — but only
            # then; the common delete touches no extreme and skips the
            # O(n * d) rescan entirely
            frange = snap.frange
            xd = snap.x[newly]
            if ((xd == snap.frange[0]).any() or
                    (xd == snap.frange[1]).any()):
                lv = snap.x[valid_host]
                if len(lv):
                    frange = (lv.min(0), lv.max(0))
            self._make_snapshot(snap.epoch + 1, snap.x, frange,
                                snap.segments, valid_host,
                                live, prev_indexes=snap.indexes)
            return int(len(newly))

    def compact(self) -> dict:
        """Merge every sealed segment into ONE re-sorted segment (a
        fresh global Morton order per subset) and swap it in atomically.
        The heavy build runs OUTSIDE the mutation lock against a fixed
        snapshot — the serving thread keeps appending/deleting/querying
        meanwhile; at swap time the merged segment replaces exactly the
        segments it covered (ids < its row count) and any delta appended
        during the build survives as the new tail. Tombstones are a
        validity overlay, so deletes that landed mid-build stay masked.
        Only one compaction runs at a time; a concurrent call returns
        ``{"skipped": True}`` immediately."""
        if not self._compact_lock.acquire(blocking=False):
            return {"skipped": True, "reason": "compaction in progress"}
        try:
            t0 = time.perf_counter()
            snap0 = self._snap
            if len(snap0.segments) <= 1:
                return {"skipped": True, "reason": "single segment",
                        "epoch": snap0.epoch}
            n0 = snap0.n
            # fault seam BEFORE the merge build: a fired fault aborts the
            # attempt with the old snapshot still serving and ``_geom``
            # unchanged — the swap below is the only mutation
            self._fault("compact")
            merged = self._build_segment(snap0.x[:n0], 0, shard=0)
            with self._lock:
                cur = self._snap
                tail = tuple(s for s in cur.segments if s.offset >= n0)
                self._geom += 1        # old geometries' hints are void
                snap = self._make_snapshot(
                    cur.epoch + 1, cur.x, cur.frange, (merged,) + tail,
                    cur.valid_host, cur.live_rows,
                    valid_base=cur._valid_dev)
            if self.persist is not None:
                # durable two-phase commit: phase 1 lands the merged +
                # tail segments' column files on disk, phase 2 flips the
                # manifest atomically (persist.commit_manifest). A crash
                # at either phase recovers to the PRE-compaction state
                # from the previous manifest + full WAL tail — query-
                # identical, since results are invariant to segmentation
                # — and phase-1 orphan files are GC'd on reopen.
                self.checkpoint()
            return {"skipped": False, "epoch": snap.epoch,
                    "merged_segments": len(snap0.segments),
                    "merged_rows": n0, "tail_segments": len(tail),
                    "compact_s": time.perf_counter() - t0}
        finally:
            self._compact_lock.release()

    # ------------------------------------------------------------------
    # durability: checkpoint / close / open
    # ------------------------------------------------------------------

    def checkpoint(self) -> dict:
        """Write the current snapshot as a durable checkpoint: every
        sealed segment's column files (phase 1), then the manifest
        naming that exact segment set + epoch + WAL horizon (phase 2,
        the atomic commit point). Runs against an immutable (snapshot,
        lsn) pair captured under the mutation lock, so concurrent
        mutations keep landing in the WAL past the horizon and replay
        on recovery — checkpointing never blocks the serving path."""
        if self.persist is None:
            raise PersistenceError(
                "catalog has no persist_dir — nothing to checkpoint to")
        with self._ckpt_lock:
            t0 = time.perf_counter()
            with self._lock:
                snap = self._snap
                lsn = self._lsn
                next_shard = self._next_shard
            entries = [self.persist.write_segment(
                snap.x[s.offset:s.offset + s.n_rows], s.indexes,
                offset=s.offset, rows=s.n_rows, shard=s.shard,
                block=self.block) for s in snap.segments]
            config = {"d": int(self._xbuf.shape[1]),
                      "block": self.block, "n_shards": self.n_shards,
                      "subsets": np.asarray(self.subsets).tolist()}
            mid = self.persist.commit_manifest(
                epoch=snap.epoch, geom=snap.geom, lsn=lsn,
                next_shard=next_shard, n_rows=snap.n,
                live_rows=snap.live_rows, frange=snap.frange,
                valid=snap.valid_host, config=config, segments=entries)
            self.persist.stats["checkpoints"] += 1
            return {"manifest_id": mid, "epoch": snap.epoch, "lsn": lsn,
                    "segments": len(entries),
                    "checkpoint_s": time.perf_counter() - t0}

    def close(self) -> None:
        """Flush + fsync the WAL and release the handle. A ``sync=
        "none"`` catalog becomes fully durable at close; the other modes
        already were."""
        if self.persist is not None:
            self.persist.close()

    @classmethod
    def open(cls, path, *, faults=None, sync: str = "batch",
             strict: bool = True):
        """Crash-consistent recovery: load the newest valid manifest,
        rebuild its segments bitwise from the column files, replay the
        WAL tail through the REAL append/delete code paths, then re-arm
        durability for live operation. The result is pinned by tests to
        be bitwise query-identical to the never-crashed catalog at
        every crash point.

        Damage handling: torn/corrupt bytes are quarantined and the
        salvaged prefix recovered; with ``strict=True`` (default) the
        damage raises ``RecoveryError`` CARRYING the salvaged catalog
        (``err.catalog``) and report (``err.report``), so a server can
        keep serving the salvage while surfacing ``degraded`` health —
        corruption is never folded silently into results."""
        # hold the single-writer lock across recover -> replay -> re-arm
        # (DirLock is reentrant in-process, so the nested acquisitions
        # by recover() and the fresh Persistence share this hold)
        with persistmod.DirLock(path):
            state = persistmod.recover(path, faults=faults)
            cat = cls._from_recovered(path, state, sync=sync,
                                      faults=faults)
        if strict and not state.report.clean:
            raise RecoveryError(
                f"recovered {path} with damage: "
                + "; ".join(state.report.errors),
                report=state.report, catalog=cat)
        return cat

    @classmethod
    def _from_recovered(cls, path, state, *, sync: str, faults=None):
        self = cls.__new__(cls)
        cfg = state.config
        self.subsets = np.asarray(cfg["subsets"])
        self.block = int(cfg["block"])
        self.n_shards = int(cfg["n_shards"])
        # replay runs with durability and fault seams DISABLED: the tail
        # ops are already durable, and replay must be deterministic
        self.faults = None
        self.persist = None
        self.recovery = state.report
        self._lock = threading.Lock()
        self._compact_lock = threading.Lock()
        self._ckpt_lock = threading.Lock()
        self._geom = int(state.geom)
        self._lsn = int(state.lsn)
        self._next_shard = int(state.next_shard)
        n, d = int(state.n_rows), int(cfg["d"])
        cap = n + max(n // self._HEADROOM_FRAC, self._HEADROOM_MIN)
        self._xbuf = np.empty((cap, d), np.float32)
        self._vbuf = np.ones(cap, bool)
        self._vbuf[:n] = state.valid
        segments = []
        for entry, feats, cols in sorted(state.segments,
                                         key=lambda t: t[0]["offset"]):
            o, m = int(entry["offset"]), int(entry["rows"])
            self._xbuf[o:o + m] = feats
            idxs = []
            for k, (perm, zlo, zhi) in enumerate(cols):
                dims = np.asarray(self.subsets[k])
                # rows reconstruct bitwise from features + permutation:
                # exactly build_index's sub[perm] with +inf padding
                sub = np.ascontiguousarray(feats[:, dims])
                rows = np.full((perm.shape[0], dims.shape[0]), np.inf,
                               np.float32)
                real = perm >= 0
                rows[real] = sub[perm[real]]
                idxs.append(ZoneMapIndex(
                    dims, np.asarray(perm), rows,
                    np.asarray(zlo, np.float32),
                    np.asarray(zhi, np.float32), self.block, m, k))
            segments.append(Segment(o, m, int(entry["shard"]), idxs))
        frange = (np.asarray(state.frange_lo, np.float32),
                  np.asarray(state.frange_hi, np.float32))
        self._make_snapshot(int(state.epoch), self._xbuf[:n], frange,
                            tuple(segments), self._vbuf[:n],
                            int(state.live_rows))
        # replay the WAL tail through the real mutation paths: each
        # record bumps the epoch and evolves frange/validity exactly as
        # the original mutation did (bitwise — append features are the
        # exact f32 bytes, build_index is deterministic)
        for rec in state.tail:
            if rec.op == "append":
                self.append(rec.features)
            else:
                self.delete(rec.ids)
        # re-arm durability + fault seams for live operation; new WAL
        # records continue at the next LSN in a fresh file
        self.persist = persistmod.Persistence(path, sync=sync,
                                              faults=faults)
        self.faults = faults
        return self

    # ------------------------------------------------------------------
    def stats(self) -> dict:
        snap = self._snap
        return {
            "epoch": snap.epoch,
            "geom": snap.geom,
            "n_segments": len(snap.segments),
            "rows": snap.n,
            "rows_live": snap.live_rows,
            "rows_tombstoned": snap.n - snap.live_rows,
            "n_shards": self.n_shards,
            "shard_tail_segments": [
                sum(1 for s in snap.segments if s.shard == sh)
                for sh in range(self.n_shards)],
            "segments": [s.stats(snap.valid_host) for s in snap.segments],
            "durable": (None if self.persist is None else
                        {"sync": self.persist.sync, "lsn": self._lsn,
                         **self.persist.stats}),
        }
