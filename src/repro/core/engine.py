"""The RapidEarth search engine — paper §4 "Search application".

Orchestrates the full query-processing path:

  offline:  features [N, D]  ->  K feature subsets  ->  K zone-map indexes
  online :  (pos ids, neg ids, model)  ->  fit classifier  ->
            boxes  ->  range queries on the pre-built indexes  ->
            ranked object ids + query statistics

Five search models (paper §4.1), all returning the same QueryResult:

  dbranch   index-aware decision branches            (index path)
  dbens     25-model decision-branch ensemble        (index path)
  dtree     CART decision tree                       (full scan)
  rforest   25-tree random forest                    (full scan)
  knn       top-k nearest neighbours on one subset   (index rows, MXU)

The scan-based models reuse the same box_scan kernel over the FULL
feature matrix — the latency difference against the index path is purely
which bytes each model touches, which is the paper's headline claim.

The index path is device-resident END TO END (DESIGN.md §9): per-subset
fused queries accumulate into one persistent [N, Q] device score buffer
in original row order (kernels/ops.accumulate_scores), overflow checks
are deferred to ONE batched host sync per round, and with ``max_results``
set the ranking itself runs on device (kernels/ops.rank_topk) so only
[Q, k] ids/scores ever cross to the host — per-query device->host
traffic is O(k), independent of catalog size.
"""
from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import knn as knn_mod
from repro.core.boxes import BoxSet, concat_box_arrays
from repro.core.dbranch import (DBENS_SUBSET_CANDIDATES, dbens_draws,
                                fit_dbens, fit_dbranch_best_subset,
                                fit_select_jax, split_tables)
from repro.core.capacity import HintTable
from repro.core.capacity import hybrid_bucket as _cap_hybrid
from repro.core.capacity import pow2ceil as _cap_pow2ceil
from repro.core.capacity import quantum_bucket as _cap_quantum
from repro.core.errors import RecoveryError, check_deadline
from repro.core.persist import has_state as persist_has_state
from repro.core.index import (ShardedZoneMapIndex, ZoneMapIndex,
                              build_index, build_sharded_index, full_scan,
                              fused_stats, pad_boxes, query_index,
                              query_index_sharded, quantized_compact,
                              quantized_probe, quantized_recheck,
                              sharded_fused_stats, sharded_query_accumulate,
                              sharded_rank_merge, sharded_sparse_probe,
                              sharded_survivor_tiles, sparse_probe)
from repro.core.segments import (SegmentedCatalog, SegmentedZoneMapIndex,
                                 mask_tombstones, segmented_fused_stats,
                                 segmented_query_accumulate,
                                 segmented_sparse_probe)
from repro.core.subsets import make_subsets
from repro.core.trees import fit_decision_tree, fit_random_forest
from repro.kernels import ops as kops
from repro.obs import profile as obs_profile
from repro.obs import trace as obs_trace

MODELS = ("dbranch", "dbens", "dtree", "rforest", "knn")

log = logging.getLogger(__name__)

# sentinel: "no per-call override — use the engine default"
_UNSET = object()

# the obs layer stays stdlib-only: this JAX-importing layer installs its
# profiler hooks — spans also enter a TraceAnnotation of their name, and
# an executable built or loaded on a traced thread becomes a ``compile``
# span of the request that paid for it (DESIGN.md §17)
obs_trace.install_hooks(jax.profiler.TraceAnnotation,
                        jax.monitoring.register_event_duration_secs_listener)


@dataclass
class QueryResult:
    """What the web application receives back (paper §4, step 4)."""

    model: str
    ids: np.ndarray               # result row ids, ranked by confidence
    scores: np.ndarray            # per-id confidence (box-membership votes)
    train_time_s: float
    query_time_s: float
    stats: Dict = field(default_factory=dict)

    @property
    def n_found(self) -> int:
        return int(len(self.ids))

    def summary(self) -> str:
        return (f"{self.model}: {self.n_found} objects in "
                f"{1e3 * (self.train_time_s + self.query_time_s):.1f} ms "
                f"(fit {1e3 * self.train_time_s:.1f} + "
                f"query {1e3 * self.query_time_s:.1f})")


@dataclass
class _EngineView:
    """What one query (or batch window) binds at entry: the index set,
    feature matrix, feature range and validity mask of ONE consistent
    catalog state. Static engines hand out a trivial view over their own
    fields; live engines hand out the SegmentedCatalog snapshot of the
    moment — so an append/delete/compact landing mid-window changes
    nothing for queries already in flight (DESIGN.md §12)."""
    indexes: Sequence
    n: int
    x: np.ndarray
    frange: Tuple[np.ndarray, np.ndarray]
    epoch: int = 0
    geom: int = 0        # compaction generation — capacity-hint key tag
    live: bool = False
    valid: Optional[jax.Array] = None          # [n] int32 device mask
    valid_host: Optional[np.ndarray] = None    # [n] bool host mirror
    live_rows: int = -1                        # -1 -> all n rows live


@dataclass
class SparseScores:
    """Survivor-sparse device score form (DESIGN.md §13): the scores of
    one query batch as row tiles keyed on GLOBAL id — ``keys`` [R] int32
    (TILE_INVALID padding), ``vals`` [R, Q] int32 per-query vote counts
    (zero padding). R is bounded by the survivor-row count across
    subsets, never by N; a global id may appear in several tiles (one
    per subset that matched it) and the consumers sum duplicates —
    int32 addition is exactly associative, so any merge order is
    bitwise-equal to the dense [N, Q] accumulation."""
    keys: jax.Array               # [R] int32 global ids
    vals: jax.Array               # [R, Q] int32 counts
    n: int                        # catalog rows (dense-equivalent height)

    @property
    def nbytes(self) -> int:
        return int(self.keys.nbytes) + int(self.vals.nbytes)


class _DeviceRound:
    """One launch round of a score loop (``SearchEngine._device_round``):
    the launch loop runs inside it as a context — the ``dispatch`` span
    and the ``jit_dispatch`` profile site — and ``sync`` then reads every
    subset's stat vector in ONE batched device->host transfer — the
    ``sync`` span and the ``device_sync`` site. Both are children of the
    round's ``device_round`` span."""

    __slots__ = ("_engine", "_span", "_t0")

    def __init__(self, engine: "SearchEngine"):
        self._engine = engine

    def __enter__(self):
        self._span = obs_trace.span("dispatch")
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            obs_profile.record("jit_dispatch",
                               time.perf_counter() - self._t0)
        return self._span.__exit__(exc_type, exc, tb)

    def sync(self, parts, agg: Dict) -> np.ndarray:
        self._engine._fault("device_sync")
        with obs_trace.span("sync"), obs_profile.profile("device_sync"):
            out = np.asarray(jnp.stack(parts))
        agg["n_host_syncs"] += 1
        agg["host_bytes_transferred"] += int(out.nbytes)
        return out


class SearchEngine:
    """End-to-end engine over an in-memory feature shard.

    On a pod, each host holds one engine over its feature shard and
    queries fan out (boxes are tiny); see serve/engine.py for the batched
    multi-query front end and core/index.distributed_query for the
    shard_map'd device path.

    ``max_results`` (constructor default, overridable per query) caps how
    many ranked ids a query returns AND switches ranking to the device
    top-k stage: only [Q, k] crosses device->host. With max_results=None
    the full ranked result list is returned via the host ranking oracle.

    ``n_shards > 1`` (DESIGN.md §11) partitions the catalog row-space
    into contiguous shards, each with its own per-subset zone-map index;
    queries run the same fused prune/gather/refine per shard, scores
    accumulate into per-shard device buffers, and ranking becomes a
    device-side per-shard top-k + cross-shard merge that preserves the
    pinned tie-break contract — results are bitwise-identical for every
    shard count, and ranked host traffic stays O(k) regardless of it.
    ``shard_mesh``: None auto-builds a "shards" mesh when the backend
    has >= n_shards devices (jax.shard_map), False forces the
    single-device vmap fallback, or pass a Mesh.

    ``live=True`` (DESIGN.md §12) makes the catalog MUTABLE: ``append``
    seals new rows into delta segments (global ids append-ordered and
    stable forever), ``delete`` tombstones rows in a device-resident
    validity mask, and ``compact`` merges segments back into one Morton
    order off the serving thread. Queries bind an immutable snapshot at
    entry, run base + deltas as one fused program over the concatenated
    virtual block space, and return bitwise the ids/scores a monolithic
    rebuild over the surviving rows would. With ``n_shards > 1`` live
    engines run the flat fallback with per-shard delta tails.
    """

    def __init__(
        self,
        features: Optional[np.ndarray] = None,
        *,
        n_subsets: int = 32,
        subset_dim: int = 6,
        block: int = 1024,
        seed: int = 0,
        use_pallas: bool = True,
        use_fused: bool = True,
        capacity_frac: float = 0.25,
        max_results: Optional[int] = None,
        use_jax_fit: bool = True,
        fit_max_nodes: int = 64,
        n_shards: int = 1,
        shard_mesh=None,
        live: bool = False,
        score_mode: str = "sparse",
        mirror: str = "f32",
        faults=None,
        data_dir=None,
        wal_sync: str = "batch",
    ):
        # durability (DESIGN.md §15): ``data_dir`` makes a live catalog
        # persistent. When the directory already holds a durable catalog
        # DISK WINS — the engine recovers it (newest manifest + WAL
        # replay) and adopts its geometry/config wholesale, ignoring any
        # ``features`` passed (the recovered state is the truth a crash
        # must not lose); a fresh directory starts from ``features`` and
        # writes the genesis checkpoint. Damage found during recovery
        # lands in ``self.recovery`` (a persist.RecoveryReport) with the
        # salvaged state serving — the serve layer surfaces it as
        # degraded health instead of silently wrong results. A data_dir
        # has exactly ONE writing process: both paths below take the
        # directory's fcntl lock (persist.DirLock), so a second process
        # racing this has_state check fails with PersistenceError
        # instead of interleaving WAL/manifest writes.
        self.recovery = None
        recovered: Optional[SegmentedCatalog] = None
        if data_dir is not None:
            if not live:
                raise ValueError("data_dir requires live=True")
            if persist_has_state(data_dir):
                try:
                    recovered = SegmentedCatalog.open(
                        data_dir, faults=faults, sync=wal_sync)
                except RecoveryError as e:
                    if e.catalog is None:
                        raise
                    recovered = e.catalog
                self.recovery = recovered.recovery
        if recovered is not None:
            self.x = np.asarray(recovered.snapshot().x)
        elif features is None:
            raise ValueError(
                "features is required unless data_dir holds a "
                "recoverable durable catalog")
        else:
            self.x = np.ascontiguousarray(np.asarray(features, np.float32))
        self.n, self.d = self.x.shape
        self.use_pallas = use_pallas
        # device-resident batched trainer (DESIGN.md §10): every dbranch/
        # dbens fit of a batch window runs as ONE jit'd program and the
        # winning boxes stay on device; the numpy trainers remain the
        # correctness oracle, selectable with use_jax_fit=False
        self.use_jax_fit = use_jax_fit
        # worklist FLOOR per trained model (batched fits scale it up to
        # 2x the padded positive count so realistic trees never hit the
        # cap); also bounds the compacted box-count pad, so it is a
        # jit-cache key the same way capacities are
        self.fit_max_nodes = fit_max_nodes
        # fused path: prune->gather->refine as one jit'd device program
        # over the cached device mirror of each index (core/index.py)
        self.use_fused = use_fused
        self.capacity_frac = capacity_frac
        self.max_results = max_results
        # survivor counts observed by _device_scores, keyed by
        # (generation, subset, box-count bucket); sizes the next
        # like-shaped fused gather so steady-state queries never
        # overflow-retry (policy lives in core/capacity.HintTable)
        self._cap_hints = HintTable()
        # fault-injection seams (DESIGN.md §14): an object with a
        # check(site) method, or None. The engine never imports the
        # injector — serve/faults.py stays above core in the layering.
        self.faults = faults
        self.n_shards = max(int(n_shards), 1)
        self.live = bool(live)
        # score accumulation form (DESIGN.md §13): "sparse" keeps device
        # scores as survivor tiles keyed on global id — bounded by the
        # survivor count, never N*Q — while "dense" materialises the full
        # [N, Q] buffer (the original formulation, kept as the oracle).
        # int32 vote addition is exactly associative, so both forms are
        # bitwise-identical end to end.
        self.score_mode = str(score_mode)
        if self.score_mode not in ("sparse", "dense"):
            raise ValueError(f"score_mode must be 'sparse' or 'dense', "
                             f"got {score_mode!r}")
        # "quantized" probes int8/f16 device mirrors with a conservative
        # code-space prune, then re-checks the candidate set against the
        # exact f32 rows — results stay bitwise, device bytes drop ~4x
        self.mirror = str(mirror)
        if self.mirror not in ("f32", "quantized"):
            raise ValueError(f"mirror must be 'f32' or 'quantized', "
                             f"got {mirror!r}")
        if self.mirror == "quantized" and (
                self.score_mode != "sparse" or not self.use_fused
                or self.live or self.n_shards > 1):
            raise ValueError(
                "mirror='quantized' requires score_mode='sparse', "
                "use_fused=True and a static non-sharded catalog")
        # high-water mark of device score-buffer bytes across queries
        self._score_bytes_peak = 0
        self._catalog: Optional[SegmentedCatalog] = None
        self._sync_lock = threading.Lock()
        t0 = time.perf_counter()
        if recovered is not None:
            # disk wins: geometry/config come from the manifest, not the
            # constructor args — the recovered catalog must be bitwise
            # the one that crashed, whatever this process was passed
            self.subsets = np.asarray(recovered.subsets)
            self.n_shards = recovered.n_shards
        else:
            self.subsets = make_subsets(self.d, n_subsets, subset_dim,
                                        seed=seed)
        if self.live:
            # live catalogs (DESIGN.md §12) run the segmented flat path
            # on every backend; with n_shards > 1 the base is the usual
            # ceil-split partition and deltas land on per-shard tails —
            # composition at the flat-fallback level (a mesh leg for
            # live segments would need per-shard delta mirrors and is
            # future work, so shard_mesh is ignored here)
            self.shard_mesh = None
            self._shard_flat = self.n_shards > 1
            if recovered is not None:
                self._catalog = recovered
            else:
                self._catalog = SegmentedCatalog(self.x, self.subsets,
                                                 block=block,
                                                 n_shards=self.n_shards,
                                                 faults=faults,
                                                 persist_dir=data_dir,
                                                 sync=wal_sync)
            self.indexes = list(self._catalog.snapshot().indexes)
        elif self.n_shards > 1:
            self.shard_mesh = self._resolve_shard_mesh(shard_mesh)
            # no mesh -> the single device runs the whole shard set as
            # ONE flat fused index: capacities are then GLOBAL bounds,
            # sized exactly like the single-device path's
            self._shard_flat = self.shard_mesh is None
            self.indexes = [
                build_sharded_index(self.x, dims, self.n_shards,
                                    block=block, subset_id=k)
                for k, dims in enumerate(self.subsets)
            ]
        else:
            self.shard_mesh = None
            self._shard_flat = False
            self.indexes = [
                build_index(self.x, dims, block=block, subset_id=k)
                for k, dims in enumerate(self.subsets)
            ]
        self.build_time_s = time.perf_counter() - t0
        # global per-dim feature range (used by box expansion); a
        # recovered catalog's physical rows include tombstones, so its
        # LIVE range comes from the snapshot, never a full-column rescan
        if recovered is not None:
            self.frange = recovered.snapshot().frange
        else:
            self.frange = (self.x.min(0), self.x.max(0))

    # ------------------------------------------------------------------
    def _resolve_shard_mesh(self, mesh):
        """None -> auto: a 1-d "shards" mesh over the first n_shards
        devices when the backend has enough, else the single-device vmap
        fallback. False forces the fallback; a Mesh is used as given.
        Both modes run the SAME per-shard program — the mesh only decides
        where it executes, never what it returns."""
        if mesh is False:
            return None
        if mesh is not None:
            return mesh
        devs = jax.devices()
        if len(devs) >= self.n_shards:
            from jax.sharding import Mesh
            return Mesh(np.asarray(devs[:self.n_shards]), ("shards",))
        return None

    @staticmethod
    def _index_nbytes(ix) -> int:
        return (ix.rows_nbytes
                if isinstance(ix, (ShardedZoneMapIndex,
                                   SegmentedZoneMapIndex))
                else int(ix.rows.nbytes))

    def _view(self) -> _EngineView:
        """Bind the catalog state one query (or batch window) runs
        against. Live engines read the current snapshot ONCE here; every
        downstream stage takes the view, never self.indexes/self.n."""
        if self._catalog is None:
            return _EngineView(self.indexes, self.n, self.x, self.frange)
        s = self._catalog.snapshot()
        return _EngineView(s.indexes, s.n, s.x, s.frange, epoch=s.epoch,
                           geom=s.geom, live=True, valid=s.valid_device(),
                           valid_host=s.valid_host, live_rows=s.live_rows)

    # ------------------------------------------------------------------
    # robustness seams (DESIGN.md §14)
    # ------------------------------------------------------------------
    def _fault(self, site: str) -> None:
        """Fault-injection checkpoint: no-op unless an injector was
        threaded in at construction."""
        if self.faults is not None:
            self.faults.check(site)

    def _device_round(self, deadline_s) -> "_DeviceRound":
        """Open one device launch round of a score loop: closes the
        previous ``device_round`` span and opens the next on every
        ambient trace (no-op untraced), then the fused-query fault seam
        and the between-rounds deadline check — a request whose budget
        is gone stops HERE instead of burning another round of device
        time (rounds are the natural cancellation points; in-flight
        device programs are not interruptible). The returned round
        times its launch loop as a context and does its one sync."""
        obs_trace.round_mark()
        self._fault("fused_query")
        check_deadline(deadline_s, "device query round")
        return _DeviceRound(self)

    def invalidate_capacity_hints(self) -> int:
        """Drop every capacity hint (cold-start sizing resumes). The
        serving layer calls this after a FAILED compaction — the
        conservative reset for hints observed around a crash; normal
        compactions prune by generation instead. Returns the number of
        entries dropped."""
        return self._cap_hints.invalidate()

    # ------------------------------------------------------------------
    # live-catalog lifecycle (DESIGN.md §12)
    # ------------------------------------------------------------------
    def _require_live(self) -> SegmentedCatalog:
        if self._catalog is None:
            raise RuntimeError(
                "this engine is static — construct SearchEngine(..., "
                "live=True) to append/delete/compact")
        return self._catalog

    def _sync_live(self) -> None:
        """Refresh the engine-level mirrors of the catalog head (what
        index_stats and external callers read); queries never use these
        directly — they bind a snapshot via _view(). Serialised against
        itself (a background compaction finishes on its own thread) and
        safe against concurrent hint inserts from a serving thread: the
        prune works on an atomic copy and swaps the dict wholesale."""
        with self._sync_lock:
            s = self._catalog.snapshot()
            self.indexes = list(s.indexes)
            self.x = s.x
            self.n = s.n
            self.frange = s.frange
            # capacity hints are tagged with the compaction GENERATION
            # (not the mutation epoch — hints survive appends/deletes,
            # whose geometry they still describe); pruning dead
            # generations keeps a long-running server's table bounded
            self._cap_hints.prune_generation(s.geom)

    def append(self, features: np.ndarray) -> np.ndarray:
        """Seal new rows into a delta segment; returns their global ids
        (append-ordered, stable forever). O(new rows) index build — no
        rebuild, no re-upload of existing segments."""
        ids = self._require_live().append(features)
        self._sync_live()
        return ids

    def delete(self, ids) -> int:
        """Tombstone global ids in the device-resident validity mask;
        returns how many rows went live -> dead. Ranked queries never
        surface tombstoned rows again (masked at score accumulation)."""
        nd = self._require_live().delete(ids)
        self._sync_live()
        return nd

    def compact(self, background: bool = False):
        """Merge all sealed segments into one re-sorted segment and swap
        it in atomically under a new epoch. ``background=True`` runs the
        (heavy, O(catalog)) merge off the calling thread and returns the
        started Thread; serving continues on the old snapshot until the
        swap. Synchronous calls return the compaction stats dict."""
        cat = self._require_live()
        if background:
            t = threading.Thread(target=self._compact_now, daemon=True)
            t.start()
            return t
        return self._compact_now()

    def _compact_now(self) -> Dict:
        with obs_profile.profile("compact"):
            st = self._catalog.compact()
        self._sync_live()
        return st

    def checkpoint(self) -> Dict:
        """Durably checkpoint the live catalog (segment column files +
        manifest, DESIGN.md §15); requires ``data_dir``. Truncates the
        WAL replay a future recovery must perform."""
        return self._require_live().checkpoint()

    def close(self) -> None:
        """Flush + fsync the durable catalog's WAL and release its file
        handle; a no-op for static or non-durable engines."""
        if self._catalog is not None:
            self._catalog.close()

    def index_stats(self) -> Dict:
        st = {
            "rows": self.n,
            "dims": self.d,
            "n_subsets": len(self.indexes),
            "subset_dim": int(self.subsets.shape[1]),
            "n_shards": self.n_shards,
            "build_time_s": self.build_time_s,
            "index_bytes": int(sum(self._index_nbytes(ix)
                                   for ix in self.indexes)),
            "feature_bytes": int(self.x.nbytes),
            "score_mode": self.score_mode,
            "mirror": self.mirror,
        }
        # ACTUAL device-mirror residency, by kind and per index — only
        # mirrors that have been uploaded count (lazy caches report 0
        # until first use), so this is what the accelerator really holds
        dev: Dict[str, int] = {}
        per_index = []
        for ix in self.indexes:
            db = ix.device_bytes()
            per_index.append({"subset_id": int(ix.subset_id),
                              **{k: int(v) for k, v in db.items()},
                              "total": int(sum(db.values()))})
            for k, v in db.items():
                dev[k] = dev.get(k, 0) + int(v)
        st["device_bytes"] = {**dev, "total": int(sum(dev.values()))}
        st["device_bytes_per_index"] = per_index
        st["score_buffer_bytes_peak"] = int(self._score_bytes_peak)
        if self._catalog is not None:
            st["live"] = True
            st.update(self._catalog.stats())
        return st

    # ------------------------------------------------------------------
    def query(
        self,
        pos_ids: Sequence[int],
        neg_ids: Sequence[int],
        model: str = "dbranch",
        *,
        k_neighbors: int = 1000,
        max_depth: int = 12,
        n_models: int = 25,
        seed: int = 0,
        include_training: bool = False,
        max_results=_UNSET,
        deadline_s: Optional[float] = None,
    ) -> QueryResult:
        """One user query: label sets in, ranked ids out.

        ``max_results=k`` truncates the ranked list to its top k entries
        and, on the fused index path, runs the ranking on device so the
        host receives O(k) bytes instead of the full score vector.

        ``deadline_s`` is an absolute ``time.monotonic()`` deadline
        (DESIGN.md §14): checked before the fit and between per-subset
        device rounds, raising a typed ``DeadlineExceeded`` instead of
        finishing work nobody is waiting for."""
        # snapshot + label-row gather is real pre-fit wall: billed as its
        # own span so the root's children account for >=90% of the wall
        with obs_trace.span("prepare"):
            if model not in MODELS:
                raise ValueError(
                    f"unknown model {model!r}; choose from {MODELS}")
            check_deadline(deadline_s, "fit")
            mr = self.max_results if max_results is _UNSET else max_results
            view = self._view()
            pos_ids = np.asarray(list(pos_ids), np.int64)
            neg_ids = np.asarray(list(neg_ids), np.int64)
            xp, xn = view.x[pos_ids], view.x[neg_ids]

        t0 = time.perf_counter()
        with obs_trace.span("fit"):
            if model in ("dbranch", "dbens"):
                if self.use_jax_fit and self.use_fused:
                    # device fit, device boxes: only the [2, G] winner
                    # meta crosses to the host (DESIGN.md §10)
                    lo_c, hi_c, entries = self._fit_boxes_batched(
                        [(model, xp, xn, n_models, seed)],
                        max_depth=max_depth, return_device=True,
                        frange=view.frange)
                    if isinstance(entries[0], Exception):
                        raise entries[0]
                    boxes = ("device", lo_c, hi_c, entries[0])
                else:
                    # the non-fused engine is the all-oracle
                    # configuration: host inference AND the numpy trainer
                    # (DESIGN.md §10)
                    boxes = self._fit_boxes(
                        model, xp, xn, max_depth=max_depth,
                        n_models=n_models, seed=seed, use_jax=False,
                        frange=view.frange)
            elif model == "dtree":
                xtr = np.concatenate([xp, xn])
                ytr = np.concatenate([np.ones(len(xp)), np.zeros(len(xn))])
                tree = fit_decision_tree(xtr, ytr, max_depth=max_depth)
            elif model == "rforest":
                xtr = np.concatenate([xp, xn])
                ytr = np.concatenate([np.ones(len(xp)), np.zeros(len(xn))])
                forest = fit_random_forest(xtr, ytr, n_trees=n_models,
                                           max_depth=max_depth, seed=seed)
        t_fit = time.perf_counter() - t0

        # ---- inference + ranking --------------------------------------
        t0 = time.perf_counter()
        check_deadline(deadline_s, "inference")
        stats: Dict = {}
        if model in ("dbranch", "dbens"):
            ids, scores, stats = self._run_index_path(
                boxes, pos_ids, neg_ids, include_training, mr, view,
                deadline_s=deadline_s)
            stats["path"] = "index"
            stats["fit_path"] = ("jax" if self.use_jax_fit and self.use_fused
                                 else "numpy")
        elif model == "knn":
            n_live = view.live_rows if view.live else view.n
            k = min(k_neighbors, n_live)
            ids_k, dists = knn_mod.knn_subset(view.indexes[0], xp, k=k,
                                              live=view.valid_host)
            counts = knn_mod.knn_vote(ids_k, view.n)
            stats = {"path": "index",
                     "bytes_touched": self._index_nbytes(view.indexes[0])}
            t_fit = 0.0
            ids, scores = self._rank(counts, pos_ids, neg_ids,
                                     include_training)
        else:
            lo, hi = (tree.lo, tree.hi) if model == "dtree" else forest.boxes()
            if len(lo) == 0:
                counts = np.zeros(view.n, np.int32)
            else:
                counts = np.asarray(full_scan(view.x, lo, hi,
                                              use_pallas=self.use_pallas))
            if view.valid_host is not None:
                # scan models see every physical row; tombstoned rows
                # must not surface from this path either
                counts = np.where(view.valid_host, counts, 0)
            stats = {"path": "scan", "bytes_touched": int(view.x.nbytes),
                     "n_boxes": int(len(lo))}
            ids, scores = self._rank(counts, pos_ids, neg_ids,
                                     include_training)
        if mr is not None:      # device-ranked results are already <= mr
            ids, scores = ids[:mr], scores[:mr]
        t_query = time.perf_counter() - t0

        return QueryResult(model, ids, scores, t_fit, t_query, stats)

    # ------------------------------------------------------------------
    def _fit_boxes(self, model: str, xp: np.ndarray, xn: np.ndarray, *,
                   max_depth: int, n_models: int, seed: int,
                   use_jax: Optional[bool] = None,
                   frange=None) -> List[BoxSet]:
        """Fit an index-path model; both query() and query_batch() go
        through here so batched and sequential answers train identically.
        The engine's feature range is plumbed into both trainers so box
        expansion sees the catalog's spread, not the training sample's
        (live engines pass their snapshot's LIVE-row range via
        ``frange`` — the monolithic-rebuild parity contract needs it).
        ``use_jax`` overrides the engine default (benchmarks pin the
        numpy oracle as their legacy baseline)."""
        use_jax = self.use_jax_fit if use_jax is None else use_jax
        frange = self.frange if frange is None else frange
        if use_jax:
            return self._fit_boxes_batched(
                [(model, xp, xn, n_models, seed)], max_depth=max_depth,
                frange=frange)[0]
        if model == "dbranch":
            return [fit_dbranch_best_subset(xp, xn, self.subsets,
                                            max_depth=max_depth,
                                            feature_range=frange)]
        return fit_dbens(xp, xn, self.subsets, n_models=n_models,
                         max_depth=max_depth, seed=seed,
                         feature_range=frange)

    def _fit_boxes_batched(self, specs: Sequence[Tuple], *,
                           max_depth: int, return_device: bool = False,
                           frange=None):
        """Device-resident batched fit (DESIGN.md §10): train EVERY model
        of a batch window — (candidate subsets x ensemble members x
        requests) lanes — on device (one capped jit'd round over all
        lanes, one survivor round for deep trees), select each model's
        winning subset on device, and keep the winning boxes there.

        specs: [(model, xp, xn, n_models, seed)] with xp/xn the raw
        full-width label features. With ``return_device`` the raw
        compacted winner arrays come back — (lo [G, S, d'], hi, entries
        per spec of (winner row, subset id, box count)) — and flow
        straight into _make_jobs_flat/fused_query with no host round
        trip; otherwise box-set lists aligned with specs are built (the
        oracle-compatible API used by tests and benchmarks). Shapes are
        bucketed (P, Ng, lanes, groups) so varied label-set sizes share
        compilations; the only device->host result traffic is one [2, G]
        (winner lane, box count) sync plus the round-1 survivor flags."""
        frange = self.frange if frange is None else frange
        n_sub = len(self.subsets)
        dsub = int(self.subsets.shape[1])
        groups = []     # (spec_idx, cand ids, lane start, boot pos, boot neg)
        lane0 = p_max = n_max = 0
        for si, (model, xp, xn, n_models, seed) in enumerate(specs):
            xp = np.asarray(xp, np.float32)
            xn = np.asarray(xn, np.float32)
            p_max, n_max = max(p_max, len(xp)), max(n_max, len(xn))
            if model == "dbranch":
                draws = [(None, None, np.arange(n_sub))]
            else:       # dbens: same bootstrap draws as the numpy trainer
                draws = dbens_draws(len(xp), len(xn), n_sub, n_models,
                                    DBENS_SUBSET_CANDIDATES, seed)
            for ip, ineg, cand in draws:
                bp = xp if ip is None else xp[ip]
                bn = xn if ineg is None else (xn[ineg] if len(xn) else xn)
                groups.append((si, np.asarray(cand), lane0, bp, bn))
                lane0 += len(cand)
        t = lane0
        g_real = len(groups)
        # bucketing: pow2 for small values, then coarse linear quanta —
        # padding waste stays <= ~25% while the jit-key count stays tiny
        p_pad = self._fit_bucket(p_max, 32)
        n_pad = self._fit_bucket(n_max, 32)
        t_pad = self._fit_bucket(t, 128)
        # dummy lanes park in an extra dummy group so real winners are
        # never contested by padding
        g_pad = self._pow2ceil(g_real + (1 if t_pad > t else 0))
        # packed inputs (samples, validity, ranges): one upload each —
        # eager dispatches/uploads cost ~1ms apiece on small CPU hosts
        x_b = np.zeros((t_pad, p_pad + n_pad, dsub), np.float32)
        m_b = np.zeros((t_pad, p_pad + n_pad), bool)
        fr_b = np.zeros((t_pad, 2, dsub), np.float32)
        gid_b = np.full(t_pad, g_real, np.int32)
        for g, (si, cand, l0, bp, bn) in enumerate(groups):
            c = len(cand)
            dims = self.subsets[cand]                          # [C, d']
            x_b[l0:l0 + c, :len(bp)] = bp[:, dims].transpose(1, 0, 2)
            m_b[l0:l0 + c, :len(bp)] = True
            if len(bn):
                x_b[l0:l0 + c, p_pad:p_pad + len(bn)] = \
                    bn[:, dims].transpose(1, 0, 2)
                m_b[l0:l0 + c, p_pad:p_pad + len(bn)] = True
            fr_b[l0:l0 + c, 0] = frange[0][dims]
            fr_b[l0:l0 + c, 1] = frange[1][dims]
            gid_b[l0:l0 + c] = g
        # split-search tables on the host: numpy sorts the whole lane
        # stack in one shot, the device program never sorts
        si_b, re_b = split_tables(x_b)
        # the worklist cap: trees that outgrow it emit early, diverging
        # from the (uncapped) numpy oracle — scale headroom with the
        # label-set size so realistic trees always fit (a tree has at
        # most one leaf per positive)
        max_nodes = max(self.fit_max_nodes, 2 * p_pad)
        lo_c, hi_c, meta_dev = fit_select_jax(
            jnp.asarray(x_b), jnp.asarray(m_b), jnp.asarray(fr_b),
            jnp.asarray(gid_b), jnp.asarray(
                np.concatenate([si_b, re_b], axis=2)),
            p_cnt=p_pad, n_groups=g_pad, max_nodes=max_nodes,
            max_depth=max_depth)
        meta = np.asarray(meta_dev)                    # the ONE result sync
        # decode winners PER SPEC: a request whose label set produced no
        # boxes fails alone — its exception rides in its slot and the
        # rest of the window keeps its finished device fit
        entries: List = [[] for _ in specs]
        for g, (si, cand, start, _, _) in enumerate(groups):
            if isinstance(entries[si], Exception):
                continue
            wl, nb = int(meta[0, g]), int(meta[1, g])
            if wl >= t or nb <= 0:
                entries[si] = RuntimeError("no subset produced boxes")
                continue
            sid = int(cand[wl - start])
            entries[si].append((g, sid, nb))
        if return_device:
            return lo_c, hi_c, entries
        out = []
        for ent in entries:
            if isinstance(ent, Exception):
                raise ent
            out.append([BoxSet(lo_c[g, :nb], hi_c[g, :nb],
                               self.subsets[sid], sid)
                        for g, sid, nb in ent])
        return out

    def _make_jobs_flat(self, parts, nq: int):
        """The _make_jobs counterpart for device-resident fit output.

        parts: [(lo_c, hi_c, g, sid, cnt, q)] — the [G, S, d'] compacted
        winner arrays from _fit_boxes_batched(return_device=True), a
        winner row g, its subset, real box count, and owning query.
        Builds identical jobs with ONE device gather per (subset, fit
        array) instead of per-model slices: eager dispatches cost ~1ms
        each on small CPU hosts, so per-group slicing would dwarf the
        fit itself at dbens scale (DESIGN.md §10)."""
        by_subset: Dict[int, List] = {}
        for part in parts:
            by_subset.setdefault(part[3], []).append(part)
        jobs = []
        totals = np.zeros(nq, np.int64)
        for sid, group in by_subset.items():
            by_arr: Dict[int, Tuple] = {}
            for lo_c, hi_c, g, _, cnt, q in group:
                by_arr.setdefault(id(lo_c), (lo_c, hi_c, []))[2].append(
                    (g, cnt, q))
            los, his, owners = [], [], []
            for lo_c, hi_c, ents in by_arr.values():
                s, d = lo_c.shape[1], lo_c.shape[2]
                idx = np.concatenate(
                    [np.arange(cnt, dtype=np.int32) + g * s
                     for g, cnt, _ in ents])
                los.append(jnp.take(lo_c.reshape(-1, d), jnp.asarray(idx),
                                    axis=0))
                his.append(jnp.take(hi_c.reshape(-1, d), jnp.asarray(idx),
                                    axis=0))
                owners += [np.full(cnt, q, np.int32) for _, cnt, q in ents]
            lo = los[0] if len(los) == 1 else jnp.concatenate(los)
            hi = his[0] if len(his) == 1 else jnp.concatenate(his)
            owner = np.concatenate(owners)
            jobs.append((sid, BoxSet(lo, hi, self.subsets[sid], sid),
                         owner))
            totals += np.bincount(owner, minlength=nq)
        return jobs, (int(totals.max()) if jobs else 0)

    # capacity/shape bucketing is shared policy (core/capacity.py) — the
    # engine methods survive as thin delegates because they are part of
    # the class surface tests and subclasses poke at
    @staticmethod
    def _pow2ceil(v: int) -> int:
        return _cap_pow2ceil(v)

    @staticmethod
    def _fit_bucket(v: int, quantum: int) -> int:
        """Shape bucket for the batched trainer: pow2 below ``quantum``
        (few keys for tiny sizes), then quantum multiples (a 128-lane
        dbens window pads to 640 lanes, not 1024)."""
        v = max(int(v), 1)
        if v <= quantum:
            return _cap_pow2ceil(v)
        return _cap_quantum(v, quantum)

    def _cap_key(self, sid: int, n_boxes: int, geom: int = 0):
        """Hints are keyed by (geometry generation, subset, pow2-bucketed
        box count): survivor counts scale with the merged boxset's
        surface, so a single query (few boxes) and a batch window's union
        (many boxes) must not poison each other's capacity sizing — and
        the GENERATION tag means a live catalog's hints die with the
        geometry they were observed on (a pre-compaction survivor count
        says nothing about the re-sorted block space and must never be
        consulted again), while surviving appends and deletes, which only
        extend or overlay the geometry the hint describes."""
        return (int(geom), sid, self._pow2ceil(max(int(n_boxes), 1)))

    def _mesh_sharded(self) -> bool:
        return self.n_shards > 1 and not self._shard_flat

    def _cap_blocks(self, index) -> int:
        """The block count a capacity is bounded by: the single index's
        blocks, the PER-SHARD block bound on a mesh, the whole virtual
        block space in flat fallback mode — and a segmented index
        reports its concatenated virtual space directly."""
        if isinstance(index, ShardedZoneMapIndex):
            return (index.nb_max if self._mesh_sharded()
                    else index.n_shards * index.nb_max)
        return index.n_blocks

    def _cap_bucket(self, v: int, n_blocks: int) -> int:
        """Capacity shape bucket. Single-device (and flat-fallback)
        capacities pow2-round: few jit keys, and 2x headroom is cheap
        against ONE big gather. Mesh capacities apply PER SHARD — every
        shard gathers the bucket — so pow2 rounding the per-shard max
        would multiply the whole engine's refine bytes by up to 2x per
        shard; multiples of 8 keep the waste bounded at 7 blocks/shard
        while the key count stays ~n_blocks/8 (per-shard block counts
        are small)."""
        v = max(int(v), 1)
        b = _cap_quantum(v, 8) if self._mesh_sharded() else _cap_pow2ceil(v)
        return min(b, n_blocks)

    def _initial_capacity(self, index, n_boxes: Optional[int] = None,
                          geom: int = 0) -> int:
        """Gather capacity for a subset's fused call: the last observed
        survivor count for a like-sized boxset when one is known (the
        deferred-sync rounds report it for free — DESIGN.md §6 says to
        size capacity just above the typical survivor count, and now the
        engine does it itself), otherwise the capacity_frac cold-start
        policy. Results stay exact either way: an under-sized guess is
        caught by the batched overflow check and retried. Mesh-sharded
        hints track the PER-SHARD max and carry 25% headroom (the
        single-path pow2 rounding supplies headroom implicitly; the
        tighter per-shard bucket must add its own or every drifting
        query retries)."""
        nbk = self._cap_blocks(index)
        if n_boxes is not None:
            hint = self._cap_hints.get(self._cap_key(index.subset_id,
                                                     n_boxes, geom))
            if hint is not None:
                if self._mesh_sharded():
                    hint += -(-hint // 4)
                return self._cap_bucket(hint, nbk)
        cap = max(1, int(nbk * self.capacity_frac))
        return self._cap_bucket(cap, nbk)

    @staticmethod
    def _new_agg() -> Dict:
        return {"blocks_touched": 0, "blocks_gathered": 0, "blocks_total": 0,
                "bytes_touched": 0, "n_boxes": 0, "n_range_queries": 0,
                "host_bytes_transferred": 0, "n_host_syncs": 0,
                "retried_subsets": 0, "accumulate_rows": 0}

    @staticmethod
    def _accumulate_agg(agg: Dict, st: Dict, n_boxes: int) -> None:
        agg["blocks_touched"] += st["blocks_touched"]
        # host path has no bounded gather: it reads exactly the survivors
        agg["blocks_gathered"] += st.get("blocks_gathered",
                                         st["blocks_touched"])
        agg["blocks_total"] += st["blocks_total"]
        agg["bytes_touched"] += st["bytes_touched"]
        agg["n_boxes"] += n_boxes
        agg["n_range_queries"] += n_boxes

    @staticmethod
    def _finalize_agg(agg: Dict, view: _EngineView) -> Dict:
        # priced against the catalog the query actually BOUND: a live
        # engine's head may have grown by the time the stats finalize
        agg["scan_bytes_equiv"] = int(view.x.nbytes)
        agg["bytes_saved_frac"] = 1.0 - agg["bytes_touched"] / max(
            view.x.nbytes, 1)
        return agg

    # ------------------------------------------------------------------
    # device-resident scoring (the online hot path, DESIGN.md §9)
    # ------------------------------------------------------------------
    def _make_jobs(self, pairs: Sequence[Tuple[BoxSet, int]], nq: int):
        """Group (BoxSet, owner-query) pairs per subset.

        Returns ([(sid, merged BoxSet, owner [B] int32)] — one fused
        device call each — and the max per-query total box count, the
        score upper bound the device ranking needs for its id-composed
        keys)."""
        by_subset: Dict[int, List[Tuple[BoxSet, int]]] = {}
        for bs, q in pairs:
            by_subset.setdefault(bs.subset_id, []).append((bs, q))
        jobs = []
        totals = np.zeros(nq, np.int64)
        for sid, group in by_subset.items():
            # device-resident boxes (jax arrays, from the batched
            # trainer) merge on device; the owner map is host metadata
            lo = concat_box_arrays([bs.lo for bs, _ in group])
            hi = concat_box_arrays([bs.hi for bs, _ in group])
            owner = np.concatenate([np.full(bs.n_boxes, q, np.int32)
                                    for bs, q in group])
            jobs.append((sid, BoxSet(lo, hi, group[0][0].dims, sid), owner))
            totals += np.bincount(owner, minlength=nq)
        return jobs, (int(totals.max()) if jobs else 0)

    def _device_scores(self, jobs, nq: int, view: _EngineView,
                       deadline_s=None):
        """Mode dispatch for the score accumulation, under a trace
        round scope: each ``_device_round`` inside becomes one
        ``device_round`` span on every ambient trace (including
        overflow-retry rounds — the retries are visible per attempt).
        The scope is a shared no-op when nothing is attached."""
        with obs_trace.round_scope() as scope:
            scores, agg = self._device_scores_impl(jobs, nq, view,
                                                   deadline_s=deadline_s)
            # rows scattered over n rows a subset: the dense accumulate's
            # work against one pass over the whole buffer per subset
            agg["accumulate_share"] = agg["accumulate_rows"] / max(
                view.n * len(jobs), 1)
            # the window's own counters, on the last round: the slow-
            # query log says why a request was slow
            scope.set(n_host_syncs=agg["n_host_syncs"],
                      retried_subsets=agg["retried_subsets"],
                      blocks_touched=agg["blocks_touched"],
                      accumulate_rows=agg["accumulate_rows"],
                      accumulate_share=agg["accumulate_share"])
            return scores, agg

    def _device_scores_impl(self, jobs, nq: int, view: _EngineView,
                            deadline_s=None):
        """Answer every subset's boxes and accumulate all counts into ONE
        persistent [n, nq] device score buffer in ORIGINAL row order
        (row-major so each row's [Q] update is contiguous).

        Per round: launch every pending subset's fused query (async
        dispatch, no blocking), then ONE batched device->host sync reads
        all survivor counts together. Subsets whose survivors exceeded
        capacity are re-queued with capacity >= the observed count and are
        the ONLY work the next round re-runs; everything else scatter-adds
        its C * block gathered rows into the score buffer by row id, on
        device (kops.accumulate_scores; ``accumulate_rows`` counts them).
        The common case is exactly one sync of a few int32s per query batch —
        the per-subset blocking int(n_hit) round-trips of the old path
        are gone.

        score_mode="sparse" (the default, DESIGN.md §13) replaces the
        persistent dense buffer with survivor tiles: same rounds, same
        sync cadence, same retries — the accumulation form is the only
        difference, and it is bitwise-equivalent."""
        if self.score_mode == "sparse":
            if self.mirror == "quantized":
                return self._device_scores_quantized(
                    jobs, nq, view, deadline_s=deadline_s)
            return self._device_scores_sparse(jobs, nq, view,
                                              deadline_s=deadline_s)
        if view.live:
            return self._device_scores_segmented(jobs, nq, view,
                                                 deadline_s=deadline_s)
        if self.n_shards > 1:
            return self._device_scores_sharded(jobs, nq, view,
                                               deadline_s=deadline_s)
        scores = jnp.zeros((view.n, nq), jnp.int32)
        agg = self._new_agg()
        pending = [(sid, merged, owner,
                    self._initial_capacity(view.indexes[sid],
                                           merged.n_boxes))
                   for sid, merged, owner in jobs]
        while pending:
            with self._device_round(deadline_s) as rnd:
                launched = []
                for sid, merged, owner, cap in pending:
                    index = view.indexes[sid]
                    rows3, zlo, zhi = index.device_arrays()
                    lo, hi, owner_p = pad_boxes(merged.lo, merged.hi,
                                                owner)
                    onehot = jnp.asarray(
                        (owner_p[:, None] == np.arange(nq)[None]
                         ).astype(np.float32))
                    counts, cand, n_hit = kops.fused_query(
                        rows3, zlo, zhi, jnp.asarray(lo), jnp.asarray(hi),
                        onehot, capacity=cap, use_pallas=self.use_pallas)
                    launched.append((sid, merged, owner, cap, counts, cand,
                                     n_hit))
            # ONE batched sync covers the whole round's overflow checks
            n_hits = rnd.sync([l[6] for l in launched], agg)
            pending = []
            for (sid, merged, owner, cap, counts, cand, n_hit), nh in zip(
                    launched, n_hits):
                index = view.indexes[sid]
                nh = int(nh)
                # size the NEXT like-shaped query right: rise to a new
                # peak instantly, decay old peaks slowly so one light
                # query can't make the next heavy one overflow-retry
                key = self._cap_key(sid, merged.n_boxes)
                self._cap_hints.observe(key, nh)
                if nh > cap:
                    # the failed attempt still gathered (and priced) cap
                    # blocks of device traffic; count it so bytes_touched
                    # reflects every gather the device really performed
                    agg["blocks_gathered"] += cap
                    agg["bytes_touched"] += int(
                        cap * index.block * index.rows.shape[1] * 4)
                    pending.append((sid, merged, owner,
                                    min(self._pow2ceil(nh), index.n_blocks)))
                    continue
                scores = kops.accumulate_scores(scores, counts, cand,
                                                n_hit, index.device_gids())
                agg["accumulate_rows"] += cap * index.block
                self._accumulate_agg(
                    agg, fused_stats(index, nh, cap, merged.n_boxes),
                    merged.n_boxes)
            agg["retried_subsets"] += len(pending)
        self._note_dense_buffer(agg, scores, nq, view)
        return scores, self._finalize_agg(agg, view)

    def _device_scores_sharded(self, jobs, nq: int, view: _EngineView,
                               deadline_s=None):
        """_device_scores over the sharded indexes (DESIGN.md §11): the
        persistent score buffer is [S, Nloc_max, nq] — one shard-local
        buffer per shard, stacked — and each subset runs ONE device
        program (vmap on one device, shard_map across the mesh) that
        fuses the per-shard query AND the conditional accumulation, so
        a subset costs one dispatch instead of two.

        The deferred-sync contract survives sharding with FLAT host
        traffic: per subset the per-shard survivor counts are reduced ON
        DEVICE to three ints (max, sum of refined, sum) before the one
        batched round sync, so the sync is [J, 3] int32 regardless of
        shard count. Overflow is per subset against the PER-SHARD
        capacity (every shard gathers the same static bound); the fused
        program discards an overflowed subset's accumulation on device
        and the retry re-runs it with capacity >= the observed max."""
        sidx0 = self.indexes[0]
        scores = jnp.zeros((self.n_shards, sidx0.n_loc_max, nq), jnp.int32)
        agg = self._new_agg()
        agg["n_shards"] = self.n_shards
        pending = [(sid, merged, owner,
                    self._initial_capacity(self.indexes[sid],
                                           merged.n_boxes))
                   for sid, merged, owner in jobs]
        while pending:
            with self._device_round(deadline_s) as rnd:
                launched = []
                for sid, merged, owner, cap in pending:
                    sindex = self.indexes[sid]
                    lo, hi, owner_p = pad_boxes(merged.lo, merged.hi,
                                                owner)
                    onehot = jnp.asarray(
                        (owner_p[:, None] == np.arange(nq)[None]
                         ).astype(np.float32))
                    scores, st3 = sharded_query_accumulate(
                        sindex, scores, jnp.asarray(lo), jnp.asarray(hi),
                        onehot, capacity=cap, mesh=self.shard_mesh,
                        use_pallas=self.use_pallas)
                    launched.append((sid, merged, owner, cap, st3))
            # ONE batched sync, [3] ints per subset — flat in shard count
            hit_stats = rnd.sync([l[4] for l in launched], agg)
            pending = []
            for (sid, merged, owner, cap, _), st in zip(launched,
                                                        hit_stats):
                sindex = self.indexes[sid]
                mx, sum_min = int(st[0]), int(st[1])
                key = self._cap_key(sid, merged.n_boxes)
                self._cap_hints.observe(key, mx)
                if mx > cap:
                    # the discarded attempt still gathered (and priced)
                    # cap blocks per shard (or globally, flat mode) of
                    # device traffic
                    gathered = cap if self._shard_flat \
                        else self.n_shards * cap
                    agg["blocks_gathered"] += gathered
                    agg["bytes_touched"] += int(
                        gathered * sindex.block * len(sindex.dims) * 4)
                    pending.append((sid, merged, owner, self._cap_bucket(
                        mx, self._cap_blocks(sindex))))
                    continue
                agg["accumulate_rows"] += (
                    cap if self._shard_flat else self.n_shards * cap
                ) * sindex.block
                self._accumulate_agg(
                    agg, sharded_fused_stats(sindex, mx, sum_min, cap,
                                             merged.n_boxes,
                                             flat=self._shard_flat),
                    merged.n_boxes)
            agg["retried_subsets"] += len(pending)
        self._note_dense_buffer(agg, scores, nq, view)
        return scores, self._finalize_agg(agg, view)

    def _device_scores_segmented(self, jobs, nq: int, view: _EngineView,
                                 deadline_s=None):
        """_device_scores over a live catalog's segmented indexes
        (DESIGN.md §12): the score buffer is [N_total, nq] with row index
        == global id (the concatenated virtual space needs no remap), one
        fused program per subset covers base + every delta, tombstoned
        rows are masked to 0 once, on the finished buffer, and the batched
        deferred sync carries [1 + S] ints per subset — the survivor
        total for the overflow check plus the per-segment refined-block
        attribution the honest stats report."""
        scores = jnp.zeros((view.n, nq), jnp.int32)
        agg = self._new_agg()
        n_segs = view.indexes[0].n_segments
        agg["n_segments"] = n_segs
        agg["rows_live"] = view.live_rows
        agg["rows_tombstoned"] = view.n - view.live_rows
        per_seg_agg = np.zeros(n_segs, np.int64)
        pending = [(sid, merged, owner,
                    self._initial_capacity(view.indexes[sid],
                                           merged.n_boxes,
                                           geom=view.geom))
                   for sid, merged, owner in jobs]
        while pending:
            with self._device_round(deadline_s) as rnd:
                launched = []
                for sid, merged, owner, cap in pending:
                    segx = view.indexes[sid]
                    lo, hi, owner_p = pad_boxes(merged.lo, merged.hi,
                                                owner)
                    onehot = jnp.asarray(
                        (owner_p[:, None] == np.arange(nq)[None]
                         ).astype(np.float32))
                    scores, stvec = segmented_query_accumulate(
                        segx, scores, jnp.asarray(lo), jnp.asarray(hi),
                        onehot, capacity=cap, use_pallas=self.use_pallas)
                    launched.append((sid, merged, owner, cap, stvec))
            # ONE batched sync: [J, 1 + S] int32 for the whole round
            stvecs = rnd.sync([l[4] for l in launched], agg)
            pending = []
            for (sid, merged, owner, cap, _), st in zip(launched, stvecs):
                segx = view.indexes[sid]
                nh = int(st[0])
                key = self._cap_key(sid, merged.n_boxes, view.geom)
                self._cap_hints.observe(key, nh)
                if nh > cap:
                    # the discarded attempt still gathered (and priced)
                    # cap blocks of the virtual space
                    agg["blocks_gathered"] += cap
                    agg["bytes_touched"] += int(
                        cap * segx.block * len(segx.dims) * 4)
                    pending.append((sid, merged, owner,
                                    min(self._pow2ceil(nh), segx.n_blocks)))
                    continue
                agg["accumulate_rows"] += cap * segx.block
                st_d = segmented_fused_stats(segx, nh, st[1:], cap,
                                             merged.n_boxes,
                                             view.live_rows)
                per_seg_agg += np.asarray(
                    st_d["per_segment_blocks_touched"], np.int64)
                self._accumulate_agg(agg, st_d, merged.n_boxes)
            agg["retried_subsets"] += len(pending)
        agg["per_segment_blocks_touched"] = per_seg_agg.tolist()
        scores = mask_tombstones(scores, view.valid)
        self._note_dense_buffer(agg, scores, nq, view)
        return scores, self._finalize_agg(agg, view)

    def _note_dense_buffer(self, agg: Dict, scores, nq: int,
                           view: _EngineView) -> None:
        """Dense-path memory accounting, symmetric with the sparse form:
        the peak device score footprint IS the full persistent buffer."""
        agg["score_buffer_bytes_peak"] = int(scores.nbytes)
        agg["score_rows"] = int(scores.nbytes) // (4 * max(nq, 1))
        agg["dense_score_bytes_equiv"] = int(view.n) * nq * 4
        self._score_bytes_peak = max(self._score_bytes_peak,
                                     int(scores.nbytes))

    def _device_scores_sparse(self, jobs, nq: int, view: _EngineView,
                              deadline_s=None):
        """The survivor-sparse accumulation (tentpole, DESIGN.md §13).

        Identical round structure to the dense methods — same probes and
        capacities, same ONE batched stat sync per round, same hint
        updates, same overflow pricing and requeue buckets — so every
        pinned sync/retry contract holds unchanged. The difference is
        Phase B: instead of scatter-adding into an [N, Q] buffer, each
        round's non-overflowed subsets compact their surviving rows
        into one packed, EXACTLY-sized tile (the stat sync that cleared
        the overflow check also reported the match counts, so tiles can
        never overflow and never add a retry round). The zone prune is
        conservative — every row with a nonzero count lives in a
        surviving block — and int32 vote addition is associative, so
        the tile merge is bitwise-equal to the dense accumulation."""
        agg = self._new_agg()
        live = view.live
        sharded = (not live) and self.n_shards > 1
        mesh_mode = sharded and not self._shard_flat
        per_seg_agg = None
        if live:
            n_segs = view.indexes[0].n_segments
            agg["n_segments"] = n_segs
            agg["rows_live"] = view.live_rows
            agg["rows_tombstoned"] = view.n - view.live_rows
            per_seg_agg = np.zeros(n_segs, np.int64)
        if sharded:
            agg["n_shards"] = self.n_shards
        geom = view.geom if live else 0
        tile_parts, tile_bytes, score_rows = [], 0, 0
        # every per-row, per-query count is bounded by its round's merged
        # box count, so when the whole batch stays below 2**15 the tile
        # values fit int16 exactly — half the value bytes, upcast to
        # int32 before any summation (sparse_topk / host export)
        val_dt = (jnp.int16
                  if max(m.n_boxes for _, m, _ in jobs) < 2 ** 15
                  else jnp.int32)
        val_sz = np.dtype(val_dt).itemsize
        transient = 0
        pending = [(sid, merged, owner,
                    self._initial_capacity(view.indexes[sid],
                                           merged.n_boxes, geom=geom))
                   for sid, merged, owner in jobs]
        while pending:
            round_parts, round_rcaps = [], []
            with self._device_round(deadline_s) as rnd:
                launched = []
                for sid, merged, owner, cap in pending:
                    index = view.indexes[sid]
                    lo, hi, owner_p = pad_boxes(merged.lo, merged.hi,
                                                owner)
                    onehot = jnp.asarray(
                        (owner_p[:, None] == np.arange(nq)[None]
                         ).astype(np.float32))
                    lo_d, hi_d = jnp.asarray(lo), jnp.asarray(hi)
                    if live:
                        probe = segmented_sparse_probe(
                            index, lo_d, hi_d, onehot, view.valid,
                            capacity=cap, use_pallas=self.use_pallas)
                    elif sharded:
                        probe = sharded_sparse_probe(
                            index, lo_d, hi_d, onehot, capacity=cap,
                            mesh=self.shard_mesh,
                            use_pallas=self.use_pallas)
                    else:
                        probe = sparse_probe(index, lo_d, hi_d, onehot,
                                             capacity=cap,
                                             use_pallas=self.use_pallas)
                    launched.append((sid, merged, owner, cap) + probe)
            # ONE batched sync: a FIXED-width int vector per subset —
            # flat in shard count, exactly the dense cadence
            stvecs = rnd.sync([l[7] for l in launched], agg)
            pending = []
            for (sid, merged, owner, cap, counts, gids, ok, _), st in zip(
                    launched, stvecs):
                index = view.indexes[sid]
                nh = int(st[0])
                key = self._cap_key(sid, merged.n_boxes, geom)
                self._cap_hints.observe(key, nh)
                if nh > cap:
                    # the failed attempt still gathered (and priced) cap
                    # blocks — per shard on a mesh, globally otherwise
                    if sharded:
                        gathered = cap if self._shard_flat \
                            else self.n_shards * cap
                        retry = self._cap_bucket(nh,
                                                 self._cap_blocks(index))
                    else:
                        gathered = cap
                        retry = min(self._pow2ceil(nh), index.n_blocks)
                    agg["blocks_gathered"] += gathered
                    agg["bytes_touched"] += int(
                        gathered * index.block * len(index.dims) * 4)
                    pending.append((sid, merged, owner, retry))
                    continue
                if live:
                    st_d = segmented_fused_stats(index, nh, st[2:], cap,
                                                 merged.n_boxes,
                                                 view.live_rows)
                    per_seg_agg += np.asarray(
                        st_d["per_segment_blocks_touched"], np.int64)
                    nm = int(st[1])
                    score_rows += nm
                elif sharded:
                    st_d = sharded_fused_stats(index, nh, int(st[1]), cap,
                                               merged.n_boxes,
                                               flat=self._shard_flat)
                    nm = int(st[3])     # per-shard max (flat: global)
                    score_rows += int(st[4])
                else:
                    st_d = fused_stats(index, nh, cap, merged.n_boxes)
                    nm = int(st[1])
                    score_rows += nm
                self._accumulate_agg(agg, st_d, merged.n_boxes)
                if mesh_mode:
                    # pow2 keeps the tile divisible across mesh shards
                    rcap = self._pow2ceil(max(nm, 1))
                    keys, vals = sharded_survivor_tiles(
                        counts, gids, ok, row_capacity=rcap,
                        mesh=self.shard_mesh)
                    tile_parts.append((keys, vals))
                    tile_bytes += int(keys.nbytes) + int(vals.nbytes)
                else:
                    # quantum bucketing above 512 rows: at large survivor
                    # counts the tile IS the score memory, and the ~2x a
                    # pow2 round can overshoot would land straight on
                    # the scale gate's peak-bytes budget
                    rcap = _cap_hybrid(max(nm, 1), quantum=512)
                    round_parts.append((counts, gids, ok))
                    round_rcaps.append(rcap)
            if len(round_parts) == 1:
                # single-subset round: the compaction's output IS the
                # merged tile — no slice writes, no packing scratch
                keys, vals, _ = kops.survivor_tiles(
                    *round_parts[0], row_capacity=round_rcaps[0],
                    val_dtype=val_dt)
                tile_parts.append((keys, vals))
                tile_bytes += int(keys.nbytes) + int(vals.nbytes)
            elif round_parts:
                # one jit packs every subset of this round straight into
                # a single merged tile (in-place slice writes): peak is
                # the merged tile + one subset's scratch, never the
                # per-subset tiles PLUS a concatenated copy
                keys, vals = kops.packed_survivor_tiles(
                    tuple(round_parts), row_capacities=tuple(round_rcaps),
                    val_dtype=val_dt)
                tile_parts.append((keys, vals))
                tile_bytes += int(keys.nbytes) + int(vals.nbytes)
                transient = max(transient,
                                max(rc * (4 + nq * val_sz)
                                    for rc in round_rcaps))
            agg["retried_subsets"] += len(pending)
        if live:
            agg["per_segment_blocks_touched"] = per_seg_agg.tolist()
        return self._finish_sparse(tile_parts, tile_bytes, score_rows,
                                   agg, nq, view,
                                   transient_bytes=transient)

    def _device_scores_quantized(self, jobs, nq: int, view: _EngineView,
                                 deadline_s=None):
        """Sparse scoring against the COMPRESSED device mirrors
        (DESIGN.md §13, mirror='quantized'): the probe prunes zones in
        outward-widened f16 and tests rows in int8 code space with
        conservative thresholds — it can only OVER-select, never drop a
        true survivor — then the candidate ids cross to the host and the
        exact f32 rows of ONLY those candidates are staged back up for
        the bitwise re-check that emits the tiles. Device-resident row
        bytes drop ~4x; host staging is O(candidates) per subset. The
        extra per-subset candidate sync is why this path is opt-in: it
        trades the dense/sparse paths' pinned one-sync-per-round cadence
        for mirror compression."""
        agg = self._new_agg()
        tile_parts, tile_bytes, score_rows = [], 0, 0
        pending = [(sid, merged, owner,
                    self._initial_capacity(view.indexes[sid],
                                           merged.n_boxes))
                   for sid, merged, owner in jobs]
        while pending:
            with self._device_round(deadline_s) as rnd:
                launched = []
                for sid, merged, owner, cap in pending:
                    index = view.indexes[sid]
                    lo, hi, owner_p = pad_boxes(merged.lo, merged.hi,
                                                owner)
                    onehot = jnp.asarray(
                        (owner_p[:, None] == np.arange(nq)[None]
                         ).astype(np.float32))
                    lo_d, hi_d = jnp.asarray(lo), jnp.asarray(hi)
                    gids, cmask, st = quantized_probe(index, lo_d, hi_d,
                                                      capacity=cap)
                    launched.append((sid, merged, owner, cap, gids, cmask,
                                     st, lo_d, hi_d, onehot))
            stvecs = rnd.sync([l[6] for l in launched], agg)
            pending = []
            for (sid, merged, owner, cap, gids, cmask, _, lo_d, hi_d,
                 onehot), st in zip(launched, stvecs):
                index = view.indexes[sid]
                nh, ncand = int(st[0]), int(st[1])
                key = self._cap_key(sid, merged.n_boxes)
                self._cap_hints.observe(key, nh)
                if nh > cap:
                    agg["blocks_gathered"] += cap
                    # the discarded gather moved int8 rows: 1 byte/dim
                    agg["bytes_touched"] += int(
                        cap * index.block * len(index.dims))
                    pending.append((sid, merged, owner,
                                    min(self._pow2ceil(nh),
                                        index.n_blocks)))
                    continue
                st_d = fused_stats(index, nh, cap, merged.n_boxes)
                # the surviving gather also moved int8, not f32
                st_d["bytes_touched"] = int(st_d["bytes_touched"]) // 4
                self._accumulate_agg(agg, st_d, merged.n_boxes)
                rcap = self._pow2ceil(max(ncand, 1))
                cgids_dev, _ = quantized_compact(gids, cmask,
                                                 row_capacity=rcap)
                cgids = np.asarray(cgids_dev)      # O(candidates) sync
                agg["n_host_syncs"] += 1
                agg["host_bytes_transferred"] += int(cgids.nbytes)
                # stage the EXACT f32 rows of only the candidate set;
                # +inf pad rows match nothing and carry zeroed vals
                xsub = np.full((rcap, len(index.dims)), np.inf,
                               np.float32)
                livem = cgids >= 0
                if livem.any():
                    xsub[livem] = view.x[cgids[livem]][:, index.dims]
                agg["host_bytes_transferred"] += int(xsub.nbytes)
                keys, vals = quantized_recheck(jnp.asarray(xsub),
                                               jnp.asarray(cgids),
                                               lo_d, hi_d, onehot)
                score_rows += ncand
                tile_parts.append((keys, vals))
                tile_bytes += int(keys.nbytes) + int(vals.nbytes)
            agg["retried_subsets"] += len(pending)
        return self._finish_sparse(tile_parts, tile_bytes, score_rows,
                                   agg, nq, view)

    def _finish_sparse(self, tile_parts, tile_bytes: int, score_rows: int,
                       agg: Dict, nq: int, view: _EngineView, *,
                       transient_bytes: int = 0):
        """Merge the survivor tile parts into ONE SparseScores and close
        out the memory accounting. On the packed path there is exactly
        one part per round — already a single merged buffer, no copy —
        so the peak is the tiles plus the packing scratch the caller
        measured (``transient_bytes``). Multi-part rounds (mesh shards,
        the quantized re-check, retry rounds) still pay a concatenated
        copy, and the accounting says so. Either way the footprint is
        bounded by survivors, never by N*Q."""
        copied = 0
        if tile_parts:
            if len(tile_parts) == 1:
                keys, vals = tile_parts[0]
            else:
                keys = jnp.concatenate([t[0] for t in tile_parts])
                vals = jnp.concatenate([t[1] for t in tile_parts])
                copied = int(keys.nbytes) + int(vals.nbytes)
        else:
            keys = jnp.full((1,), kops.TILE_INVALID, jnp.int32)
            vals = jnp.zeros((1, nq), jnp.int32)
        sp = SparseScores(keys, vals, int(view.n))
        peak = int(tile_bytes) + max(copied, int(transient_bytes))
        agg["score_buffer_bytes_peak"] = peak
        agg["score_rows"] = int(score_rows)
        agg["dense_score_bytes_equiv"] = int(view.n) * nq * 4
        self._score_bytes_peak = max(self._score_bytes_peak, peak)
        return sp, self._finalize_agg(agg, view)

    def _scores_to_host(self, scores_dev, view: _EngineView) -> np.ndarray:
        """[N, Q] int32 host counts in GLOBAL row order from the device
        score buffer — the single transfer the max_results=None path
        pays. Sharded buffers are [S, Nloc_max, Q]; each shard's real
        rows land back at its global offset (padding never copied).
        Segmented (live) buffers are already in global id order.
        SparseScores transfer only the survivor tiles and de-duplicate
        by scatter-add — int32 addition makes the result bitwise equal
        to the dense transfer at O(survivors) traffic."""
        if isinstance(scores_dev, SparseScores):
            keys = np.asarray(scores_dev.keys)
            vals = np.asarray(scores_dev.vals)
            out = np.zeros((scores_dev.n, vals.shape[1]), np.int32)
            m = keys != int(kops.TILE_INVALID)
            np.add.at(out, keys[m], vals[m])
            return out
        if view.live or self.n_shards == 1:
            return np.asarray(scores_dev)
        sc = np.asarray(scores_dev)
        out = np.zeros((self.n, sc.shape[2]), sc.dtype)
        offs = self.indexes[0].offsets
        for s in range(self.n_shards):
            nl = int(offs[s + 1] - offs[s])
            if nl:
                out[offs[s]:offs[s] + nl] = sc[s, :nl]
        return out

    def _index_inference(self, boxsets: List[BoxSet], view: _EngineView):
        """Host/oracle range-query path (use_fused=False): per-subset
        query_index with the host prune/gather reference implementation.
        Kept as the correctness oracle for the device-resident path.
        Live catalogs run it per segment (counts land at each segment's
        global offset) with tombstoned rows zeroed afterwards — the host
        oracle of the masked segmented path."""
        counts = np.zeros(view.n, np.int64)
        agg = self._new_agg()
        if view.live:
            def qfn(segx, merged, use_pallas):
                c = np.zeros(view.n, np.int64)
                st_sum: Dict = {}
                for seg, off in zip(segx.segs, segx.offsets[:-1]):
                    cs, st = query_index(seg, merged, use_pallas=use_pallas)
                    c[off:off + seg.n_rows] = cs
                    for k, v in st.items():
                        st_sum[k] = st_sum.get(k, 0) + v
                return c, st_sum
        else:
            qfn = query_index_sharded if self.n_shards > 1 else query_index
        by_subset: Dict[int, List[BoxSet]] = {}
        for bs in boxsets:
            by_subset.setdefault(bs.subset_id, []).append(bs)
        for sid, group in by_subset.items():
            merged = group[0]
            for g in group[1:]:
                merged = merged.concatenate(g)
            c, st = qfn(view.indexes[sid], merged,
                        use_pallas=self.use_pallas)
            counts += c
            self._accumulate_agg(agg, st, merged.n_boxes)
        if view.valid_host is not None:
            counts = np.where(view.valid_host, counts, 0)
        return counts, self._finalize_agg(agg, view)

    def _run_index_path(self, boxsets, pos_ids, neg_ids,
                        include_training: bool, mr: Optional[int],
                        view: _EngineView, deadline_s=None):
        """Single-query index inference + ranking; fused engines score on
        device and, with ``mr`` set, rank on device too. ``boxsets`` is a
        List[BoxSet], or the ("device", lo, hi, entries) form handed out
        by the batched device fit — those boxes never touch the host."""
        if not self.use_fused:
            counts, stats = self._index_inference(boxsets, view)
            ids, scores = self._rank(counts, pos_ids, neg_ids,
                                     include_training)
            return ids, scores, stats    # query() applies the mr cut
        # job assembly (per-subset grouping, device slicing) sits between
        # fit and the first device round: billed so it never reads as an
        # unexplained gap in the trace
        with obs_trace.span("prepare") as sp:
            if isinstance(boxsets, tuple) and boxsets[0] == "device":
                _, lo_c, hi_c, ent = boxsets
                jobs, bound = self._make_jobs_flat(
                    [(lo_c, hi_c, g, sid, cnt, 0) for g, sid, cnt in ent],
                    1)
            else:
                jobs, bound = self._make_jobs([(bs, 0) for bs in boxsets],
                                              1)
            sp.set(jobs=len(jobs))
        scores_dev, stats = self._device_scores(jobs, 1, view,
                                                deadline_s=deadline_s)
        with obs_trace.span("rank"):
            if mr is None:
                counts = self._scores_to_host(scores_dev, view)[:, 0]
                # sparse buffers cross as tiles: price what actually moved
                stats["host_bytes_transferred"] += (
                    scores_dev.nbytes
                    if isinstance(scores_dev, SparseScores)
                    else int(counts.nbytes))
                ids, scores = self._rank(counts, pos_ids, neg_ids,
                                         include_training)
            else:
                ranked, hb = self._rank_device(
                    scores_dev, [(pos_ids, neg_ids, include_training)], mr,
                    bound, view)
                stats["host_bytes_transferred"] += hb
                ids, scores = ranked[0]
        return ids, scores, stats

    # ------------------------------------------------------------------
    def _rank(self, counts: np.ndarray, pos_ids: np.ndarray,
              neg_ids: np.ndarray, include_training: bool):
        """counts -> (ids ranked by confidence, scores) on the HOST — the
        ranking oracle the device stage must reproduce exactly: stable
        argsort of -counts == descending score, ascending id on ties."""
        found = np.nonzero(counts > 0)[0]
        if not include_training:
            found = found[~np.isin(found,
                                   np.concatenate([pos_ids, neg_ids]))]
        order = np.argsort(-counts[found], kind="stable")
        ids = found[order]
        return ids, counts[ids].astype(np.float64)

    def _rank_device(self, scores_dev, masks, k: int, score_bound: int,
                     view: _EngineView):
        """Device ranking (kops.rank_topk) over the [N, Q] device score
        buffer; only [Q, k] ids/scores plus [Q] valid counts cross to the
        host. masks: per-query (pos, neg, include_training). Returns
        ([(ids, scores)] aligned with masks, host bytes transferred).

        Sharded engines rank the [S, Nloc_max, Q] buffer with the
        per-shard top-k + cross-shard merge (core/index.
        sharded_rank_merge): identical tie-break contract, identical
        bits, still O(k) host traffic — training ids stay GLOBAL here
        and each shard drops the ones outside its row range. Segmented
        (live) buffers are global-id-ordered and already tombstone-
        masked, so they rank exactly like the single-device path."""
        n, nq = view.n, len(masks)
        # k is a static jit arg: pow2-bucket it (like capacities and the
        # tmax pad) so varied per-request max_results share compilations;
        # callers slice the valid prefix down to their own k
        kk = min(self._pow2ceil(max(int(k), 1)), n)
        tmax = max([1] + [len(p) + len(ng) for p, ng, inc in masks
                          if not inc])
        tmax = -(-tmax // 16) * 16      # bucket -> few distinct jit keys
        tids = np.full((nq, tmax), n, np.int32)   # N pads are dropped
        for q, (pos, neg, inc) in enumerate(masks):
            if not inc:
                tr = np.concatenate([pos, neg])
                tids[q, :len(tr)] = tr
        if isinstance(scores_dev, SparseScores):
            # the tiles carry GLOBAL ids, so one streaming merge + top-k
            # serves every configuration — monolithic, sharded and live
            # alike; no per-shard extraction stage, still [Q, k] out
            ids_k, scores_k, n_valid = kops.sparse_topk(
                scores_dev.keys, scores_dev.vals, jnp.asarray(tids), k=kk)
        elif self.n_shards > 1 and not view.live:
            ids_k, scores_k, n_valid = sharded_rank_merge(
                view.indexes[0], scores_dev, jnp.asarray(tids), k=kk,
                score_bound=score_bound, mesh=self.shard_mesh)
        else:
            ids_k, scores_k, n_valid = kops.rank_topk(
                scores_dev, jnp.asarray(tids), k=kk,
                score_bound=score_bound, scores_transposed=True)
        ids_k = np.asarray(ids_k)
        scores_k = np.asarray(scores_k)
        n_valid = np.asarray(n_valid)
        hb = int(ids_k.nbytes + scores_k.nbytes + n_valid.nbytes)
        out = []
        for q in range(nq):
            nv = int(n_valid[q])
            out.append((ids_k[q, :nv].astype(np.int64),
                        scores_k[q, :nv].astype(np.float64)))
        return out, hb

    def query_batch(self, requests: Sequence[Dict],
                    deadline_s: Optional[float] = None) -> List:
        """Answer MANY concurrent queries with ONE fused device call per
        feature subset, all accumulating into ONE [N, Q] device score
        buffer (the tentpole of the batched serving path).

        Each request is a dict with ``pos_ids``/``neg_ids`` plus the same
        optional keys query() accepts (model, max_depth, n_models, seed,
        include_training, max_results, ...). Index-path models
        (dbranch/dbens) are fitted per request, their boxes flattened with
        a per-box owner id, grouped per subset, and every subset answered
        by a single fused device call whose one-hot ownership map de-muxes
        counts per query ON DEVICE. When every request in the batch sets
        ``max_results`` the ranking runs on device too and only [Q, k]
        crosses to the host. Non-index models fall back to sequential
        query().

        Returns a list aligned with ``requests``; entries are QueryResult
        on success or the raised Exception on per-request failure (the
        batch itself never dies — serve-layer error isolation).

        Stats: batch-wide aggregates describe the SHARED device phase and
        are namespaced ``batch_*``; the only per-request figure is
        ``n_boxes`` (that request's own box count)."""
        results: List = [None] * len(requests)
        # the WHOLE window binds one catalog snapshot: appends/deletes/
        # compactions landing while this batch runs take effect for the
        # NEXT window, never mid-flight (DESIGN.md §12)
        view = self._view()
        to_fit = []   # (slot, model, pos, neg, incl, mr, depth, n_models, seed)
        for i, req in enumerate(requests):
            try:
                model = req.get("model", "dbranch")
                if model not in MODELS:
                    raise ValueError(
                        f"unknown model {model!r}; choose from {MODELS}")
                if model not in ("dbranch", "dbens") or not self.use_fused:
                    kw = {k: v for k, v in req.items()
                          if k not in ("pos_ids", "neg_ids", "model")}
                    results[i] = self.query(req["pos_ids"], req["neg_ids"],
                                            model=model, **kw)
                    continue
                pos = np.asarray(list(req["pos_ids"]), np.int64)
                neg = np.asarray(list(req["neg_ids"]), np.int64)
                mr = (req["max_results"] if "max_results" in req
                      else self.max_results)
                to_fit.append((i, model, pos, neg,
                               req.get("include_training", False), mr,
                               req.get("max_depth", 12),
                               req.get("n_models", 25), req.get("seed", 0)))
            except Exception as e:  # noqa: BLE001 — per-request isolation
                results[i] = e
        if not to_fit:
            return results
        check_deadline(deadline_s, "batch fit")

        # ---- fit phase: the WHOLE window trains on device together ----
        # (one jit'd program per distinct max_depth — DESIGN.md §10);
        # use_jax_fit=False keeps the per-request numpy oracle
        t0 = time.perf_counter()
        fitted = []   # (slot, model, boxsets, pos, neg, incl, mr, t_fit)
        # window-wide device-fit failures that fell back to the numpy
        # trainer: answers stay exact, so only this count shows it
        fit_fallbacks = 0
        # slot -> ("device", lo, hi, entries) or List[BoxSet] fallback
        boxsets_by_slot: Dict[int, object] = {}
        # the batched fit is one shared device phase: every trace in the
        # window carries the same fit span (shared-cost attribution)
        with obs_trace.span("fit", {"batch": len(to_fit)}):
            if self.use_jax_fit:
                by_depth: Dict[int, List] = {}
                for it in to_fit:
                    by_depth.setdefault(it[6], []).append(it)
                for depth, items in by_depth.items():
                    try:
                        lo_c, hi_c, entries = self._fit_boxes_batched(
                            [(it[1], view.x[it[2]], view.x[it[3]], it[7],
                              it[8]) for it in items], max_depth=depth,
                            return_device=True, frange=view.frange)
                    except Exception:  # noqa: BLE001 — degrade, don't die
                        entries = None  # batch-wide: per-request oracle
                        fit_fallbacks += 1
                        log.warning("batched device fit failed for %d "
                                    "requests; refitting each on the "
                                    "numpy trainer", len(items),
                                    exc_info=True)
                    for j, it in enumerate(items):
                        if entries is not None and not isinstance(
                                entries[j], Exception):
                            boxsets_by_slot[it[0]] = ("device", lo_c, hi_c,
                                                      entries[j])
                            continue
                        # this request failed the device fit (or the
                        # whole window did): retry it alone on the numpy
                        # oracle so one bad label set never drags the
                        # batch down
                        try:
                            boxsets_by_slot[it[0]] = self._fit_boxes(
                                it[1], view.x[it[2]], view.x[it[3]],
                                max_depth=it[6], n_models=it[7],
                                seed=it[8], use_jax=False,
                                frange=view.frange)
                        except Exception as e:  # noqa: BLE001
                            results[it[0]] = e
            else:
                for it in to_fit:
                    t1 = time.perf_counter()
                    try:
                        boxsets = self._fit_boxes(
                            it[1], view.x[it[2]], view.x[it[3]],
                            max_depth=it[6], n_models=it[7], seed=it[8],
                            frange=view.frange)
                    except Exception as e:  # noqa: BLE001
                        results[it[0]] = e
                        continue
                    fitted.append((it[0], it[1], boxsets, it[2], it[3],
                                   it[4], it[5], time.perf_counter() - t1))
        fit_wall = time.perf_counter() - t0
        if self.use_jax_fit:
            # the fit is a shared device phase; bill it evenly
            share = fit_wall / max(len(boxsets_by_slot), 1)
            for it in to_fit:
                if it[0] in boxsets_by_slot:
                    fitted.append((it[0], it[1], boxsets_by_slot[it[0]],
                                   it[2], it[3], it[4], it[5], share))
        if not fitted:
            return results

        # ---- ONE fused device call per subset, ONE deferred sync -------
        t0 = time.perf_counter()
        nq = len(fitted)
        # shared assembly wall, same attribution rule as the fit span
        with obs_trace.span("prepare") as sp:
            # device-fit requests contribute (winner-array, row) parts
            # and never touch the host; oracle-fit (or fallback) requests
            # contribute classic BoxSets — both merge into the same jobs
            flat_parts, box_pairs = [], []
            for q, (_, _, boxes, *_r) in enumerate(fitted):
                if isinstance(boxes, tuple) and boxes[0] == "device":
                    flat_parts += [(boxes[1], boxes[2], g, sid, cnt, q)
                                   for g, sid, cnt in boxes[3]]
                else:
                    box_pairs += [(bs, q) for bs in boxes]
            jobs, bound = [], 0
            if flat_parts:
                jobs, bound = self._make_jobs_flat(flat_parts, nq)
            if box_pairs:
                j2, b2 = self._make_jobs(box_pairs, nq)
                # a request's boxes live entirely in one form, so
                # per-query score bounds combine by max
                jobs, bound = jobs + j2, max(bound, b2)
            sp.set(jobs=len(jobs))
        scores_dev, agg = self._device_scores(jobs, nq, view,
                                              deadline_s=deadline_s)

        # ---- ranking ---------------------------------------------------
        with obs_trace.span("rank"):
            mrs = [f[6] for f in fitted]
            if all(m is not None for m in mrs):
                masks = [(pos, neg, incl)
                         for (_, _, _, pos, neg, incl, _, _) in fitted]
                ranked, hb = self._rank_device(scores_dev, masks, max(mrs),
                                               bound, view)
                agg["host_bytes_transferred"] += hb
                ranked = [(ids[:m], sc[:m])
                          for (ids, sc), m in zip(ranked, mrs)]
            else:
                # any full-result request forces the score buffer to the
                # host ONCE; ranking shares the oracle so truncated
                # requests still see the exact device-ranking prefix
                counts = np.ascontiguousarray(
                    self._scores_to_host(scores_dev, view).T)
                # sparse buffers cross as tiles: price what actually moved
                agg["host_bytes_transferred"] += (
                    scores_dev.nbytes
                    if isinstance(scores_dev, SparseScores)
                    else int(counts.nbytes))
                ranked = []
                for q, (_, _, _, pos, neg, incl, m, _) in enumerate(fitted):
                    ids, sc = self._rank(counts[q], pos, neg, incl)
                    if m is not None:
                        ids, sc = ids[:m], sc[:m]
                    ranked.append((ids, sc))
        t_query = time.perf_counter() - t0

        # ---- de-mux to per-request results -----------------------------
        base = {f"batch_{k}": v for k, v in agg.items()}
        base["path"] = "index"
        base["batch_size"] = nq
        base["batch_fit_s"] = fit_wall
        base["batch_fit_fallbacks"] = fit_fallbacks
        for q, (slot, model, boxes, pos, neg, incl, m, t_fit) in enumerate(
                fitted):
            ids, sc = ranked[q]
            # per request: which trainer produced THESE boxes
            on_device = isinstance(boxes, tuple) and boxes[0] == "device"
            if on_device:
                nb = int(sum(cnt for _, _, cnt in boxes[3]))
            else:
                nb = int(sum(bs.n_boxes for bs in boxes))
            stats = {**base, "n_boxes": nb,
                     "fit_path": "jax" if on_device else "numpy"}
            results[slot] = QueryResult(model, ids, sc, t_fit, t_query,
                                        stats)
        return results

    # ------------------------------------------------------------------
    def refine(self, result: QueryResult, extra_pos: Sequence[int],
               extra_neg: Sequence[int], prev_pos: Sequence[int],
               prev_neg: Sequence[int], **kw) -> QueryResult:
        """Paper §5: iterative refinement — add labels, re-query.

        No index rebuild is needed (the index is label-independent);
        only the (cheap) model fit and the range queries rerun."""
        pos = list(prev_pos) + list(extra_pos)
        neg = list(prev_neg) + list(extra_neg)
        return self.query(pos, neg, model=result.model, **kw)
