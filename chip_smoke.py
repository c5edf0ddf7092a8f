#!/usr/bin/env python3
"""End-to-end smoke of the search engine on a TPU.

    python chip_smoke.py                 # one chip: the serving path
    python chip_smoke.py --four-chips    # four chips: the sharded catalog

One chip: a live catalog of ``--rows`` clustered Gaussian rows at the
paper's feature width (d=384) served over HTTP exactly as
``python -m repro.serve.http`` serves it (SearchEngine(live=True) ->
QueryServer -> HttpFrontEnd). It posts a window of concurrent dbranch /
dbens queries, an append followed by a query, and one repeat that the
result cache answers, and requires every answer to come from the device
trainer with no error and no fallback. It then checks that the compiled
query program holds the Pallas kernels, and that a live engine over the
first 50,000 rows ranks bitwise like the plain reference (numpy trainer,
jnp counts, host ranking).

Four chips: the catalog sharded over a four-device mesh must rank
bitwise like the one-shard engine, with every shard's mirrors on its
own device.

Everything runs in this one process (the HTTP loop is a thread). With no
TPU the script exits non-zero before doing any work. Timings printed on
the way are one-off readings, not benchmark numbers. The last line of
standard output is one JSON object: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.configs.rapidearth_vit import FEATURE_DIM  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

N_CLUSTERS = 1024
PARITY_ROWS = 50_000     # labels come from here, so every engine can serve them
MAX_RESULTS = 100
WINDOW = 8               # concurrent requests: one batch window


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(*parts) -> None:
    print("smoke:", *parts, flush=True)


# ----------------------------------------------------------------------
# data and requests, all from --seed
# ----------------------------------------------------------------------

def make_features(n: int, seed: int):
    """Clustered Gaussians at d=384, float32 throughout (no float64 copy
    of the catalog on the host). Returns (features, cluster of each row,
    cluster centres)."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(0, 5.0, (N_CLUSTERS, FEATURE_DIM)).astype(np.float32)
    assign = rng.integers(0, N_CLUSTERS, n)
    feats = rng.standard_normal((n, FEATURE_DIM), dtype=np.float32)
    step = 65_536
    for i in range(0, n, step):
        part = feats[i:i + step]
        part *= np.float32(0.3)
        part += centers[assign[i:i + step]]
    return feats, assign, centers


def make_requests(assign: np.ndarray, seed: int):
    """WINDOW label sets, one cluster each, drawn from the first
    PARITY_ROWS rows; dbranch and dbens alternate."""
    rng = np.random.default_rng(seed + 1)
    head = assign[:PARITY_ROWS]
    reqs = []
    for i in range(WINDOW):
        in_c = np.nonzero(head == i)[0]
        out_c = np.nonzero(head != i)[0]
        reqs.append({
            "pos_ids": rng.choice(in_c, min(12, len(in_c)),
                                  replace=False).tolist(),
            "neg_ids": rng.choice(out_c, 40, replace=False).tolist(),
            "model": ("dbranch", "dbens")[i % 2],
            "max_results": MAX_RESULTS})
    return reqs


# ----------------------------------------------------------------------
# HTTP client
# ----------------------------------------------------------------------

def post(port: int, path: str, body: dict):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
    try:
        conn.request("POST", path, json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def check_answer(status: int, body: dict, what: str) -> None:
    check(status == 200 and body.get("ok"),
          f"{what}: HTTP {status} {body.get('error_type')} "
          f"{body.get('error')}")
    check(body.get("fit_path") == "jax",
          f"{what}: fit_path {body.get('fit_path')!r}, not the device "
          f"trainer")
    check(0 < len(body["ids"]) <= MAX_RESULTS,
          f"{what}: {len(body['ids'])} ids")


def same_ranking(got, want, what: str) -> None:
    for i, (a, b) in enumerate(zip(got, want)):
        check(not isinstance(a, Exception), f"{what}[{i}] raised {a!r}")
        check(not isinstance(b, Exception),
              f"{what}[{i}] reference raised {b!r}")
        check(np.array_equal(a.ids, b.ids)
              and np.array_equal(a.scores, b.scores),
              f"{what}[{i}]: ranked ids/scores differ from the reference")


# ----------------------------------------------------------------------
# one chip
# ----------------------------------------------------------------------

def serve_over_http(engine, reqs, centers, seed: int) -> None:
    from repro.serve.cache import ResultCache
    from repro.serve.engine import QueryServer
    from repro.serve.http import HttpFrontEnd

    server = QueryServer(engine, max_results=MAX_RESULTS,
                         max_batch=WINDOW, batch_window_s=0.05,
                         cache=ResultCache())
    server.start()
    front = HttpFrontEnd(server, host="127.0.0.1", port=0)
    _, port = front.start()
    try:
        t0 = time.perf_counter()
        with ThreadPoolExecutor(WINDOW) as pool:
            answers = list(pool.map(lambda r: post(port, "/query", r), reqs))
        say(f"first window ({WINDOW} queries, compiles included) "
            f"{time.perf_counter() - t0:.3f} s")
        for i, (status, body) in enumerate(answers):
            check_answer(status, body, f"query {i}")
        say(f"{len(answers)} answers ok, fit_path "
            f"{sorted({body['fit_path'] for _, body in answers})}")

        rng = np.random.default_rng(seed + 2)
        extra = (centers[rng.integers(0, N_CLUSTERS, 1024)]
                 + np.float32(0.3) * rng.standard_normal(
                     (1024, FEATURE_DIM), dtype=np.float32))
        status, body = post(port, "/ingest", {"op": "append",
                                              "features": extra.tolist()})
        check(status == 200 and body.get("ok"),
              f"append: HTTP {status} {body.get('error')}")
        for what in ("query after append", "repeated query"):
            t0 = time.perf_counter()
            status, body = post(port, "/query", reqs[0])
            check_answer(status, body, what)
            say(f"{what}: {1e3 * (time.perf_counter() - t0):.3f} ms, "
                f"cache {body['cache']}")
        check(body["cache"] == "hit", "the repeated query missed the cache")
    finally:
        front.close()
        server.close()
    summ = server.summary()
    say("server:", {k: summ[k] for k in (
        "served", "errors", "batches", "batched_queries", "batch_fallbacks",
        "fit_fallbacks", "ingests", "ingest_errors", "cache_served")})
    check(summ["errors"] == 0, f"{summ['errors']} queries failed")
    check(summ["batch_fallbacks"] == 0, "a batch window fell back")
    check(summ["fit_fallbacks"] == 0, "a device fit fell back to numpy")
    check(summ["ingest_errors"] == 0, "the append failed")
    check(summ["batched_queries"] >= 2, "no batch window formed")


def check_pallas(engine) -> None:
    """The fused query program at the served catalog's mirror shapes
    must hold the Pallas kernels, not the jnp oracles."""
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    index = engine._view().indexes[0]
    rows3, zlo, zhi = index.device_arrays()
    n_boxes, dsub = 64, rows3.shape[-1]
    lo = jnp.zeros((n_boxes, dsub), jnp.float32)
    onehot = jnp.ones((n_boxes, WINDOW), jnp.float32)
    text = kops.fused_query.lower(
        rows3, zlo, zhi, lo, lo + 1, onehot,
        capacity=engine._initial_capacity(index)).as_text()
    n = text.count("tpu_custom_call")
    say(f"fused_query over {rows3.shape[0]} blocks: {n} tpu_custom_call")
    check(n >= 2, "zone_prune and box_scan_seg are not Pallas calls")


def check_parity(feats, reqs) -> None:
    from repro.core.engine import SearchEngine

    head = feats[:PARITY_ROWS]
    live = SearchEngine(head, live=True)
    ref = SearchEngine(head, use_jax_fit=False, use_fused=False,
                       use_pallas=False)
    got = live.query_batch(reqs)
    same_ranking(got, ref.query_batch(reqs), "parity")
    check(all(r.stats["fit_path"] == "jax" for r in got),
          "parity: the live engine did not fit on device")
    say(f"parity ok: {len(reqs)} requests over {len(head)} rows, ids and "
        f"scores bitwise equal to the reference")


def one_chip(args) -> None:
    import jax

    from repro.core.engine import SearchEngine

    t0 = time.perf_counter()
    feats, assign, centers = make_features(args.rows, args.seed)
    reqs = make_requests(assign, args.seed)
    say(f"rows {feats.shape[0]} d {feats.shape[1]} generated in "
        f"{time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    engine = SearchEngine(feats, live=True)
    say(f"build {time.perf_counter() - t0:.3f} s "
        f"({len(engine.subsets)} subsets x {engine.subsets.shape[1]} dims)")

    serve_over_http(engine, reqs, centers, args.seed)

    engine.query_batch(reqs)
    t0 = time.perf_counter()
    warm = engine.query_batch(reqs)
    dt = time.perf_counter() - t0
    check(not any(isinstance(r, Exception) for r in warm),
          "warm window raised")
    say(f"warm window {1e3 * dt:.3f} ms, {1e3 * dt / len(reqs):.3f} ms "
        f"per query (engine.query_batch, {len(reqs)} queries)")
    st = engine.index_stats()
    say(f"device mirrors {st['device_bytes']['total']} bytes "
        f"{st['device_bytes']}")
    mem = jax.devices()[0].memory_stats() or {}
    say(f"peak_bytes_in_use {mem.get('peak_bytes_in_use')}")
    check_pallas(engine)
    del engine
    check_parity(feats, reqs)


# ----------------------------------------------------------------------
# four chips
# ----------------------------------------------------------------------

def four_chips(args) -> None:
    import jax

    from repro.core.engine import SearchEngine

    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chips needs 4 devices, found {len(devs)}")
    feats, assign, _ = make_features(args.rows, args.seed)
    reqs = make_requests(assign, args.seed)
    say(f"rows {feats.shape[0]} d {feats.shape[1]}")

    t0 = time.perf_counter()
    sharded = SearchEngine(feats, n_shards=4)
    say(f"sharded build {time.perf_counter() - t0:.3f} s")
    mesh = sharded.shard_mesh
    check(mesh is not None, "n_shards=4 built no mesh")
    mesh_devs = set(mesh.devices.flat)
    check(len(mesh_devs) == 4, f"mesh spans {len(mesh_devs)} devices")
    got = sharded.query_batch(reqs)

    # every stacked mirror: one [1, ...] shard on each mesh device
    for ix in sharded.indexes:
        for arr in (*ix.device_arrays(mesh), ix.device_gids(mesh)):
            on = [s.device for s in arr.addressable_shards]
            check(set(on) == mesh_devs and len(on) == 4,
                  f"subset {ix.subset_id}: mirror shards on {on}")
            check(all(s.data.shape[0] == 1 for s in arr.addressable_shards),
                  f"subset {ix.subset_id}: a device holds more than one "
                  f"shard")
    used = [(d.memory_stats() or {}).get("bytes_in_use") for d in devs[:4]]
    say(f"bytes_in_use per device {used}")

    t0 = time.perf_counter()
    single = SearchEngine(feats, n_shards=1)
    say(f"one-shard build {time.perf_counter() - t0:.3f} s")
    same_ranking(got, single.query_batch(reqs), "sharded vs one shard")
    say(f"sharded == one shard: {len(reqs)} requests, ids and scores "
        f"bitwise equal")


# ----------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=None,
                    help="catalog rows (default 2,000,000 on one chip, "
                         "1,000,000 with --four-chips)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path and its comparison")
    args = ap.parse_args(argv)
    if args.rows is None:
        args.rows = 1_000_000 if args.four_chips else 2_000_000
    check(args.rows >= PARITY_ROWS, f"--rows must be >= {PARITY_ROWS}")

    cache_dir = enable_compile_cache()
    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (JAX backend {jax.default_backend()!r})",
              file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    say(f"device_kind {dev.device_kind} platform {dev.platform} "
        f"count {len(jax.devices())} compile cache {cache_dir}")
    (four_chips if args.four_chips else one_chip)(args)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
