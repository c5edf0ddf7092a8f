#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``: catalog, engine and server settings) and
a traffic mix (``bench/traffic/<mix>.json``). One run, in one process:

1. fails before any work unless JAX finds a TPU and the cell's chips;
2. makes the catalog on the device from ``--seed`` and copies it to the
   host; builds ``SearchEngine`` and serves it through ``QueryServer`` and
   ``HttpFrontEnd`` on 127.0.0.1;
3. warms up with the mix's own traffic (untimed): a grid of requests
   of every label-count bucket and model, then fresh requests, in device
   windows of every size the server forms, then the mix over HTTP; then
   drives ``POST /query`` for ``--seconds``;
4. with ``--trace 1`` profiles that window and reads the per-layer
   metrics (``bench/metrics/<metric>.py``), otherwise reads the
   end-to-end metrics;
5. frees the engine, checks a sample of the window's answers, drawn from
   the seed, against the plain reference (``bench/reference.py``), and
   prints one JSON line last on standard output.

Set-up (``setup_s``) runs from the start of the process to the window's
first due request. JAX's persistent compilation cache is kept in
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import trace_reduce  # noqa: E402
from bench.catalog import make_catalog  # noqa: E402
from bench.reference import Reference, compare  # noqa: E402
from bench.roofline import peaks_for  # noqa: E402
from bench.traffic import client, driver, load_mix  # noqa: E402
from bench.traffic.labels import LabelSets  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# the run's independent random streams, each drawn from --seed
WINDOW_LABELS, WINDOW_ARRIVALS, WARM_LABELS, WARM_ARRIVALS, SAMPLE = range(5)


def stream(seed: int, which: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), which])


class NoChip(Exception):
    pass


# ----------------------------------------------------------------------
# the benchmark as data
# ----------------------------------------------------------------------

def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def resolve(bench: dict, workload: str, root: Path = ROOT):
    """(workload entry, config entry, config file contents, mix)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(root / conf["file"]) as f:
        cfg = json.load(f)
    return cell, conf, cfg, load_mix(cell["traffic"],
                                     root / "bench" / "traffic")


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    keeping every program (also the ones that compile fast), so that only
    the first run of a cell here compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def cell_metrics(bench: dict, cell: dict, trace: bool):
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or
    with ``trace`` its per-layer ones."""
    e2e = [m for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell["name"] in m.get("workloads", [cell["name"]])
            and m["moves"] in moved]


def metric_reader(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ----------------------------------------------------------------------
# what the harness observes
# ----------------------------------------------------------------------

class Compiles:
    """Every executable JAX builds or loads from its persistent cache,
    timed by JAX's own compile event."""

    def __init__(self):
        self.events = []
        self.cache_hits = []
        self._lock = threading.Lock()
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            with self._lock:
                self.events.append((time.perf_counter(), float(duration),
                                    str(kw.get("fun_name", ""))))

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits.append(time.perf_counter())

    def between(self, t0: float, t1: float):
        with self._lock:
            return [e for e in self.events if t0 <= e[0] <= t1]


def watch_windows(engine, sink: list) -> None:
    """Record each device window's own counters (syncs, surviving blocks,
    boxes, queries) as the served path calls the engine. Observation
    only: the wrapped calls return what they returned."""
    query_batch, query = engine.query_batch, engine.query

    def note(t0, stats, prefix, size):
        sink.append({"t0": t0, "t1": time.perf_counter(),
                     "syncs": stats.get(prefix + "n_host_syncs", 0),
                     "blocks_touched": stats.get(prefix + "blocks_touched",
                                                 0),
                     "n_boxes": stats.get(prefix + "n_boxes", 0),
                     "size": size})

    def watched_batch(requests, *a, **kw):
        t0 = time.perf_counter()
        out = query_batch(requests, *a, **kw)
        st = next((r.stats for r in out if not isinstance(r, Exception)),
                  None)
        if st is not None:
            note(t0, st, "batch_", int(st.get("batch_size", len(out))))
        return out

    def watched_query(*a, **kw):
        t0 = time.perf_counter()
        res = query(*a, **kw)
        note(t0, res.stats, "", 1)
        return res

    engine.query_batch = watched_batch
    engine.query = watched_query


# ----------------------------------------------------------------------
# one cell
# ----------------------------------------------------------------------

class Cell:
    """A cell's catalog, engine and server, set up once."""

    def __init__(self, cfg: dict, mix: dict, seed: int, chips: int, *,
                 require_tpu: bool = True):
        import jax
        backend = jax.default_backend()
        if require_tpu and backend != "tpu":
            raise NoChip(f"JAX backend is {backend!r}, not 'tpu'")
        devs = jax.devices()
        if len(devs) < chips:
            raise NoChip(f"{len(devs)} device(s), the cell needs {chips}")
        self.devices = devs[:chips]
        self.compiles = Compiles()
        self.cfg, self.mix, self.seed = cfg, mix, seed
        cat = cfg["catalog"]
        self.x, self.cluster = make_catalog(seed, cat["rows"], cat["dim"],
                                            cat["n_clusters"], cat["spread"],
                                            cat["noise"])
        from repro.core.engine import SearchEngine
        from repro.serve.cache import ResultCache
        from repro.serve.engine import QueryServer
        from repro.serve.http import HttpFrontEnd

        t0 = time.perf_counter()
        self.engine = SearchEngine(self.x, **cfg["engine"])
        self.index_build_s = time.perf_counter() - t0
        self.windows: list = []
        watch_windows(self.engine, self.windows)
        srv = dict(cfg["server"])
        cache = ResultCache() if srv.pop("result_cache") else None
        self.server = QueryServer(self.engine, cache=cache, **srv)
        self.server.start()
        self.front = HttpFrontEnd(self.server, host="127.0.0.1", port=0)
        _, self.port = self.front.start()
        self.labels = LabelSets(self.cluster, mix,
                                stream(seed, WINDOW_LABELS))
        self.arrivals = stream(seed, WINDOW_ARRIVALS)
        self.warm_labels = LabelSets(self.cluster, mix,
                                     stream(seed, WARM_LABELS))
        self.warm_arrivals = stream(seed, WARM_ARRIVALS)

    def drive(self, mix: dict, seconds: float, on_done=None,
              grace_s: float = 60.0, warm: bool = False):
        """Drive the mix over HTTP for ``seconds``: the window's streams,
        or with ``warm`` the warm-up's."""
        drv = driver(mix["kind"])
        bodies = draw_bodies(self.warm_labels if warm else self.labels,
                             mix, seconds)
        return drv.drive(self.port, bodies, mix, seconds,
                         self.warm_arrivals if warm else self.arrivals,
                         on_done=on_done, grace_s=grace_s)

    def warm_up(self, mix: dict, log=None):
        """Set-up's warm-up, from streams of its own, straight through the
        engine (so the server's result cache stays cold), then the mix
        over HTTP. First the ``grid``: one request of every listed
        positive count, negative count and model, in device windows of
        each size the server can form (``sizes``), so that every bucket
        of the fit's label shapes is built whatever the seed draws; then
        ``rounds`` passes over windows of those sizes of fresh requests
        of the mix, for the shapes that follow the data (survivor and box
        counts, ranking). The window's compiles read what is left.
        Returns the requests sent."""
        w = mix["warmup"]
        n = 0

        def window(bodies):
            out = self.engine.query_batch(bodies)
            bad = [x for x in out if isinstance(x, Exception)]
            if bad:
                raise RuntimeError(f"warm-up window failed: {bad[0]!r}")
            return len(out)

        def note(what, t0):
            if log is not None:
                built = self.compiles.between(t0, time.perf_counter())
                log(f"bench: warm-up {what}: {len(built)} executables "
                    f"built or loaded in {time.perf_counter() - t0:.3f} s")

        grid = w.get("grid")
        if grid:
            t0 = time.perf_counter()
            reqs = [self.warm_labels.one(int(p), int(q), str(m))
                    for p in grid["positives"] for q in grid["negatives"]
                    for m in grid["models"]]
            for q in w["sizes"]:
                for i in range(0, len(reqs), int(q)):
                    n += window(reqs[i:i + int(q)])
            note(f"grid of {len(reqs)} label shapes", t0)
        rounds, t0 = int(w["rounds"]), time.perf_counter()
        for r in range(rounds):
            for q in w["sizes"]:
                n += window(self.warm_labels.draw(int(q)))
            if (r + 1) % max(rounds // 5, 1) == 0 or r + 1 == rounds:
                note(f"rounds to {r}", t0)
                t0 = time.perf_counter()
        # the first run in a checkout compiles inside its warm-up: give
        # the answers as long as such a run may take
        warm, _, _ = self.drive(mix, float(w["http_s"]), grace_s=900.0,
                                warm=True)
        bad = [r for r in warm if not r.get("ok")]
        if bad:
            raise RuntimeError(f"{len(bad)} of {len(warm)} warm-up requests "
                               f"failed: {bad[0].get('error')}")
        return n + len(warm)

    def close(self) -> None:
        self.front.close()
        self.server.close()
        self.front = self.server = self.engine = None
        gc.collect()


def draw_bodies(labels: LabelSets, mix: dict, seconds: float):
    """The bodies a window of ``seconds`` may send, in the order sent:
    one stratified draw for the whole window, or one per ``stratum``
    requests where the callers send as fast as they are answered."""
    drv = driver(mix["kind"])
    n = drv.needed(mix, seconds)
    step = drv.stratum(mix) if hasattr(drv, "stratum") else n
    return [b for i in range(0, n, step)
            for b in labels.draw(min(step, n - i))]


def _spans_on_trace_clock(spans: dict, offset_ns: float):
    out = {}
    for tr in spans.values():
        for sp in tr.get("spans", []):
            s = sp["t0"] * 1e9 + offset_ns
            out[(sp["name"], round(s))] = (sp["name"], s,
                                           s + sp["dur_s"] * 1e9)
    return list(out.values())


def run(bench: dict, cell: dict, cfg: dict, mix: dict, seed: int,
        seconds: float, trace: bool, *, require_tpu: bool = True,
        log=print) -> dict:
    """One run of ``cell`` (its configuration file ``cfg`` and mix ``mix``
    already read); returns the result line as a dict."""
    import jax

    workload = cell["name"]
    c = Cell(cfg, mix, seed, int(cell["chips"]), require_tpu=require_tpu)
    log(f"bench: {workload} seed {seed}: {cfg['catalog']['rows']} rows "
        f"built in {c.index_build_s:.3f} s")

    t0 = time.perf_counter()
    n_warm = c.warm_up(mix, log=log)
    log(f"bench: warm-up {n_warm} requests in "
        f"{time.perf_counter() - t0:.3f} s, "
        f"{len(c.compiles.between(t0, time.perf_counter()))} executables "
        f"built or loaded")

    spans: dict = {}
    lock = threading.Lock()
    store = c.server.obs.traces

    def on_done(rec):
        tid = rec.get("trace_id")
        if tid:
            tr = store.get(tid)
            if tr is not None:
                with lock:
                    spans[tid] = tr

    trace_dir = None
    before = c.server.summary()
    n_windows_before = len(c.windows)
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
        p0 = time.perf_counter()
        ann.__enter__()
    t_drive = time.perf_counter()
    records, t_start, t_end = c.drive(mix, seconds, on_done=on_done)
    t_back = time.perf_counter()
    setup_s = t_start - T_PROCESS
    summary = None
    if trace:
        ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        pd = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
        offset = trace_reduce.window_bounds(pd)[0] - p0 * 1e9
        summary = trace_reduce.reduce(
            pd, t_start * 1e9 + offset, t_end * 1e9 + offset,
            _spans_on_trace_clock(spans, offset))
        summary["to_trace_ns"] = lambda t: t * 1e9 + offset
        del pd
        shutil.rmtree(trace_dir, ignore_errors=True)
    after = c.server.summary()
    windows = c.windows[n_windows_before:]
    compiles = c.compiles.between(t_drive, t_back)
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
           for d in c.devices]
    dev0 = c.devices[0]

    # ---- end-to-end ----------------------------------------------------
    answered = [r for r in records if r.get("ok")]
    failed = len(records) - len(answered)
    lat = [client.latency_s(r) for r in records]
    late = [r["sent"] - r["due"] for r in records if "sent" in r]
    e2e = {
        "queries_per_s": len(answered) / (t_end - t_start),
        "query_p90_ms": client.percentile_ms(lat, 90),
        "setup_s": setup_s,
    }
    hits = sum(1 for t in c.compiles.cache_hits if t_drive <= t <= t_back)
    log(f"bench: window {len(records)} requests, {failed} failed, "
        f"{len(windows)} device windows, {len(compiles)} executables built "
        f"or loaded in the window ({hits} from the persistent cache, "
        f"{sum(d for _, d, _ in compiles):.3f} s) "
        f"{sorted({n for _, _, n in compiles})[:12]}")
    if late:
        log(f"bench: generator lateness ms p50 "
            f"{client.percentile_ms(late, 50):.3f} p99 "
            f"{client.percentile_ms(late, 99):.3f} max {1e3 * max(late):.3f}")

    wanted = cell_metrics(bench, cell, trace)
    metrics = {}
    if not trace:
        for m in wanted:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        ctx = {"records": records, "spans": spans, "windows": windows,
               "before": before, "after": after, "compiles": compiles,
               "device": summary, "seconds": seconds, "t_start": t_start,
               "t_end": t_end, "config": cfg, "mix": mix,
               "index_build_s": c.index_build_s,
               "peaks": peaks_for(dev0.device_kind) if require_tpu
               else None}
        for m in wanted:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(c.devices), "memory_peak_bytes": int(max(mem))}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]

    # ---- correctness, after the program's state is freed ---------------
    x = c.x
    c.close()
    del c
    gc.collect()
    eng = cfg["engine"]
    ref = Reference(x, n_subsets=eng["n_subsets"],
                    subset_dim=eng["subset_dim"], subset_seed=eng["seed"])
    t0 = time.perf_counter()
    pick = sample(records, int(mix["check_sample"]), seed)
    got = [(r["ids"], r["scores"]) if r.get("ok") else None
           for r in (records[i] for i in pick)]
    want = [ref.answer(records[i]["body"]) for i in pick]
    checks = compare(got, want)
    log(f"bench: reference over {len(pick)} sampled answers in "
        f"{time.perf_counter() - t0:.3f} s")
    limits = {k: 0 for k in checks}
    out = {"correct": all(checks[k] <= limits[k] for k in checks),
           "attempted": len(records), "failed": failed,
           "metrics": metrics, "device": device}
    if summary is not None:
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    out["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                     for k in checks}
    return out


def sample(records, n: int, seed: int):
    """Indices of the window's requests to check: ``n`` drawn from the
    seed, always with the largest label set among them."""
    if not records:
        return []
    rng = stream(seed, SAMPLE)
    size = [len(r["body"]["pos_ids"]) + len(r["body"]["neg_ids"])
            for r in records]
    big = int(np.argmax(size))
    rest = [i for i in range(len(records)) if i != big]
    k = min(max(n - 1, 0), len(rest))
    return [big] + sorted(int(i) for i in rng.choice(rest, k,
                                                     replace=False))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        enable_compile_cache()
        bench = load_benchmark()
        cell, _, cfg, mix = resolve(bench, args.workload)
        out = run(bench, cell, cfg, mix, args.seed, args.seconds,
                  bool(args.trace), log=log)
    except NoChip as e:
        log(f"bench: no accelerator for this cell: {e}")
        return 2
    for k, v in out["checks"].items():
        log(f"check {k} {v['value']} limit {v['limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
