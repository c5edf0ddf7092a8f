"""Per-query tracing (ISSUE 10 / DESIGN.md §17): propagation through
every serving layer, under faults, over a real socket.

Contracts pinned here:
  * every HTTP query's trace is one tree: exactly one ``request`` root,
    every other span's parent in the same trace, children inside their
    parents; the root's children — wire, admission, queue, fit,
    device rounds, rank, cache — cover >=90% of the root's wall, so the
    trace accounts for where the time went instead of sampling it;
  * ``window_wait`` lies inside its request's ``queue`` span; each
    ``device_round`` carries ``dispatch`` and ``sync`` children and the
    last one the window's counters; an executable built under an
    attached trace leaves a ``compile`` span with its ``fun_name``;
  * fault-injected retries leave per-attempt evidence: a retry marker
    plus a second fit/device-round group, so a slow query's trace shows
    WHICH attempt burned the budget;
  * overflow-retry rounds (cold capacity hints) appear as extra
    device_round spans;
  * a deadline-expired request still finishes its trace with the typed
    status — rejected work is visible work;
  * trace ids are unique across concurrent submits, a caller-supplied
    ``X-Request-Id`` becomes the trace id end-to-end, and ``/metrics``
    + ``/traces`` expose the whole thing over the wire;
  * traces slower than the threshold land in the slow-query log as
    parseable JSON lines.
"""
import contextlib
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.core.engine import SearchEngine
from repro.core.errors import deadline_after
from repro.obs import Observability
from repro.obs.trace import Trace
from repro.serve.cache import ResultCache
from repro.serve.engine import QueryRequest, QueryServer
from repro.serve.faults import FaultInjector, FaultSpec
from repro.serve.http import HttpFrontEnd
from repro.serve.policy import RetryPolicy

ENG = dict(n_subsets=4, subset_dim=4, block=64)


def _data(n=500, d=16, seed=0):
    return np.random.default_rng(seed).normal(
        0, 1, (n, d)).astype(np.float32)


def _labels():
    return list(range(10)), list(range(100, 150))


@contextlib.contextmanager
def _serving(srv):
    srv.start()
    fe = HttpFrontEnd(srv)
    host, port = fe.start()
    try:
        yield f"http://{host}:{port}"
    finally:
        fe.close()
        srv.close(drain=False)


def _post(base, path, body, headers=None, timeout=120):
    req = urllib.request.Request(
        base + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _get(base, path, timeout=30):
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        return r.status, r.headers.get("Content-Type", ""), r.read()


def _span_names(trace_dict):
    return [s["name"] for s in trace_dict["spans"]]


# ----------------------------------------------------------------------
# Trace primitives
# ----------------------------------------------------------------------

def test_trace_span_and_mark_arithmetic():
    tr = Trace("t1")
    tr.open("a")
    time.sleep(0.01)
    tr.close("a")
    tr.open("queue", annotated=False)        # closed on another thread
    assert tr.pending("queue") is not None
    time.sleep(0.01)
    closer = threading.Thread(target=tr.close, args=("queue",))
    closer.start()
    closer.join()
    tr.close("queue")                        # consumed: no-op
    assert tr.pending("queue") is None
    tr.finish("ok")
    tr.finish("late")                        # idempotent: first wins
    d = tr.to_dict()
    assert d["status"] == "ok"
    assert _span_names(d) == ["a", "queue", "request"]
    assert all(s["dur_s"] >= 0.009 for s in d["spans"])
    assert tr.wall_s >= 0.02
    root = _root(d)
    assert root["dur_s"] == pytest.approx(tr.wall_s)
    assert [s["parent"] for s in d["spans"][:2]] == [root["id"]] * 2


# ----------------------------------------------------------------------
# the end-to-end acceptance trace (real socket)
# ----------------------------------------------------------------------

def _root(trace_dict):
    roots = [s for s in trace_dict["spans"] if s["parent"] is None]
    assert len(roots) == 1, roots
    assert roots[0]["name"] == "request"
    return roots[0]


def _union_s(spans):
    total, edge = 0.0, None
    for s, e in sorted((sp["t0"], sp["t0"] + sp["dur_s"]) for sp in spans):
        if edge is None or s > edge:
            total += e - s
            edge = e
        elif e > edge:
            total += e - edge
            edge = e
    return total


def _assert_one_tree(trace_dict, slack_s=2e-4):
    """One root; every other span's parent in the trace; each child's
    interval inside its parent's (``slack_s`` for clock reads)."""
    root = _root(trace_dict)
    by_id = {s["id"]: s for s in trace_dict["spans"]}
    assert len(by_id) == len(trace_dict["spans"]), "span ids repeat"
    for s in trace_dict["spans"]:
        if s is root:
            continue
        assert s["parent"] in by_id, (s["name"], s["parent"])
        p = by_id[s["parent"]]
        assert s["t0"] >= p["t0"] - slack_s, (s["name"], p["name"])
        assert s["t0"] + s["dur_s"] <= p["t0"] + p["dur_s"] + slack_s, \
            (s["name"], p["name"])
    assert trace_dict["wall_s"] == pytest.approx(root["dur_s"])
    return root, by_id


def test_http_trace_covers_90_percent_of_wall():
    eng = SearchEngine(_data(), **ENG, live=True)
    srv = QueryServer(eng, max_results=20, cache=ResultCache())
    pos, neg = _labels()
    with _serving(srv) as base:
        st, body, _ = _post(base, "/query",
                            {"pos_ids": pos, "neg_ids": neg})
        assert st == 200 and body["ok"]
        tid = body["trace_id"]
    tr = srv.obs.traces.get(tid)
    assert tr is not None and tr["status"] == "ok"
    names = _span_names(tr)
    for required in ("admission", "queue", "fit", "device_round",
                     "rank", "cache", "http_read", "handoff",
                     "http_encode"):
        assert required in names, (required, names)
    root, _ = _assert_one_tree(tr)
    # the root's own children, overlaps counted once: nested spans
    # (dispatch under device_round) must not count twice
    kids = [s for s in tr["spans"] if s["parent"] == root["id"]]
    covered = _union_s(kids)
    assert covered >= 0.90 * root["dur_s"], \
        f"children cover {covered / root['dur_s']:.1%} of the root " \
        f"({sorted({s['name'] for s in kids})})"


def test_every_http_request_is_one_span_tree():
    eng = SearchEngine(_data(), **ENG, live=True)
    srv = QueryServer(eng, max_results=20, cache=ResultCache(),
                      batch_window_s=0.01)
    pos, neg = _labels()
    with _serving(srv) as base:
        bodies = [_post(base, "/query", {"pos_ids": pos[:k],
                                         "neg_ids": neg})[1]
                  for k in (4, 5, 4)]          # the last is a cache hit
        st, bad, _ = _post(base, "/query", {"pos_ids": pos,
                                            "bogus": 1})
        assert st == 400
    assert bodies[2]["cache"] == "hit"
    traces = [srv.obs.traces.get(b["trace_id"]) for b in bodies]
    for tr in traces:
        root, by_id = _assert_one_tree(tr)
        kids = {s["name"] for s in tr["spans"]
                if s["parent"] == root["id"]}
        assert {"http_read", "queue", "handoff", "http_encode"} <= kids
    # device rounds carry their two phases, the last one the counters
    _, by_id = _assert_one_tree(traces[0])
    rounds = [s for s in traces[0]["spans"] if s["name"] == "device_round"]
    for r in rounds:
        phases = sorted(s["name"] for s in traces[0]["spans"]
                        if s["parent"] == r["id"]
                        and s["name"] != "compile")
        assert phases == ["dispatch", "sync"], phases
    last = rounds[-1]["attrs"]
    assert last["rounds"] == len(rounds)
    assert last["n_host_syncs"] >= 1 and "blocks_touched" in last
    assert "retried_subsets" in last
    assert "accumulate_rows" in last and "accumulate_share" in last
    # a request refused at parse time still leaves its (short) tree
    refused = [t for t in srv.obs.traces.recent(50)
               if t["status"] == "bad_request"]
    assert len(refused) == 1
    _assert_one_tree(refused[0])
    assert _span_names(refused[0]) == ["http_read", "request"]


def test_window_wait_lies_inside_queue():
    eng = SearchEngine(_data(), **ENG, live=True)
    srv = QueryServer(eng, max_results=20, max_batch=4,
                      batch_window_s=0.05)
    srv.start()
    pos, neg = _labels()
    try:
        outs = [srv.submit(QueryRequest(i, pos[:4 + i % 3], neg,
                                        "dbranch")) for i in range(6)]
        resps = [o.get(timeout=120) for o in outs]
    finally:
        srv.close()
    assert all(r.ok for r in resps)
    for r in resps:
        tr = srv.obs.traces.get(r.info["trace_id"])
        _, by_id = _assert_one_tree(tr)
        (ww,) = [s for s in tr["spans"] if s["name"] == "window_wait"]
        q = by_id[ww["parent"]]
        assert q["name"] == "queue"
        assert q["t0"] <= ww["t0"]
        assert ww["t0"] + ww["dur_s"] <= q["t0"] + q["dur_s"]
        # nobody else arrives in most windows: the wait is the window
        assert ww["dur_s"] <= srv.batch_window_s + 0.05


def test_compile_span_names_the_executable_and_its_parent():
    import jax
    import jax.numpy as jnp

    import repro.core.engine  # noqa: F401 — installs the obs hooks
    from repro.obs import trace as obs_trace

    def compile_probe_tripled(x):
        return x * 3.0 + 1.0

    tr = Trace("compiles")
    with obs_trace.attach([tr]):
        with obs_trace.span("dispatch"):
            jax.jit(compile_probe_tripled)(
                jnp.ones((7, 13))).block_until_ready()
    tr.finish("ok")
    d = tr.to_dict()
    comp = [s for s in d["spans"] if s["name"] == "compile"
            and "compile_probe_tripled" in s["attrs"]["fun_name"]]
    assert len(comp) == 1, d["spans"]
    (disp,) = [s for s in d["spans"] if s["name"] == "dispatch"]
    assert comp[0]["parent"] == disp["id"]
    assert 0.0 < comp[0]["dur_s"] <= disp["dur_s"]
    # unattached threads record nothing
    jax.jit(compile_probe_tripled)(jnp.ones((3, 5))).block_until_ready()
    assert [s["name"] for s in tr.to_dict()["spans"]].count("compile") \
        == len([s for s in d["spans"] if s["name"] == "compile"])


def test_span_ids_and_parents_hold_under_thread_contention():
    """Threads that each attach their own trace and nest spans, under a
    short switch interval: ids never repeat across traces and every
    span's parent is the span that enclosed it on its own thread."""
    import sys

    from repro.obs import trace as obs_trace

    traces = [Trace(f"t{i}") for i in range(8)]

    def work(tr):
        with obs_trace.attach([tr]):
            for _ in range(200):
                with obs_trace.span("outer"):
                    with obs_trace.span("inner"):
                        pass

    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(tr,))
                   for tr in traces]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(prev)
    ids = []
    for tr in traces:
        tr.finish("ok")
        d = tr.to_dict()
        root, by_id = _assert_one_tree(d, slack_s=0.0)
        for sp in d["spans"]:
            if sp["name"] == "inner":
                assert by_id[sp["parent"]]["name"] == "outer"
            elif sp["name"] == "outer":
                assert sp["parent"] == root["id"]
        ids += list(by_id)
    assert len(ids) == len(set(ids)) == 8 * (2 * 200 + 1)


def test_held_trace_is_archived_once_when_the_front_end_finishes_first():
    """The front end gives up on a request it created the trace for (a
    resolve timeout, a 500) while the server still holds the request:
    the trace is archived once, and the server's later finish leaves the
    ring and the histograms as they are."""
    eng = SearchEngine(_data(), **ENG, live=True)
    srv = QueryServer(eng, max_results=20)
    pos, neg = _labels()
    tr = srv.obs.new_trace(held=True)
    try:
        srv.obs.observe_trace(tr, "internal")          # front end first
        resp = srv.handle(QueryRequest(1, pos, neg, "dbranch", trace=tr))
        assert resp.ok and resp.info["trace_id"] == tr.trace_id
        srv.obs.observe_trace(tr, "ok")                # a second finish
    finally:
        srv.close()
    assert len(srv.obs.traces) == 1
    assert srv.obs.traces.get(tr.trace_id)["status"] == "internal"
    assert srv.obs.request_seconds.labels(status="internal").count == 1
    assert srv.obs.request_seconds.labels(status="ok").count == 0
    assert srv.obs.span_seconds.labels(name="request").count == 1


def test_untraced_http_query_answers_without_a_trace():
    eng = SearchEngine(_data(), **ENG, live=True)
    srv = QueryServer(eng, max_results=20,
                      obs=Observability(metrics_enabled=False,
                                        tracing_enabled=False))
    pos, neg = _labels()
    with _serving(srv) as base:
        st, body, hdrs = _post(base, "/query",
                               {"pos_ids": pos, "neg_ids": neg})
    assert st == 200 and body["ok"] and "trace_id" not in body
    assert "X-Request-Id" not in hdrs
    assert len(srv.obs.traces) == 0


def test_cache_hit_trace_has_cache_span_and_fresh_id():
    eng = SearchEngine(_data(), **ENG, live=True)
    srv = QueryServer(eng, max_results=20, cache=ResultCache())
    pos, neg = _labels()
    q = {"pos_ids": pos, "neg_ids": neg}
    with _serving(srv) as base:
        _, b1, _ = _post(base, "/query", q)
        _, b2, _ = _post(base, "/query", q)
        assert b2["cache"] == "hit"
        assert b2["trace_id"] != b1["trace_id"]
    tr = srv.obs.traces.get(b2["trace_id"])
    names = _span_names(tr)
    assert "cache" in names
    # a hit never touches the device
    assert "device_round" not in names and "fit" not in names


# ----------------------------------------------------------------------
# traces under fault injection (satellite c)
# ----------------------------------------------------------------------

def test_retry_attempts_visible_in_trace():
    inj = FaultInjector(specs=[FaultSpec("fused_query", at_calls=(1,))])
    eng = SearchEngine(_data(), **ENG, live=True, faults=inj)
    srv = QueryServer(eng, max_results=20,
                      retry_policy=RetryPolicy(max_attempts=3,
                                               backoff_s=0.001))
    srv.start()
    try:
        pos, neg = _labels()
        req = QueryRequest(1, pos, neg, "dbranch")
        resp = srv.submit(req).get(timeout=120)
        assert resp.ok
        assert srv.stats["retries"] == 1
        tr = srv.obs.traces.get(resp.info["trace_id"])
        names = _span_names(tr)
        assert names.count("retry") == 1
        # both attempts fitted and reached the device: the failed
        # attempt's spans survive next to the successful one's
        assert names.count("fit") == 2
        assert names.count("device_round") >= 2
        assert names.index("retry") > names.index("fit")
    finally:
        srv.close()


def test_overflow_retry_rounds_leave_extra_device_round_spans():
    # capacity_frac ~0 forces the cold gather capacity to 1 row per
    # subset: the first round overflows and the engine re-queues at
    # observed size — the trace must show the extra round(s)
    eng_tiny = SearchEngine(_data(), **ENG, live=True,
                            capacity_frac=1e-6)
    srv = QueryServer(eng_tiny, max_results=20)
    srv.start()
    try:
        pos, neg = _labels()
        resp = srv.submit(QueryRequest(1, pos, neg,
                                       "dbranch")).get(timeout=120)
        assert resp.ok
        tr = srv.obs.traces.get(resp.info["trace_id"])
        rounds = [s for s in tr["spans"] if s["name"] == "device_round"]
        assert len(rounds) >= 2, _span_names(tr)
    finally:
        srv.close()


def test_deadline_expired_request_still_finishes_its_trace():
    eng = SearchEngine(_data(), **ENG, live=True)
    srv = QueryServer(eng, max_results=20)
    srv.start()
    try:
        pos, neg = _labels()
        req = QueryRequest(1, pos, neg, "dbranch",
                           deadline_s=deadline_after(-1.0))
        resp = srv.submit(req).get(timeout=30)
        assert not resp.ok and resp.error_type == "deadline_exceeded"
        tr = srv.obs.traces.get(resp.info["trace_id"])
        assert tr is not None
        assert tr["status"] == "deadline_exceeded"
    finally:
        srv.close()


def test_trace_ids_unique_across_concurrent_submits():
    eng = SearchEngine(_data(), **ENG, live=True)
    srv = QueryServer(eng, max_results=20, queue_depth=256,
                      cache=ResultCache())
    srv.start()
    ids, lock = [], threading.Lock()
    pos, neg = _labels()

    def one(i):
        resp = srv.submit(QueryRequest(i, pos, neg,
                                       "dbranch")).get(timeout=120)
        with lock:
            ids.append(resp.info.get("trace_id"))

    try:
        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(100)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert len(ids) == 100
        assert None not in ids
        assert len(set(ids)) == 100
    finally:
        srv.close()


# ----------------------------------------------------------------------
# wire surface: /metrics, /traces, X-Request-Id
# ----------------------------------------------------------------------

def test_metrics_endpoint_is_prometheus_text():
    eng = SearchEngine(_data(), **ENG, live=True)
    srv = QueryServer(eng, max_results=20, cache=ResultCache())
    pos, neg = _labels()
    with _serving(srv) as base:
        _post(base, "/query", {"pos_ids": pos, "neg_ids": neg})
        st, ctype, raw = _get(base, "/metrics")
        assert st == 200
        assert ctype.startswith("text/plain")
        assert "version=0.0.4" in ctype
        text = raw.decode()
    from test_obs import _assert_valid_exposition
    _assert_valid_exposition(text)
    for family in ("server_latency_seconds_bucket", "span_seconds_sum",
                   "request_seconds_count", "cache_hits_total",
                   "server_served"):
        assert family in text, family


def test_traces_endpoint_and_x_request_id_honored():
    eng = SearchEngine(_data(), **ENG, live=True)
    srv = QueryServer(eng, max_results=20)
    pos, neg = _labels()
    with _serving(srv) as base:
        st, body, hdrs = _post(base, "/query",
                               {"pos_ids": pos, "neg_ids": neg},
                               headers={"X-Request-Id": "corr-77"})
        assert st == 200
        assert body["trace_id"] == "corr-77"
        assert hdrs.get("X-Request-Id") == "corr-77"
        st2, ctype2, raw2 = _get(base, "/traces?n=10")
        assert st2 == 200 and ctype2.startswith("application/json")
        payload = json.loads(raw2)
    ids = [t["trace_id"] for t in payload["traces"]]
    assert "corr-77" in ids
    tr = [t for t in payload["traces"] if t["trace_id"] == "corr-77"][0]
    assert "device_round" in _span_names(tr)


def test_slow_query_log_lines_parse(tmp_path):
    log = tmp_path / "slow.jsonl"
    obs = Observability(slow_query_s=0.0, slow_log_path=str(log))
    eng = SearchEngine(_data(), **ENG, live=True)
    srv = QueryServer(eng, max_results=20, obs=obs)
    srv.start()
    try:
        pos, neg = _labels()
        resp = srv.submit(QueryRequest(1, pos, neg,
                                       "dbranch")).get(timeout=120)
        assert resp.ok
    finally:
        srv.close()
    lines = [json.loads(ln) for ln in
             log.read_text().strip().splitlines()]
    assert lines, "no slow-query lines written"
    entry = lines[0]
    assert entry["slow_query"] is True
    assert entry["trace_id"] == resp.info["trace_id"]
    assert entry["status"] == "ok"
    assert entry["wall_ms"] > 0
    assert "fit" in entry["spans"] and "device_round" in entry["spans"]
    assert obs.traces.slow_log(5)   # in-memory mirror carries it too


def test_slow_query_log_says_why():
    obs = Observability(slow_query_s=0.0)
    # cold capacity hints: the first round overflows, a retry round runs
    eng = SearchEngine(_data(seed=3), **ENG, live=True, capacity_frac=1e-6)
    srv = QueryServer(eng, max_results=20, obs=obs)
    srv.start()
    try:
        pos, neg = _labels()
        resp = srv.submit(QueryRequest(1, pos, neg,
                                       "dbranch")).get(timeout=120)
        assert resp.ok
    finally:
        srv.close()
    entry = json.loads(obs.traces.slow_log(1)[0])
    rounds = entry["rounds"]
    assert rounds["rounds"] >= 2 and rounds["retried_subsets"] >= 1
    assert rounds["n_host_syncs"] == rounds["rounds"]
    assert entry["spans"]["request"] == entry["wall_ms"]
