"""Serving robustness (ISSUE 7 / DESIGN.md §14): deadlines, admission
control, retry/backoff, lifecycle.

Contracts pinned here:
  * every submitted request resolves EXACTLY once — shed, expired,
    drained or served — so ``submit(...).get()`` never blocks forever;
  * ``close(drain=True)`` answers every queued request, ``drain=False``
    resolves the backlog with typed shutdown errors, and ``submit``
    after close raises ``ServerClosed``;
  * a failed background compaction leaves the old snapshot serving
    bitwise untouched, surfaces in stats/summary, and resets capacity
    hints; a transient failure retries with backoff and succeeds;
  * deadlines are absolute and checked at admission, window formation,
    before the fit and between device rounds — typed, never silent;
  * the policy pieces (RetryPolicy, TokenBucket, AdmissionQueue) behave
    deterministically in isolation.

Every blocking ``get`` in this file carries a timeout: a hang here is a
deadlock bug, and the bounded waits convert it into a visible failure
instead of a wedged suite.
"""
import queue
import threading
import time

import numpy as np
import pytest

from repro.core.engine import SearchEngine
from repro.core.errors import (DeadlineExceeded, TransientDeviceError,
                               deadline_after)
from repro.serve.engine import IngestRequest, QueryRequest, QueryServer
from repro.serve.faults import FaultInjector, FaultSpec
from repro.serve.policy import (AdmissionQueue, Overloaded, RateLimited,
                                RetryPolicy, ServerClosed, TokenBucket)

ENG = dict(n_subsets=4, subset_dim=4, block=64)
GET_S = 120            # generous bound: first query pays jit compile


def _data(n=500, d=16, seed=0):
    return np.random.default_rng(seed).normal(
        0, 1, (n, d)).astype(np.float32)


def _labels():
    return list(range(10)), list(range(100, 150))


@pytest.fixture(scope="module")
def base_x():
    return _data()


# ----------------------------------------------------------------------
# policy units
# ----------------------------------------------------------------------

def test_retry_policy_retries_transient_then_succeeds():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientDeviceError("flake")
        return "ok"

    naps = []
    pol = RetryPolicy(max_attempts=5, backoff_s=0.01, seed=7)
    assert pol.call(flaky, sleep=naps.append) == "ok"
    assert calls["n"] == 3 and len(naps) == 2
    assert naps[1] > naps[0] > 0          # exponential, jittered


def test_retry_policy_gives_up_and_classifies():
    pol = RetryPolicy(max_attempts=2, backoff_s=0.0)
    calls = {"n": 0}

    def always():
        calls["n"] += 1
        raise TransientDeviceError("flake")
    with pytest.raises(TransientDeviceError):
        pol.call(always, sleep=lambda s: None)
    assert calls["n"] == 2
    # non-retryable types fail on the FIRST attempt
    calls["n"] = 0

    def bad():
        calls["n"] += 1
        raise ValueError("bug")
    with pytest.raises(ValueError):
        pol.call(bad, sleep=lambda s: None)
    assert calls["n"] == 1
    # DeadlineExceeded is never retryable, whatever ``retryable`` says
    assert not pol.classify(DeadlineExceeded("late"))


def test_retry_policy_respects_deadline_budget():
    pol = RetryPolicy(max_attempts=10, backoff_s=10.0)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        raise TransientDeviceError("flake")
    # remaining budget (~50 ms) < backoff (10 s): no retry, fail fast
    with pytest.raises(TransientDeviceError):
        pol.call(flaky, deadline_s=deadline_after(0.05),
                 sleep=lambda s: None)
    assert calls["n"] == 1


def test_retry_backoff_is_seeded_deterministic():
    a = RetryPolicy(max_attempts=4, backoff_s=0.01, seed=3)
    b = RetryPolicy(max_attempts=4, backoff_s=0.01, seed=3)
    assert [a.delay_s(i) for i in (1, 2, 3)] == \
        [b.delay_s(i) for i in (1, 2, 3)]


def test_token_bucket_burst_and_refill():
    t = {"now": 0.0}
    tb = TokenBucket(rate=10.0, burst=2.0, clock=lambda: t["now"])
    assert tb.try_acquire() and tb.try_acquire()
    assert not tb.try_acquire()           # burst exhausted
    t["now"] += 0.1                       # 1 token refilled
    assert tb.try_acquire()
    assert not tb.try_acquire()
    t["now"] += 10.0                      # refill caps at burst
    assert tb.tokens == pytest.approx(2.0)


def test_admission_queue_reject_newest():
    q = AdmissionQueue(depth=2)
    assert q.offer("a")[0] and q.offer("b")[0]
    admitted, evicted = q.offer("c")
    assert not admitted and evicted is None
    assert len(q) == 2 and q.depth_peak == 2
    assert q.pop(0.01) == "a"             # FIFO preserved


def test_admission_queue_reject_largest_fit():
    q = AdmissionQueue(depth=2, shed_policy="reject-largest-fit")
    q.offer("small", cost=5)
    q.offer("big", cost=50)
    admitted, evicted = q.offer("tiny", cost=1)
    assert admitted and evicted == "big"  # largest fit shed
    # a newcomer at least as costly as every queued entry is refused
    admitted, evicted = q.offer("huge", cost=100)
    assert not admitted and evicted is None
    assert q.drain() == ["small", "tiny"]
    assert len(q) == 0


def test_fault_injector_deterministic_schedule():
    specs = (FaultSpec("append", at_calls=(2,)),
             FaultSpec("compact", prob=0.5, action="slow", delay_s=0.0))

    def schedule(seed):
        inj = FaultInjector(seed=seed, specs=specs)
        fired = []
        for _ in range(20):
            try:
                inj.check("append")
            except TransientDeviceError:
                fired.append(("append", inj.calls("append")))
            inj.check("compact")
        return fired + [(r.site, r.call) for r in inj.fired]

    assert schedule(11) == schedule(11)           # replayable
    assert ("append", 2) in schedule(11)          # at_calls honoured
    assert schedule(11) != schedule(12)           # seed matters


# ----------------------------------------------------------------------
# deadlines
# ----------------------------------------------------------------------

def test_engine_query_deadline_expired_before_fit(base_x):
    eng = SearchEngine(base_x, **ENG)
    pos, neg = _labels()
    with pytest.raises(DeadlineExceeded):
        eng.query(pos, neg, model="dbranch",
                  deadline_s=time.monotonic() - 0.01)


def test_submit_rejects_expired_deadline(base_x):
    eng = SearchEngine(base_x, **ENG)
    srv = QueryServer(eng)                # not started: admission only
    pos, neg = _labels()
    resp = srv.submit(QueryRequest(0, pos, neg,
                                   deadline_s=time.monotonic() - 1)
                      ).get(timeout=5)
    assert not resp.ok and resp.error_type == "deadline_exceeded"
    assert srv.stats["rejected_deadline"] == 1


def test_deadline_expires_while_queued(base_x):
    """Window-formation checkpoint: budget burned in the queue yields a
    typed response, and the server keeps serving live requests."""
    eng = SearchEngine(base_x, **ENG)
    srv = QueryServer(eng)
    pos, neg = _labels()
    dead = srv.submit(QueryRequest(0, pos, neg,
                                   deadline_s=deadline_after(0.03)))
    live = srv.submit(QueryRequest(1, pos, neg))
    time.sleep(0.1)                       # burn request 0's budget queued
    srv.start()
    r0 = dead.get(timeout=GET_S)
    r1 = live.get(timeout=GET_S)
    srv.close()
    assert not r0.ok and r0.error_type == "deadline_exceeded"
    assert "queued" in r0.error
    assert r1.ok
    assert srv.stats["expired_in_queue"] == 1


def test_default_deadline_stamped_at_admission(base_x):
    eng = SearchEngine(base_x, **ENG)
    srv = QueryServer(eng, default_deadline_s=30.0)
    pos, neg = _labels()
    req = QueryRequest(0, pos, neg)
    srv.submit(req)
    assert req.deadline_s is not None
    assert req.deadline_s - time.monotonic() == pytest.approx(30.0, abs=1.0)
    srv.close(drain=False)


# ----------------------------------------------------------------------
# admission control / backpressure
# ----------------------------------------------------------------------

def test_queue_full_typed_rejection(base_x):
    eng = SearchEngine(base_x, **ENG)
    srv = QueryServer(eng, queue_depth=2)  # not started: queue fills
    pos, neg = _labels()
    outs = [srv.submit(QueryRequest(i, pos, neg)) for i in range(4)]
    r2 = outs[2].get(timeout=5)
    r3 = outs[3].get(timeout=5)
    assert not r2.ok and r2.error_type == "overloaded"
    assert not r3.ok and r3.error_type == "overloaded"
    assert srv.stats["rejected_overloaded"] == 2
    assert srv.stats["admitted"] == 2
    srv.close(drain=False)                # resolves the 2 queued
    for o in outs[:2]:
        assert o.get(timeout=5).error_type == "shutdown"


def test_shed_policy_evicts_largest_fit(base_x):
    eng = SearchEngine(base_x, **ENG)
    srv = QueryServer(eng, queue_depth=2,
                      shed_policy="reject-largest-fit")
    big = QueryRequest(0, list(range(40)), list(range(100, 200)))
    small = QueryRequest(1, [0, 1], [100, 101])
    tiny = QueryRequest(2, [0], [100])
    out_big = srv.submit(big)
    srv.submit(small)
    out_tiny = srv.submit(tiny)
    # the expensive fit was shed to admit the cheap newcomer
    r = out_big.get(timeout=5)
    assert not r.ok and r.error_type == "overloaded"
    assert "largest-fit" in r.error
    assert srv.stats["evicted"] == 1
    assert out_tiny.empty()               # tiny is queued, not rejected
    srv.close(drain=False)


def test_rate_limit_per_source(base_x):
    eng = SearchEngine(base_x, **ENG)
    srv = QueryServer(eng, rate_limit=(0.001, 2))   # ~no refill in-test
    pos, neg = _labels()
    rs = [srv.submit(QueryRequest(i, pos, neg, source="alice")).empty()
          for i in range(3)]
    assert rs == [True, True, False]      # third resolved = rejected
    # a different source has its own bucket
    assert srv.submit(QueryRequest(9, pos, neg, source="bob")).empty()
    assert srv.stats["rejected_rate_limited"] == 1
    srv.close(drain=False)


def test_degraded_mode_clamps_max_results(base_x):
    eng = SearchEngine(base_x, **ENG)
    srv = QueryServer(eng, max_results=50, queue_depth=4,
                      degraded_max_results=5, soft_depth_frac=0.5)
    req = QueryRequest(0, *_labels())
    assert srv._query_kwargs(req)["max_results"] == 50
    srv._degraded = True                  # what _update_health sets
    assert srv._query_kwargs(req)["max_results"] == 5
    # a request's own kwargs clamp too (never widened)
    req2 = QueryRequest(1, *_labels(), kwargs={"max_results": 3})
    assert srv._query_kwargs(req2)["max_results"] == 3


def test_degraded_windows_under_backlog(base_x):
    """End-to-end: a backlog above the soft watermark serves clamped
    windows and reports a degraded health state while it lasts."""
    eng = SearchEngine(base_x, **ENG)
    srv = QueryServer(eng, max_results=50, queue_depth=8,
                      degraded_max_results=4, soft_depth_frac=0.25,
                      max_batch=2)
    pos, neg = _labels()
    outs = [srv.submit(QueryRequest(i, pos, neg)) for i in range(6)]
    assert srv.health == "ok"             # degraded is a WINDOW property
    srv.start()
    resps = [o.get(timeout=GET_S) for o in outs]
    srv.close()
    assert all(r.ok for r in resps)
    assert srv.stats["degraded_windows"] >= 1
    # at least the first window (formed with 5 queued behind it) clamped
    assert min(len(r.result.ids) for r in resps) <= 4


# ----------------------------------------------------------------------
# lifecycle
# ----------------------------------------------------------------------

def test_close_resolves_queued_requests_with_typed_errors(base_x):
    eng = SearchEngine(base_x, **ENG)
    srv = QueryServer(eng)                # never started
    pos, neg = _labels()
    outs = [srv.submit(QueryRequest(i, pos, neg)) for i in range(3)]
    outs.append(srv.submit(IngestRequest(3, "append",
                                         features=_data(4))))
    srv.close(drain=False)
    for o in outs:
        r = o.get(timeout=5)              # never blocks forever
        assert not r.ok and r.error_type == "shutdown"
    assert srv.stats["shutdown_unserved"] == 4
    assert srv.summary()["health"] == "draining"


def test_submit_after_close_raises(base_x):
    eng = SearchEngine(base_x, **ENG)
    srv = QueryServer(eng)
    srv.start()
    srv.close()
    with pytest.raises(ServerClosed):
        srv.submit(QueryRequest(0, *_labels()))


def test_close_drain_answers_everything(base_x):
    eng = SearchEngine(base_x, **ENG)
    srv = QueryServer(eng, max_batch=2)
    pos, neg = _labels()
    outs = [srv.submit(QueryRequest(i, pos, neg)) for i in range(5)]
    srv.start()                           # backlog present at start
    srv.close(drain=True)                 # returns once all answered
    resps = [o.get(timeout=GET_S) for o in outs]
    assert all(r.ok for r in resps)
    assert srv.stats["served"] == 5
    assert srv.stats["shutdown_unserved"] == 0


def test_close_is_idempotent(base_x):
    eng = SearchEngine(base_x, **ENG)
    srv = QueryServer(eng)
    srv.start()
    srv.close()
    srv.close()                           # second close is a no-op
    srv.close(drain=False)


# ----------------------------------------------------------------------
# compaction robustness
# ----------------------------------------------------------------------

def _live_server(x, faults=None, **kw):
    eng = SearchEngine(x, **ENG, live=True, faults=faults)
    return eng, QueryServer(eng, **kw)


def test_compaction_failure_keeps_old_snapshot(base_x):
    inj = FaultInjector(specs=[FaultSpec("compact", at_calls=(1, 2, 3))])
    eng, srv = _live_server(
        base_x, faults=inj,
        compaction_retry=RetryPolicy(max_attempts=3, backoff_s=0.001))
    eng.append(_data(40, seed=5))         # >1 segment: compactable
    pos, neg = _labels()
    before = eng.query(pos, neg, model="dbranch", max_results=20)
    epoch0 = eng._catalog.epoch
    assert len(eng._cap_hints) > 0        # hints learned pre-failure
    rc = srv.handle_ingest(IngestRequest(0, "compact"))
    assert rc.ok and rc.info["background"]
    srv._compact_thread.join(timeout=30)
    assert not srv._compact_thread.is_alive()
    # every attempt failed BEFORE the merge: snapshot + epoch untouched
    assert eng._catalog.epoch == epoch0
    assert srv.stats["compaction_errors"] == 1
    assert srv.stats["compaction_retries"] == 2
    assert "injected fault" in srv.summary()["last_compaction_error"]
    assert srv.summary()["health"] == "degraded"
    # conservative reset: hints observed around the failure are void
    assert len(eng._cap_hints) == 0
    # serving continues, bitwise on the old snapshot
    after = eng.query(pos, neg, model="dbranch", max_results=20)
    np.testing.assert_array_equal(before.ids, after.ids)
    np.testing.assert_array_equal(before.scores, after.scores)
    srv.close()


def test_compaction_transient_failure_retries_to_success(base_x):
    inj = FaultInjector(specs=[FaultSpec("compact", at_calls=(1,))])
    eng, srv = _live_server(
        base_x, faults=inj,
        compaction_retry=RetryPolicy(max_attempts=3, backoff_s=0.001))
    eng.append(_data(40, seed=5))
    epoch0 = eng._catalog.epoch
    rc = srv.handle_ingest(IngestRequest(0, "compact"))
    assert rc.ok
    srv._compact_thread.join(timeout=30)
    assert srv.stats["compaction_retries"] == 1
    assert srv.stats["compaction_errors"] == 0
    assert eng._catalog.epoch == epoch0 + 1       # swap happened
    assert len(eng._catalog.snapshot().segments) == 1
    srv.close()


def test_concurrent_compact_requests_coalesce(base_x):
    inj = FaultInjector(specs=[FaultSpec("compact", action="slow",
                                         at_calls=(1,), delay_s=0.3)])
    eng, srv = _live_server(base_x, faults=inj)
    eng.append(_data(40, seed=5))
    r1 = srv.handle_ingest(IngestRequest(0, "compact"))
    r2 = srv.handle_ingest(IngestRequest(1, "compact"))
    assert r1.ok and r2.ok
    assert r2.info.get("coalesced")       # no second worker thread
    srv._compact_thread.join(timeout=30)
    assert srv.stats["compactions"] == 2
    assert inj.calls("compact") == 1      # ONE merge ran
    srv.close()


# ----------------------------------------------------------------------
# query-path retries + batch fallback billing
# ----------------------------------------------------------------------

def test_query_retries_transient_device_fault(base_x):
    inj = FaultInjector(specs=[FaultSpec("device_sync", at_calls=(1,))])
    eng = SearchEngine(base_x, **ENG, faults=inj)
    srv = QueryServer(eng, retry_policy=RetryPolicy(max_attempts=3,
                                                    backoff_s=0.001))
    resp = srv.handle(QueryRequest(0, *_labels()))
    assert resp.ok
    assert srv.stats["retries"] == 1
    # the retry re-ran the whole query: parity with a clean engine
    clean = SearchEngine(base_x, **ENG)
    want = clean.query(*_labels(), model="dbranch")
    np.testing.assert_array_equal(resp.result.ids, want.ids)


def test_batch_fallback_bills_wasted_wall(base_x):
    inj = FaultInjector(specs=[FaultSpec("fused_query", at_calls=(1,))])
    eng = SearchEngine(base_x, **ENG, faults=inj)
    srv = QueryServer(eng)                # no retry: fall back sequential
    pos, neg = _labels()
    reqs = [QueryRequest(i, pos, neg) for i in range(3)]
    sum0 = srv.stats["latency_sum"]
    resps = srv.handle_batch(reqs)
    assert all(r.ok for r in resps)
    assert srv.stats["batch_fallbacks"] == 1
    assert srv.stats["batches"] == 0      # the window never ran batched
    assert srv.stats["served"] == 3
    # the failed attempt's wall is billed to every request in the window
    assert srv.stats["latency_sum"] - sum0 == pytest.approx(
        sum(r.latency_s for r in resps), rel=1e-6)


def test_device_fit_failure_is_counted(base_x):
    """A window whose batched device fit raises is refitted request by
    request on the numpy trainer: answers stay exact, and the fallback
    shows in fit_fallbacks and in each result's fit_path."""
    eng = SearchEngine(base_x, **ENG)

    def refuse(*args, **kwargs):
        raise RuntimeError("device fit refused")
    eng._fit_boxes_batched = refuse
    srv = QueryServer(eng)
    pos, neg = _labels()
    resps = srv.handle_batch([QueryRequest(i, pos, neg, model=m)
                              for i, m in enumerate(("dbranch", "dbens"))])
    assert all(r.ok for r in resps)
    assert [r.result.stats["fit_path"] for r in resps] == ["numpy"] * 2
    summ = srv.summary()
    assert summ["fit_fallbacks"] == 1
    assert summ["batch_fallbacks"] == 0 and summ["errors"] == 0
    clean = SearchEngine(base_x, **ENG)
    for r, m in zip(resps, ("dbranch", "dbens")):
        want = clean.query(pos, neg, model=m)
        np.testing.assert_array_equal(r.result.ids, want.ids)
        np.testing.assert_array_equal(r.result.scores, want.scores)


def test_batch_deadline_exceeded_short_circuits(base_x):
    eng = SearchEngine(base_x, **ENG)
    srv = QueryServer(eng)
    pos, neg = _labels()
    dl = time.monotonic() - 0.01          # already expired
    reqs = [QueryRequest(i, pos, neg, deadline_s=dl) for i in range(2)]
    resps = srv.handle_batch(reqs)
    assert all(not r.ok for r in resps)
    assert all(r.error_type == "deadline_exceeded" for r in resps)
    assert srv.stats["batch_fallbacks"] == 0      # no pointless retry
    assert srv.stats["errors"] == 2


# ----------------------------------------------------------------------
# serving-thread stat races + expired-backlog recursion (ISSUE 9)
# ----------------------------------------------------------------------

class _InstantResult:
    """Microsecond stand-in for QueryResult: the hammer and backlog
    tests exercise the SERVER's bookkeeping, not the device path."""

    def __init__(self):
        self.ids = np.arange(4, dtype=np.int32)
        self.scores = np.ones(4, dtype=np.float32)
        self.train_time_s = 0.0
        self.query_time_s = 0.0
        self.stats = {"host_bytes_transferred": 32}


class _InstantEngine:
    """Duck-typed engine answering immediately on the serving thread."""
    live = True

    def __init__(self):
        self._next = 1000
        self._lock = threading.Lock()

    def query(self, pos, neg, model="dbranch", deadline_s=None, **kw):
        return _InstantResult()

    def query_batch(self, batch, deadline_s=None):
        return [_InstantResult() for _ in batch]

    def append(self, feats):
        with self._lock:
            lo = self._next
            self._next += len(feats)
        return np.arange(lo, lo + len(feats))


def _ledger_holds(stats):
    """DESIGN.md §14: every admitted request lands in exactly one
    terminal bucket. EXACT equality — a race that loses one locked
    increment breaks this."""
    return stats["admitted"] == (stats["served"] + stats["ingests"]
                                 + stats["expired_in_queue"]
                                 + stats["evicted"]
                                 + stats["shutdown_unserved"])


def test_stats_ledger_exact_under_hammer():
    """Many submit threads race the serving thread (and each other)
    across every admission outcome — admitted, overloaded, evicted,
    rate-limit-free expiry, ingests — for 100 server lifetimes. With
    any unlocked ``stats[k] += v`` on these paths the exact ledger
    equality fails within a few iterations."""
    n_threads, per_thread = 6, 20
    for it in range(100):
        srv = QueryServer(_InstantEngine(), max_batch=4,
                          batch_window_s=0.0005, queue_depth=24,
                          shed_policy="reject-largest-fit")
        srv.start()
        outs, outs_lock = [], threading.Lock()

        def worker(tid, srv=srv, outs=outs, outs_lock=outs_lock):
            rng = np.random.default_rng(tid)
            local = []
            for j in range(per_thread):
                rid = tid * 1000 + j
                draw = rng.random()
                if draw < 0.2:
                    req = IngestRequest(
                        rid, "append",
                        features=np.zeros((2, 4), np.float32))
                elif draw < 0.4:   # expires at admission or in queue
                    req = QueryRequest(rid, [0], [1],
                                       deadline_s=deadline_after(0.001))
                else:              # varied cost: exercises eviction
                    n = int(rng.integers(1, 30))
                    req = QueryRequest(rid, list(range(n)), [100])
                local.append(srv.submit(req))
            with outs_lock:
                outs.extend(local)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        srv.close(drain=bool(it % 2))     # alternate both close modes
        resps = [o.get(timeout=10) for o in outs]
        assert len(resps) == n_threads * per_thread   # all resolved
        s = srv.summary()
        assert _ledger_holds(s), f"iteration {it}: ledger drifted: {s}"
        # and every submit landed in exactly one admission bucket
        assert len(resps) == (s["admitted"] + s["rejected_overloaded"]
                              + s["rejected_rate_limited"]
                              + s["rejected_deadline"]
                              + s["submit_faults"])


def test_expired_backlog_resolves_iteratively():
    """5,000 already-expired requests queued ahead of a live one: the
    serving thread must drain them ALL with typed responses in constant
    stack. The old recursive ``_pop_live`` blew the interpreter's
    ~1000-frame recursion limit here, killing the serving thread and
    stranding every later request."""
    srv = QueryServer(_InstantEngine())
    dl = deadline_after(2.0)
    outs = [srv.submit(QueryRequest(i, [0], [1], deadline_s=dl))
            for i in range(5000)]
    while time.monotonic() <= dl:
        time.sleep(0.01)                  # the whole backlog is now dead
    srv.start()
    live = srv.submit(QueryRequest(9999, [0], [1]))
    resps = [o.get(timeout=GET_S) for o in outs]
    assert all(r.error_type == "deadline_exceeded" for r in resps)
    assert srv.stats["expired_in_queue"] == 5000
    # the serving thread survived the drain and still serves
    assert srv._thread.is_alive()
    assert live.get(timeout=GET_S).ok
    assert _ledger_holds(srv.summary())
    srv.close()


def test_close_drain_releases_parked_hang(base_x):
    """close(drain=True) with a request parked on an injected hang:
    once the queue is empty the drain path releases the injector, so
    the parked request resolves with its REAL answer and close returns
    in query-time, not hang-time (60 s) or join-timeout (30 s)."""
    SearchEngine(base_x, **ENG).query(*_labels(), model="dbranch")
    inj = FaultInjector(specs=[FaultSpec("fused_query", action="hang",
                                         at_calls=(1,), delay_s=60.0)])
    eng = SearchEngine(base_x, **ENG, faults=inj)
    srv = QueryServer(eng)                # srv.faults defaults to inj
    out = srv.submit(QueryRequest(0, *_labels()))
    srv.start()
    time.sleep(0.3)                       # let the thread park on the hang
    t0 = time.monotonic()
    srv.close(drain=True)
    elapsed = time.monotonic() - t0
    r = out.get(timeout=5)
    assert r.ok                           # a hang is a delay, not a failure
    assert elapsed < 15.0, f"drain-close took {elapsed:.1f}s"
    assert srv.stats["served"] == 1
    assert srv.stats["shutdown_unserved"] == 0


def test_durability_snapshot_is_locked_pair(base_x, tmp_path):
    """summary() reads (lsn, wal stats) as ONE locked pair via
    ``SegmentedCatalog.durability_snapshot`` — a concurrent append must
    never yield an lsn from after it with stats from before."""
    eng = SearchEngine(base_x, **ENG, live=True,
                       data_dir=str(tmp_path / "cat"))
    srv = QueryServer(eng)
    cat = eng._catalog
    assert cat.durability_snapshot()["lsn"] == cat._lsn
    stop = threading.Event()
    torn = []

    def reader():
        while not stop.is_set():
            s = srv.summary()["durable"]
            # wal_records counts every logged mutation; lsn is assigned
            # from it under the same lock — a torn read shows records
            # from after an append paired with the lsn from before
            if s["wal_records"] != s["lsn"]:
                torn.append(s)

    t = threading.Thread(target=reader)
    t.start()
    for i in range(100):
        eng.append(_data(2, seed=i))
    stop.set()
    t.join()
    assert torn == []
    assert srv.summary()["durable"]["lsn"] == 100
    # engines without persistence publish no durable block
    plain = SearchEngine(base_x, **ENG, live=True)
    assert plain._catalog.durability_snapshot() is None
    assert "durable" not in QueryServer(plain).summary()
    srv.close()
