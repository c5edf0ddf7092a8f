"""The program's spans on the profiler's clock: in a CPU profile of
served queries, each span opened and closed on one thread appears as a
host annotation of its name, nested as the span tree nests, within 1 ms
of where the harness's one offset (``bench/run.py``) puts the span."""
import json
import sys
import time
import urllib.request
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import run as bench_run  # noqa: E402
from bench import trace_reduce  # noqa: E402

# the spans that carry a profiler annotation (cross-thread spans and
# compile do not)
ANNOTATED = ("request", "http_read", "http_encode", "window_wait", "cache",
             "prepare", "fit", "device_round", "dispatch", "sync", "rank")
TOL_NS = 1e6


def _post(port, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/query", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    import jax

    from repro.core.engine import SearchEngine
    from repro.serve.engine import QueryServer
    from repro.serve.http import HttpFrontEnd

    x = np.random.default_rng(5).normal(0, 1, (600, 16)).astype(np.float32)
    eng = SearchEngine(x, n_subsets=4, subset_dim=4, block=64, live=True)
    srv = QueryServer(eng, max_results=20, batch_window_s=0.01)
    srv.start()
    fe = HttpFrontEnd(srv)
    _, port = fe.start()
    neg = list(range(100, 150))
    try:
        for k in (6, 7):             # compile outside the profile
            _post(port, {"pos_ids": list(range(k)), "neg_ids": neg})
        out = tmp_path_factory.mktemp("profile")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(out), profiler_options=opts)
        ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
        p0 = time.perf_counter()
        ann.__enter__()
        answers = [_post(port, {"pos_ids": list(range(k)), "neg_ids": neg})
                   for k in (8, 9)]
        ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
    finally:
        fe.close()
        srv.close()
    traces = {a["trace_id"]: srv.obs.traces.get(a["trace_id"])
              for a in answers}
    pd = trace_reduce.load(trace_reduce.find_xplane(str(out)))
    offset = trace_reduce.window_bounds(pd)[0] - p0 * 1e9
    events = [(line.name or str(line.id), e.name, e.start_ns,
               e.start_ns + e.duration_ns)
              for plane in pd.planes if plane.name.startswith("/host")
              for line in plane.lines for e in line.events
              if e.name in ANNOTATED]
    return traces, offset, events


def _match(events, name, s, e):
    return [ev for ev in events if ev[1] == name
            and abs(ev[2] - s) < TOL_NS and abs(ev[3] - e) < TOL_NS]


def test_every_annotated_span_is_on_the_profile_within_1ms(profiled):
    traces, offset, events = profiled
    mapped = bench_run._spans_on_trace_clock(traces, offset)
    names = {n for n, _, _ in mapped}
    assert set(ANNOTATED) - {"cache"} <= names, names
    for name, s, e in mapped:
        if name in ANNOTATED:
            assert _match(events, name, s, e), (name, s, e)


def test_annotations_nest_as_the_span_tree(profiled):
    traces, offset, events = profiled
    checked = set()
    for tr in traces.values():
        by_id = {sp["id"]: sp for sp in tr["spans"]}
        for sp in tr["spans"]:
            parent = by_id.get(sp["parent"])
            if sp["name"] not in ANNOTATED or parent is None \
                    or parent["name"] not in ANNOTATED:
                continue
            s = sp["t0"] * 1e9 + offset
            (child,) = _match(events, sp["name"], s,
                              s + sp["dur_s"] * 1e9)[:1]
            ps = parent["t0"] * 1e9 + offset
            (outer,) = _match(events, parent["name"], ps,
                              ps + parent["dur_s"] * 1e9)[:1]
            # same thread, and the child's event inside the parent's
            assert child[0] == outer[0]
            assert outer[2] <= child[2] and child[3] <= outer[3]
            checked.add((parent["name"], sp["name"]))
    assert {("request", "http_read"), ("request", "http_encode"),
            ("device_round", "dispatch"),
            ("device_round", "sync")} <= checked, checked
