"""Per-query tracing: one span tree per request, ambient propagation,
recent-trace ring.

A ``Trace`` carries a request id (caller-provided ``X-Request-Id`` or a
fresh uuid4 hex) and one span tree. Its root, ``request``, opens when
the HTTP request line has been read (or at ``submit`` for callers that
skip the wire) and closes before the response is written; every other
span names its parent by span id:

    request
      http_read            rest of the read + JSON parse, to QueryRequest
      admission            deadline stamp, rate limit, shed checks
      queue                admission to handle entry (cross-thread)
        window_wait        the serving thread holding its window open
      window | cache       serve-side assembly, result-cache lookup/store
      prepare, fit         pre-device glue, the device fit
      device_round         one launch round of the score loop
        dispatch           the round's launch loop
        sync               the round's one batched device->host read
      rank                 device ranking + the top-k read
      handoff              serving thread's put to the event loop resuming
      http_encode          payload build + json.dumps
    compile                (anywhere) an executable built or loaded while
                           the request was attached; ``fun_name`` attr

Each span is ``(name, t0, dur_s, attrs, span_id, parent)``. A span shared
by a batched window (its ``fit``, its ``device_round``) lands on every
trace of the window under ONE span id, so readers deduplicate by id.

Propagation: the core engine stays importable without the serving
stack, and a batched call serves many requests at once. So spans are
recorded through a *thread-local ambient set* of traces — the serving
thread calls ``attach([t1, t2, ...])`` around the engine call and
instrumented code inside just opens ``span("fit")``; the span lands on
every attached trace. A thread-local stack of open span ids gives nested
spans their parent on their own; a span opened on an empty stack hangs
off each trace's root. When nothing is attached, ``span()`` returns a
shared no-op context — one attribute lookup and a falsy check.

Spans that start on one thread and end on another (``queue``,
``handoff``) are opened on the trace itself (``Trace.open`` with
``annotated=False``), closed by name (``Trace.close``), and take the
root as parent. Device rounds are opened by marks: the score loops open
one ``device_round`` per launch round (``round_mark``), closed by the
next mark or by the enclosing ``round_scope``, which also stamps the
window's engine counters on the last round.

Every span is recorded through one pair of primitives: ``_begin`` draws
its id, takes its parent and enters its profiler annotation; ``_end``
exits the annotation and records the span on its traces.

The profiler's clock: the JAX-importing layers install two hooks
(``install_hooks``), keeping this module stdlib-only. Every annotated
span (the serving thread's spans, the HTTP loop's ``request``,
``http_read`` and ``http_encode``) also enters a
``jax.profiler.TraceAnnotation`` of its name, so a profile's host plane
carries the same tree; cross-thread spans and ``compile`` (recorded
after the fact from its duration) stay host-clock only. The HTTP loop's
``request`` and ``http_read`` stay open across awaits, so with several
connections their annotations overlap on the loop thread without
nesting: the profile's host plane nests them only for one caller at a
time. The compile listener records a ``compile`` span on the ambient
traces of the thread that built or loaded the executable.

``TraceStore`` keeps the last N finished traces in a ring and writes a
threshold-gated slow-query log line (one JSON object per slow trace:
span totals, the executables compiled, the device rounds' counters) so
"why was *that* query slow" is answerable after the fact.
"""
from __future__ import annotations

import itertools
import json
import threading
import time
import uuid
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence

__all__ = ["Span", "Trace", "TraceStore", "attach", "active", "span",
           "annotate", "round_scope", "round_mark", "new_trace_id",
           "install_hooks", "on_duration_event", "COMPILE_EVENT"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# process-wide span ids: a window's shared span carries one id on every
# trace it lands on (next() on a count is atomic under the GIL)
_ids = itertools.count(1)


def new_trace_id() -> str:
    return uuid.uuid4().hex


class Span:
    __slots__ = ("name", "t0", "dur_s", "attrs", "span_id", "parent")

    def __init__(self, name: str, t0: float, dur_s: float,
                 attrs: Optional[Dict[str, Any]] = None,
                 span_id: Optional[int] = None,
                 parent: Optional[int] = None):
        self.name = name
        self.t0 = t0
        self.dur_s = dur_s
        self.attrs = attrs
        self.span_id = next(_ids) if span_id is None else span_id
        self.parent = parent

    def to_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {"name": self.name, "t0": self.t0,
                             "dur_s": self.dur_s, "id": self.span_id,
                             "parent": self.parent}
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        return d


# ---------------------------------------------------------------------
# profiler hooks (installed by the layers that import JAX)
# ---------------------------------------------------------------------

_annotation: Optional[Callable[[str], Any]] = None
_listening = False


class _NullCtx:
    """Shared no-op context: the disabled-tracing fast path allocates
    nothing and does two attribute loads + a falsy check per span."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def set(self, **attrs) -> None:
        pass


_NULL = _NullCtx()


def install_hooks(annotation: Callable[[str], Any],
                  register_duration_listener: Callable) -> None:
    """Install the profiler's annotation factory (``jax.profiler.
    TraceAnnotation``) and, once per process, register
    ``on_duration_event`` with ``register_duration_listener``
    (``jax.monitoring.register_event_duration_secs_listener``)."""
    global _annotation, _listening
    _annotation = annotation
    if not _listening:
        register_duration_listener(on_duration_event)
        _listening = True


def annotate(name: str):
    """A profiler annotation of ``name`` on this thread (no span); the
    shared null context when no factory is installed."""
    ann = _annotation
    return ann(name) if ann is not None else _NULL


# ---------------------------------------------------------------------
# the one way a span is recorded
# ---------------------------------------------------------------------

class _Open:
    """A span begun by ``_begin`` and not yet recorded."""
    __slots__ = ("name", "t0", "span_id", "parent", "ann")


def _begin(name: str, parent: Optional[int] = None,
           annotated: bool = True, t0: Optional[float] = None) -> _Open:
    """Open a span: a fresh id, its parent (None: each trace's root)
    and, when ``annotated``, a profiler annotation entered on this
    thread."""
    h = _Open()
    h.name, h.span_id, h.parent = name, next(_ids), parent
    ann = _annotation if annotated else None
    h.ann = ann(name) if ann is not None else None
    if h.ann is not None:
        h.ann.__enter__()
    h.t0 = time.perf_counter() if t0 is None else t0
    return h


def _end(h: _Open, traces: Sequence["Trace"],
         attrs: Optional[Dict[str, Any]] = None,
         t1: Optional[float] = None) -> None:
    """Close ``h``: exit its annotation and record it on ``traces``."""
    t1 = time.perf_counter() if t1 is None else t1
    if h.ann is not None:
        h.ann.__exit__(None, None, None)
    for t in traces:
        t.add_span(h.name, h.t0, t1 - h.t0, attrs, h.span_id, h.parent)


# ---------------------------------------------------------------------
# the trace
# ---------------------------------------------------------------------

class Trace:
    """One query's span tree. Append-only under its own small lock
    (spans arrive from the HTTP loop thread, the serving thread, and —
    via ambient attach — whatever thread runs the engine call).

    Its ``request`` root opens at ``t0`` (default now) and is recorded
    by ``finish``; ``wall_s`` is the root's duration. ``held`` marks a
    trace created by the front end, which finishes it on its own thread
    (the root is annotated there); the server finishes every other
    trace."""

    __slots__ = ("trace_id", "spans", "status", "finished_s", "attrs",
                 "held", "_root", "_open", "_lock")

    def __init__(self, trace_id: Optional[str] = None, *,
                 t0: Optional[float] = None, held: bool = False):
        self.trace_id = trace_id or new_trace_id()
        self.spans: List[Span] = []
        self.attrs: Dict[str, Any] = {}
        self.status: Optional[str] = None
        self.finished_s: Optional[float] = None
        self.held = held
        self._root = _begin("request", annotated=held, t0=t0)
        self._open: Dict[str, _Open] = {}
        self._lock = threading.Lock()

    # ------------------------------------------------------ recording --
    def add_span(self, name: str, t0: float, dur_s: float,
                 attrs: Optional[Dict[str, Any]] = None,
                 span_id: Optional[int] = None,
                 parent: Optional[int] = None) -> None:
        """Record a finished span; without ``parent`` it hangs off the
        root."""
        sp = Span(name, t0, dur_s, attrs, span_id,
                  self._root.span_id if parent is None else parent)
        with self._lock:
            self.spans.append(sp)

    def open(self, name: str, annotated: bool = True) -> None:
        """Open a root child that a later call closes by name
        (``close``), on this thread (``annotated``: the HTTP loop's
        ``http_read`` and ``http_encode``, which cross an await or a
        return) or on another (``annotated=False``: ``queue``, from
        admission to handle entry, so batch-window formation wait is
        inside it; ``handoff``)."""
        self._open[name] = _begin(name, annotated=annotated)

    def pending(self, name: str) -> Optional[_Open]:
        """The open span ``name`` (its ``t0`` and ``span_id``), or None:
        a child can name it before it closes."""
        return self._open.get(name)

    def close(self, name: str,
              attrs: Optional[Dict[str, Any]] = None) -> None:
        """Record the open span ``name``; a no-op when none is open."""
        h = self._open.pop(name, None)
        if h is not None:
            _end(h, (self,), attrs)

    # ------------------------------------------------------ finishing --
    def finish(self, status: str = "ok") -> None:
        """Stamp the outcome and close the root (and any span still
        open, e.g. ``http_read`` of a request that failed to parse).
        Idempotent: the first call wins."""
        if self.finished_s is not None:
            return
        for name in list(self._open):
            self.close(name)
        self.finished_s = time.perf_counter()
        self.status = status
        root = self._root
        if root.ann is not None:
            root.ann.__exit__(None, None, None)
        with self._lock:
            self.spans.append(Span("request", root.t0,
                                   self.finished_s - root.t0, None,
                                   root.span_id, None))

    @property
    def wall_s(self) -> float:
        end = self.finished_s if self.finished_s is not None \
            else time.perf_counter()
        return end - self._root.t0

    def to_dict(self) -> Dict[str, Any]:
        with self._lock:
            spans = [s.to_dict() for s in self.spans]
        d: Dict[str, Any] = {
            "trace_id": self.trace_id,
            "status": self.status,
            "wall_s": self.wall_s,
            "spans": spans,
        }
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        return d


def _slow_line(trace: Trace) -> str:
    """One slow-query log line: span totals by name, plus what the tree
    says about why — executables compiled or loaded, retries, and the
    device rounds' counters (rounds, syncs, retried subsets, blocks)."""
    d = trace.to_dict()
    totals: Dict[str, float] = {}
    compiles, rounds = [], {}
    for s in d["spans"]:
        totals[s["name"]] = totals.get(s["name"], 0.0) + s["dur_s"]
        attrs = s.get("attrs") or {}
        if s["name"] == "compile":
            compiles.append([attrs.get("fun_name", ""),
                             round(s["dur_s"] * 1e3, 3)])
        elif s["name"] == "device_round" and "rounds" in attrs:
            rounds = {k: v for k, v in attrs.items() if k != "round"}
    line = {"slow_query": True, "trace_id": trace.trace_id,
            "wall_ms": round(trace.wall_s * 1e3, 3),
            "status": trace.status,
            "spans": {k: round(v * 1e3, 3) for k, v in totals.items()}}
    if compiles:
        line["compiles"] = compiles
    if rounds:
        line["rounds"] = rounds
    if trace.attrs:
        line["attrs"] = trace.attrs
    return json.dumps(line, sort_keys=True)


class TraceStore:
    """Ring buffer of recently finished traces + slow-query log.

    ``slow_s`` is the latency threshold: any trace finishing above it
    gets one JSON line appended to ``slow_log`` entries (and, when a
    ``slow_log_path`` is set, to that file). Bounded on both axes so a
    long-lived server can't grow without limit."""

    def __init__(self, capacity: int = 256, slow_s: float = 1.0,
                 slow_log_capacity: int = 128,
                 slow_log_path: Optional[str] = None):
        self.capacity = int(capacity)
        self.slow_s = float(slow_s)
        self.slow_log_path = slow_log_path
        self._lock = threading.Lock()
        self._ring: "deque[Trace]" = deque(maxlen=self.capacity)
        self._slow: "deque[str]" = deque(maxlen=int(slow_log_capacity))

    def add(self, trace: Trace) -> None:
        line = _slow_line(trace) if trace.wall_s > self.slow_s else None
        with self._lock:
            self._ring.append(trace)
            if line is not None:
                self._slow.append(line)
        if line is not None and self.slow_log_path:
            try:
                with open(self.slow_log_path, "a") as f:
                    f.write(line + "\n")
            except OSError:
                pass    # slow log is best-effort; never fail the query

    def recent(self, n: int = 32) -> List[Dict[str, Any]]:
        with self._lock:
            traces = list(self._ring)
        return [t.to_dict() for t in traces[-max(0, int(n)):]]

    def get(self, trace_id: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            traces = list(self._ring)
        for t in reversed(traces):
            if t.trace_id == trace_id:
                return t.to_dict()
        return None

    def slow_log(self, n: int = 32) -> List[str]:
        with self._lock:
            return list(self._slow)[-max(0, int(n)):]

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)


# ---------------------------------------------------------------------
# Ambient propagation: thread-local set of attached traces plus the
# stack of open span ids. The serving thread attaches the batch's traces
# around the engine call; engine code records spans without importing
# anything above obs.
# ---------------------------------------------------------------------

_tls = threading.local()


def _stack() -> List[int]:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _push(name: str) -> _Open:
    """Begin a span under the innermost open span of this thread (the
    root when none) and make it the innermost."""
    st = _stack()
    h = _begin(name, st[-1] if st else None)
    st.append(h.span_id)
    return h


def _pop_end(h: _Open, traces: Sequence["Trace"],
             attrs: Optional[Dict[str, Any]]) -> None:
    """Take ``h`` off this thread's stack and record it."""
    st = _stack()
    if st and st[-1] == h.span_id:
        st.pop()
    elif h.span_id in st:
        st.remove(h.span_id)
    _end(h, traces, attrs)


class _Attach:
    __slots__ = ("_traces", "_prev")

    def __init__(self, traces: Sequence[Trace]):
        self._traces = list(traces)

    def __enter__(self):
        # each attach starts its own stack: a parent id always names a
        # span recorded on the same traces
        self._prev = (getattr(_tls, "traces", None),
                      getattr(_tls, "stack", None))
        _tls.traces = self._traces
        _tls.stack = []
        return self._traces

    def __exit__(self, exc_type, exc, tb):
        _tls.traces, _tls.stack = self._prev
        return False


def attach(traces: Sequence[Trace]) -> _Attach:
    """Context manager binding ``traces`` as this thread's ambient set.
    Nested attaches stack (inner wins, outer restored on exit)."""
    return _Attach(traces)


def active() -> List[Trace]:
    return getattr(_tls, "traces", None) or []


class _SpanCtx:
    __slots__ = ("_traces", "_name", "_attrs", "_h")

    def __init__(self, traces: List[Trace], name: str,
                 attrs: Optional[Dict[str, Any]]):
        self._traces, self._name, self._attrs = traces, name, attrs

    def __enter__(self):
        self._h = _push(self._name)
        return self

    def set(self, **attrs) -> None:
        """Attributes known only inside the block (e.g. a job count)."""
        self._attrs = {**(self._attrs or {}), **attrs}

    def __exit__(self, exc_type, exc, tb):
        _pop_end(self._h, self._traces, self._attrs)
        return False


def span(name: str, attrs: Optional[Dict[str, Any]] = None):
    """A span on every ambient trace, child of the innermost open span
    (or of each trace's root); the shared null context when no trace is
    attached (the ≈zero-cost disabled path)."""
    traces = getattr(_tls, "traces", None)
    if not traces:
        return _NULL
    return _SpanCtx(traces, name, attrs)


def on_duration_event(event: str, duration: float, **kw) -> None:
    """JAX duration listener: an executable built or loaded from the
    persistent cache on this thread becomes a ``compile`` span (with its
    ``fun_name``) on the ambient traces, under the innermost open span —
    the request that paid for it."""
    if event != COMPILE_EVENT:
        return
    traces = getattr(_tls, "traces", None)
    if not traces:
        return
    t1 = time.perf_counter()
    st = _stack()
    h = _begin("compile", st[-1] if st else None, annotated=False,
               t0=t1 - duration)
    _end(h, traces, {"fun_name": str(kw.get("fun_name", ""))}, t1)


class _RoundScope:
    """Per-subset device rounds, recorded by marks not nesting.

    ``round_mark()`` (called at the top of each launch round) closes the
    open ``device_round`` span and starts the next; exiting the scope
    closes the last, stamped with the counters given to ``set`` and the
    number of rounds. The first mark only starts round 0 — so N marks +
    exit → N spans. An open round sits on the span stack, so the round's
    ``dispatch``, ``sync`` and ``compile`` spans are its children."""

    __slots__ = ("_traces", "_h", "_idx", "_final", "_prev_scope")

    def __init__(self, traces: List[Trace]):
        self._traces = traces
        self._h: Optional[_Open] = None
        self._idx = 0
        self._final: Dict[str, Any] = {}

    def __enter__(self):
        self._prev_scope = getattr(_tls, "round_scope", None)
        _tls.round_scope = self
        return self

    def __exit__(self, exc_type, exc, tb):
        self._close_open(last=True)
        _tls.round_scope = self._prev_scope
        return False

    def set(self, **counters) -> None:
        """The window's counters, recorded on the last round."""
        self._final.update(counters)

    def _close_open(self, last: bool = False) -> None:
        if self._h is None:
            return
        attrs: Dict[str, Any] = {"round": self._idx}
        if last:
            attrs.update(self._final, rounds=self._idx + 1)
        _pop_end(self._h, self._traces, attrs)
        self._h = None
        self._idx += 1

    def mark(self) -> None:
        self._close_open()
        self._h = _push("device_round")


def round_scope():
    """Scope for a score loop's device rounds; null when untraced."""
    traces = getattr(_tls, "traces", None)
    if not traces:
        return _NULL
    return _RoundScope(traces)


def round_mark() -> None:
    """One device launch round boundary. No-op unless inside an active
    ``round_scope``."""
    scope = getattr(_tls, "round_scope", None)
    if scope is not None:
        scope.mark()
