"""Structured tracing — one root span per query / lifecycle action.

Flare (PAPERS.md) makes the case bluntly: once pipelines compile into
fused native passes, only *built-in* instrumentation can explain where
time went — an external profiler sees one opaque sweep. This module is
that instrumentation for the serve and build planes:

* A **root span** wraps every query admitted by the serve frontend
  (``serve/frontend.py``) and every lifecycle action
  (``actions/base.py``). Child **stage spans** carry the breakdown
  keys — they are recorded by the SAME hooks that feed
  ``last_serve_breakdown`` / ``last_build_breakdown`` (instruments of
  ``obs/metrics.py``), so a trace's stage timings are consistent with
  the breakdowns *by construction*, never by parallel bookkeeping.

* **Lifecycle-action traces are always recorded.** ``Action.run`` opens
  its root with ``root(..., always=True)``: an action has tens of spans
  over seconds, so its account costs what the breakdown dict-and-lock
  costs. ``hyperspace.obs.enabled`` gates the SERVE plane only — the
  ``serve.query`` / ``advisor.run`` roots and the query log.

* **The decision is made once, at the root.** :func:`span`,
  :func:`stage`, :func:`accumulate`, :func:`event` and :func:`carry`
  record iff the calling context has a live parent span; none of them
  reads the switch.

* **One clock, real intervals.** Every span holds ``start_ns`` and
  ``end_ns`` from ``time.perf_counter_ns()`` and its ``parent_id``, so
  self time (duration minus what the child spans cover —
  :meth:`Span.self_seconds`) is computable and a span can be laid
  beside any other. Roots alone also keep a wall-clock ``start_ms``
  (the query log's ``ts_ms``). A :func:`stage` span given only
  ``seconds`` (busy seconds a pass summed itself) has no interval: it
  is marked ``summed`` and left out of every union and self time.

* **On the profiler's clock too.** A ``with trace.span(name)`` block
  also enters ``jax.profiler.TraceAnnotation("hs." + name)`` — free
  when no profiler session is on, and never the reason ``jax`` gets
  imported — so on a profiled run every program span lies in the
  profiler's own trace, next to the device's "XLA Ops".

* **Which span compiled.** Once ``jax`` is imported, :func:`root`
  registers two ``jax.monitoring`` listeners (once a process): every
  backend compile adds ``compiles`` and ``compile_s``, every
  persistent-cache hit ``compile_cache_hits``, to the span live in the
  compiling thread's context and to its root. jax reports a compile
  that the cache served as a compile too (its seconds are then the
  retrieval), so ``compiles - compile_cache_hits`` is what XLA built.
  No span live: nothing recorded.

* **Context propagation.** The current span rides a ``contextvars``
  ContextVar. Thread pools do not propagate context, so every pool
  boundary on the serve path (the shared ``io/scan.scan_pool``, the
  frontend executor, the per-bucket/per-shard prepare and match pools)
  wraps its submitted callables in :func:`carry` — identity when no
  span is live, a parent-handoff otherwise. Cross-PROCESS propagation
  rides the fleet planes: the single-flight claim file and the fanout
  bus events carry the publishing trace's id, so a cross-process dedup
  links winner and losers to one trace (``serve/fleet.py``,
  ``serve/bus.py``).

* **Zero-cost serve off path.** With ``hyperspace.obs.enabled`` off
  (the default) :func:`root` returns a shared no-op singleton for the
  serve roots, so no span is ever live on the serve path: there
  :func:`span` / :func:`stage` / :func:`carry` cost one
  ``ContextVar.get`` and the serve path's behavior is the pre-obs
  tree's.

Completed traces land in a bounded in-memory ring (:func:`finished`)
for bench/test introspection and are counted in the metrics registry;
the durable per-query record is the query log's job
(``obs/querylog.py``). Scope doctrine: process-global, last-writer-wins
configuration, like every telemetry plane in this tree.

Every span/metric call site in the package is declared in
``obs/sites.py`` (``OBS_SITES``) with a one-line justification —
hslint HS9xx (``analysis/obs.py``) rejects undeclared instrumentation
and stage-span names that drift from the declared vocabulary.
"""

from __future__ import annotations

import contextvars
import sys
import threading
import time
import uuid
from collections import deque
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from hyperspace_tpu import constants as C

# -- module state (SHARED_STATE-registered; hyperspace_tpu/concurrency.py) --

#: serve-plane switch (``hyperspace.obs.enabled``) — gates root() for
#: the serve.query / advisor.run roots; rebind-only bool, a racy read
#: costs one trace, never a torn value
_enabled = False

#: per-trace child-span cap / finished-trace ring size (rebind-only ints,
#: re-published whole by configure())
_max_spans = C.OBS_TRACE_MAX_SPANS_DEFAULT

_rec_lock = threading.Lock()
#: finished ROOT spans, oldest-first (guarded by _rec_lock)
_finished: deque = deque(maxlen=C.OBS_TRACE_RETAIN_DEFAULT)

#: the compile listeners are registered (guarded by _rec_lock: once a
#: process, by the first root() that finds jax imported)
_compile_listening = False

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

#: the active span of the calling context (set via activate()/span())
_current: contextvars.ContextVar = contextvars.ContextVar(
    "hs_obs_span", default=None
)


def _now_ms() -> int:
    return int(time.time() * 1000)


def _union_ns(intervals: Iterable[Tuple[int, int]], lo: int, hi: int) -> int:
    """Nanoseconds of ``[lo, hi]`` that the intervals cover."""
    covered, at = 0, lo
    for s, e in sorted(intervals):
        s, e = max(s, at), min(e, hi)
        if e > s:
            covered += e - s
            at = e
    return covered


def _annotation(name: str):
    """``jax.profiler.TraceAnnotation("hs." + name)``, so a profiled run
    holds the program's spans on the device trace's clock — or None in
    a process that never imported jax (no profiler session can be on
    there, and a span must not be what imports it)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    return jax.profiler.TraceAnnotation("hs." + name)


def _add_to_span_and_root(deltas: Dict[str, float]) -> None:
    """Add ``deltas`` into the attrs of the calling context's span and
    of its root (once, where they are one span), under the record lock:
    a span carried onto pool threads may compile on several at once."""
    cur = _current.get()
    if cur is None:
        return
    targets = (cur,) if cur.root is cur else (cur, cur.root)
    with _rec_lock:
        for sp in targets:
            for key, value in deltas.items():
                sp.attrs[key] = round(sp.attrs.get(key, 0) + value, 6)


def _on_compile_duration(event: str, duration: float, **_kw) -> None:
    if event == _COMPILE_EVENT:
        _add_to_span_and_root({"compiles": 1, "compile_s": duration})


def _on_compile_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT_EVENT:
        _add_to_span_and_root({"compile_cache_hits": 1})


def _listen_for_compiles() -> None:
    """Register the two ``jax.monitoring`` listeners, once a process and
    only where ``jax`` is already imported (:func:`_annotation`'s rule:
    a trace is never what imports it; a process without jax compiles
    nothing). jax calls a listener in the thread that compiles, so the
    span it finds in the context is the one the compile ran under."""
    global _compile_listening
    monitoring = getattr(sys.modules.get("jax"), "monitoring", None)
    if monitoring is None:
        return
    with _rec_lock:  # a root takes it again for every span it finishes
        if _compile_listening:
            return
        _compile_listening = True
    monitoring.register_event_duration_secs_listener(_on_compile_duration)
    monitoring.register_event_listener(_on_compile_event)


class Span:
    """One timed operation: ``[start_ns, end_ns]`` on the
    ``perf_counter_ns`` clock, under ``parent_id``. Roots own the flat
    list of their trace's finished spans (appended under ``_rec_lock``
    — children finish on arbitrary pool threads) and alone carry a
    wall-clock ``start_ms``; child spans carry a reference to their
    root. Attributes are plain JSON-able values."""

    __slots__ = (
        "trace_id",
        "span_id",
        "parent_id",
        "name",
        "start_ms",
        "start_ns",
        "end_ns",
        "summed_s",
        "attrs",
        "root",
        "spans",
        "events",
        "spans_dropped",
    )

    def __init__(
        self,
        name: str,
        parent: Optional["Span"] = None,
        attrs: Optional[dict] = None,
        start_ns: Optional[int] = None,
    ):
        self.name = name
        self.parent_id = parent.span_id if parent is not None else None
        self.trace_id = (
            parent.trace_id if parent is not None else uuid.uuid4().hex[:32]
        )
        self.span_id = uuid.uuid4().hex[:16]
        self.start_ms = _now_ms() if parent is None else None
        self.start_ns = (
            time.perf_counter_ns() if start_ns is None else int(start_ns)
        )
        self.end_ns: Optional[int] = None
        #: busy seconds of a span that has no interval (stage(seconds=))
        self.summed_s: Optional[float] = None
        self.attrs: Dict = dict(attrs) if attrs else {}
        self.root: "Span" = parent.root if parent is not None else self
        # root-only trace state
        self.spans: List["Span"] = []
        self.events: List[Dict] = []
        self.spans_dropped = 0

    @property
    def summed(self) -> bool:
        return self.summed_s is not None

    @property
    def duration_s(self) -> Optional[float]:
        if self.summed_s is not None:
            return self.summed_s
        if self.end_ns is None:
            return None
        return (self.end_ns - self.start_ns) / 1e9

    # -- lifecycle ----------------------------------------------------------
    def set(self, key: str, value) -> "Span":
        self.attrs[key] = value
        return self

    def add_event(self, name: str, **attrs) -> None:
        """Attach a point-in-time event (retry, degrade, shed, link) to
        the trace; recorded on the ROOT under the record lock — events
        fire from arbitrary worker threads."""
        ev = {"name": name, "ts_ms": _now_ms(), **attrs}
        with _rec_lock:
            self.root.events.append(ev)

    def finish(self) -> "Span":
        if self.end_ns is not None:
            return self  # idempotent — double-finish keeps the first
        self.end_ns = time.perf_counter_ns()
        root = self.root
        with _rec_lock:
            if len(root.spans) < _max_spans:
                root.spans.append(self)
            else:
                root.spans_dropped += 1
            if root is self:
                _finished.append(self)
        if root is self:
            from hyperspace_tpu.obs import metrics as _m

            _m.traces_total.inc()
            _m.spans_total.inc(len(self.spans))
        return self

    # -- context-manager protocol ------------------------------------------
    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        self.finish()

    def to_dict(self) -> dict:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start_ms": self.start_ms,
            "start_ns": self.start_ns,
            "end_ns": self.end_ns,
            "summed": self.summed,
            "duration_s": self.duration_s,
            "attrs": dict(self.attrs),
        }

    def _snapshot(self) -> List["Span"]:
        with _rec_lock:
            return list(self.spans)

    def stage_seconds(self) -> Dict[str, float]:
        """Root-only: child span busy-seconds keyed by span name, summed
        — the same shape as ``last_serve_breakdown`` (stages overlap
        under the pipelined serve, and a sub-stage lies inside its
        stage, so values are busy time and may sum past wall time,
        exactly like the breakdown they mirror)."""
        out: Dict[str, float] = {}
        for s in self._snapshot():
            if s is self or s.duration_s is None:
                continue
            out[s.name] = out.get(s.name, 0.0) + s.duration_s
        return out

    def children_union_s(self) -> float:
        """Root-only: seconds of the root's own interval that its DIRECT
        children cover (overlapping children count once). The root's
        duration minus this is the time no span names."""
        hi = self.end_ns if self.end_ns is not None else time.perf_counter_ns()
        kids = [
            (s.start_ns, s.end_ns)
            for s in self._snapshot()
            if s.parent_id == self.span_id and not s.summed
        ]
        return _union_ns(kids, self.start_ns, hi) / 1e9

    def self_seconds(self) -> Dict[str, float]:
        """Root-only: per span name (the root's included, once it has
        finished), duration minus the union of that span's direct
        children's intervals — where the time is spent, not merely
        passed through. ``summed`` spans have no interval and are left
        out on both sides."""
        spans = [s for s in self._snapshot() if not s.summed]
        kids: Dict[str, List[Tuple[int, int]]] = {}
        for s in spans:
            if s.parent_id is not None:
                kids.setdefault(s.parent_id, []).append((s.start_ns, s.end_ns))
        out: Dict[str, float] = {}
        for s in spans:
            covered = _union_ns(kids.get(s.span_id, ()), s.start_ns, s.end_ns)
            out[s.name] = (
                out.get(s.name, 0.0) + (s.end_ns - s.start_ns - covered) / 1e9
            )
        return out


class _NoopSpan:
    """The shared disabled-path span: every method is a no-op, so call
    sites never branch beyond the parent check in span()/root()."""

    __slots__ = ()
    trace_id = None
    span_id = None
    name = ""
    duration_s = None

    def set(self, key, value):
        return self

    def add_event(self, name, **attrs):
        pass

    def finish(self):
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass

    def stage_seconds(self):
        return {}


NOOP = _NoopSpan()


class _Activation:
    """Context manager installing ``span`` as the calling context's
    current span (and restoring the previous one on exit). With
    ``owned=True`` the span is also finished on exit and mirrored as a
    profiler annotation for the block (the ``with trace.span(...)``
    shape); a plain activation leaves it open — activation and
    lifetime are decoupled because a root span outlives several
    activations (admission thread, then the worker running the
    query)."""

    __slots__ = ("_span", "_token", "_owned", "_annotation")

    def __init__(self, span, owned: bool = False):
        self._span = span
        self._token = None
        self._owned = owned
        self._annotation = None

    def __enter__(self):
        if not isinstance(self._span, _NoopSpan):
            self._token = _current.set(self._span)
            if self._owned:
                self._annotation = _annotation(self._span.name)
                if self._annotation is not None:
                    self._annotation.__enter__()
        return self._span

    def __exit__(self, *exc):
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            self._annotation = None
        if self._token is not None:
            _current.reset(self._token)
            self._token = None
        if self._owned:
            self._span.finish()


# ---------------------------------------------------------------------------
# Public surface
# ---------------------------------------------------------------------------


def set_enabled(on: bool) -> None:
    """Flip the process-global serve-plane switch (rebind-only
    publish)."""
    global _enabled
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


def configure(conf) -> bool:
    """Adopt a session's ``hyperspace.obs.*`` trace settings (process-
    global, last-writer-wins — the telemetry doctrine): the serve-plane
    switch and the ring's bounds. Returns the resulting switch state."""
    global _max_spans, _finished
    set_enabled(conf.obs_enabled)
    _max_spans = conf.obs_trace_max_spans
    retain = conf.obs_trace_retain
    with _rec_lock:
        if retain != _finished.maxlen:
            _finished = deque(_finished, maxlen=retain)
    return _enabled


def root(name: str, *, always: bool = False, **attrs) -> Span:
    """Start a ROOT span (a new trace). Returns :data:`NOOP` when the
    serve-plane switch is off — callers hold and finish the result
    either way — unless ``always`` (lifecycle actions: their trace is
    the build's account, recorded whatever the switch says)."""
    if not (always or _enabled):
        return NOOP
    _listen_for_compiles()
    return Span(name, parent=None, attrs=attrs)


def activate(span) -> _Activation:
    """Install ``span`` as the current span for a ``with`` block (does
    not finish it on exit — see :class:`_Activation`)."""
    return _Activation(span)


def span(name: str, **attrs):
    """Start a CHILD span of the current span, as a context manager
    that finishes it on exit (and mirrors it as an ``hs.<name>``
    profiler annotation). No-op when no span is live in this context —
    stage instrumentation outside a root (a bare ``collect()``, a serve
    with obs off) costs one ``ContextVar.get``."""
    parent = _current.get()
    if parent is None:
        return NOOP
    return _Activation(Span(name, parent=parent, attrs=attrs), owned=True)


def stage(
    name: str,
    t0: Optional[float] = None,
    seconds: Optional[float] = None,
    attrs: Optional[dict] = None,
    start_ns: Optional[int] = None,
) -> None:
    """Record an already-timed stage as a child span of the current
    context: ``[t0, now]`` with ``t0`` a ``time.perf_counter()``
    reading (or ``start_ns`` a ``perf_counter_ns()`` one — the same
    clock), or an explicit ``seconds`` of busy time a pass summed
    itself, which has no interval and is marked ``summed``. This is the
    hook the serve-side ``_stage_add`` calls: the stage-span timing IS
    the breakdown increment, so trace and breakdown can never
    disagree."""
    parent = _current.get()
    if parent is None:
        return
    if start_ns is None and t0 is not None:
        start_ns = int(t0 * 1e9)
    s = Span(name, parent=parent, attrs=attrs, start_ns=start_ns)
    if seconds is not None:
        s.summed_s = max(0.0, float(seconds))
    s.finish()


def event(name: str, **attrs) -> None:
    """Attach a point event to the current trace (retry, degrade,
    shed, cross-process link); dropped when no trace is active."""
    cur = _current.get()
    if cur is not None:
        cur.add_event(name, **attrs)


def accumulate(key: str, value) -> None:
    """Add ``value`` into the ROOT span's ``attrs[key]`` (numeric
    accumulator, taken under the record lock — hooks fire from
    arbitrary pool threads). This is how per-execution counters that
    are produced deep inside the engine (zone-map pruning's rows-pruned
    count; a build's rows and bytes at each boundary) attribute to the
    query or action that caused them instead of to a process-global
    last-writer cell: each root carries exactly its own deltas, so
    concurrent executions never cross-attribute. Dropped when no trace
    is active."""
    cur = _current.get()
    if cur is None:
        return
    root_span = cur.root
    with _rec_lock:
        root_span.attrs[key] = root_span.attrs.get(key, 0) + value


def current() -> Optional[Span]:
    return _current.get()


def current_trace_id() -> Optional[str]:
    """The active trace id, for cross-process propagation (claim files,
    bus events) — None when no trace is active."""
    cur = _current.get()
    return cur.trace_id if cur is not None else None


def carry(fn: Callable) -> Callable:
    """Capture the calling context's current span and re-install it
    around every invocation of ``fn`` — the pool-boundary propagation
    shim (``ThreadPoolExecutor`` does not propagate contextvars).
    Identity when no span is live, so wrapped submit sites cost one
    ``ContextVar.get`` on the untraced path. Safe for ``pool.map``:
    each invocation sets/resets independently."""
    parent = _current.get()
    if parent is None:
        return fn

    def run(*args, **kwargs):
        token = _current.set(parent)
        try:
            return fn(*args, **kwargs)
        finally:
            _current.reset(token)

    return run


def finished(name: Optional[str] = None) -> List[Span]:
    """Completed root spans, oldest first (optionally filtered by root
    name) — the bench/test introspection surface."""
    with _rec_lock:
        roots = list(_finished)
    if name is not None:
        roots = [r for r in roots if r.name == name]
    return roots


def reset() -> None:
    """Drop the finished-trace ring (test isolation)."""
    with _rec_lock:
        _finished.clear()
