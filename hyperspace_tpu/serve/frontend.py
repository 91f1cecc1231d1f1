"""ServeFrontend — the concurrent serve plane (docs/serve-server.md).

Everything below this module executes ONE query; a process "serving
millions of users" is measured under contention. The frontend owns that
boundary:

* **Admission control.** Identical in-flight plans are deduplicated
  (single-flight by :func:`plan_fingerprint` + config version + pinned
  snapshot — N clients asking the same question cost one execution),
  and queries queued past ``hyperspace.serve.maxQueueDepth`` are shed
  with a typed :class:`ServeOverloadedError` at submit time, before any
  work is buffered.

* **Snapshot-consistent serving.** At admission each query pins the
  set of latestStable ACTIVE log entries (``metadata/log_manager.py``;
  one read, one consistent set) and the rewrite runs against that pin
  (``rules/apply.apply_hyperspace(entries=…)``) — a ``refresh`` /
  ``optimize`` / ``vacuum`` landing mid-query can never mix index
  versions inside one query. Index version file sets are immutable, so
  the pinned plan stays readable until a vacuum physically removes the
  old version — which surfaces as an I/O error and is healed by the
  retry below (re-pin + re-plan on the current snapshot). Each pin is
  also registered with the recovery plane
  (``metadata/recovery.register_pins``) for the life of the query, so
  orphan GC never quarantines a file a live serve still reads.

* **Retry / degrade at the operation boundary** (Exoshuffle doctrine:
  fault handling belongs in the application-level dataflow). TRANSIENT
  failures — real I/O errors, vacuumed-under-us files, or injected
  ``testing/faults.py`` faults — retry with exponential backoff
  (``hyperspace.serve.retry.*``), re-pinning the snapshot each attempt.
  PERSISTENT I/O failures of an index-rewritten query degrade to the
  unrewritten plan (serve straight from the source data — slower,
  bit-identical). Native-kernel faults never reach this module: every
  kernel dispatch degrades in place to its registered numpy/interpreted
  twin (``KERNEL_TWINS``, ``native.load``). Failing cache inserts are
  dropped in place (``ServeCache.insert_failures``). The result is the
  fault matrix the tests pin down: for every injection point ×
  {transient, persistent}, a serve either retries to a bit-identical
  result or degrades to an identical-output path — never a wrong
  answer, never a hung query.

Threading: queries run on the frontend's own pool (``hs-serve-*``).
Per-bucket parquet reads still go to the shared ``io/scan.scan_pool``
— serve workers BLOCK on scan futures, scan workers never block on
serve futures, so the two pools cannot deadlock (the scan pool's
documented discipline). One frontend lock guards admission state and
counters; nothing blocking and no I/O runs under it. The single-flight
map is SHARED_STATE-registered (``hyperspace_tpu/concurrency.py``,
hslint HS6xx audits every access; the runtime lock witness wraps
``_lock`` during the stress suites).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from typing import List, Optional, Tuple

from hyperspace_tpu.constants import States
from hyperspace_tpu.exceptions import (
    HyperspaceException,
    ServeOverloadedError,
)
from hyperspace_tpu.metadata import recovery
from hyperspace_tpu.obs import metrics as obs_metrics
from hyperspace_tpu.obs import planspec as obs_planspec
from hyperspace_tpu.obs import querylog as obs_querylog
from hyperspace_tpu.obs import trace as obs_trace
from hyperspace_tpu.plan.nodes import LogicalPlan
from hyperspace_tpu.testing.faults import InjectedFault


def plan_fingerprint(plan: LogicalPlan) -> Tuple:
    """Identity of a logical plan for single-flight purposes: the node
    structure (``repr`` covers operators, conditions, projections) plus
    each leaf relation's concrete file snapshot — two scans of the same
    directory at different snapshots must not coalesce."""
    leaves = tuple(
        (
            leaf.relation.files,
            leaf.relation.fmt,
            leaf.relation.excluded_file_ids,
            leaf.relation.options,
        )
        for leaf in plan.collect_leaves()
    )
    return (repr(plan), leaves)


class _SloClass:
    """Per-tenant admission state (``hyperspace.fleet.class.<name>.*``,
    docs/fleet-serve.md): ``max_concurrency`` caps how many class
    queries RUN at once (excess admissions wait in ``pending`` without
    occupying a worker thread), ``max_queue_depth`` sheds past that
    backlog — both 0 = unlimited. Mutated only under the frontend
    lock."""

    __slots__ = (
        "name",
        "max_concurrency",
        "max_queue_depth",
        "running",
        "pending",
        "admitted",
        "shed",
    )

    def __init__(self, name: str, max_concurrency: int, max_queue_depth: int):
        self.name = name
        self.max_concurrency = max_concurrency
        self.max_queue_depth = max_queue_depth
        self.running = 0
        self.pending: deque = deque()
        self.admitted = 0
        self.shed = 0

    def has_slot(self) -> bool:
        return self.max_concurrency <= 0 or self.running < self.max_concurrency


def _chain_future(inner: Future, outer: Future) -> None:
    """Propagate ``inner``'s outcome onto the caller-visible ``outer``
    (deferred SLO-class dispatch hands out ``outer`` at submit time)."""

    def _done(f: Future) -> None:
        exc = f.exception()
        if exc is not None:
            outer.set_exception(exc)
        else:
            outer.set_result(f.result())

    inner.add_done_callback(_done)


def _is_transient(exc: BaseException) -> bool:
    """Retryable? Injected faults carry the answer; every real OSError
    (missing file after a concurrent vacuum, flaky storage, Arrow I/O
    errors — OSError subclasses in pyarrow) is worth the retry budget.
    Engine errors (HyperspaceException et al.) are deterministic and
    retry would just repeat them."""
    if isinstance(exc, InjectedFault):
        return exc.transient
    return isinstance(exc, OSError)


class ServeFrontend:
    """Long-lived concurrent query frontend over one session.

    Usage (also ``session.serve_frontend`` for a shared instance)::

        fe = session.serve_frontend
        table = fe.serve(df)             # blocking
        fut = fe.submit(df)              # Future[pyarrow.Table]

    Results are shared between deduplicated callers — pyarrow Tables
    are immutable, so sharing is safe.
    """

    def __init__(self, session):
        self._session = session
        self._max_queue = session.conf.serve_max_queue_depth
        self.max_concurrency = session.conf.serve_max_concurrency
        self._pool = ThreadPoolExecutor(
            max_workers=self.max_concurrency,
            thread_name_prefix="hs-serve",
        )
        self._lock = threading.Lock()
        self._inflight: dict = {}
        self._queued = 0
        self._closed = False
        # per-tenant SLO classes, frozen at construction like the pool
        # size (docs/fleet-serve.md); unknown class names see only the
        # global bounds
        self._slo_classes = {
            name: _SloClass(name, caps[0], caps[1])
            for name, caps in session.conf.fleet_slo_classes.items()
        }
        # counters (read via stats(); all mutated under _lock)
        self._admitted = 0
        self._completed = 0
        self._deduped = 0
        self._shed = 0
        self._retries = 0
        self._degraded = 0
        self._degraded_pins = 0
        self._failed = 0
        self._latencies: deque = deque(maxlen=4096)
        # observability plane (docs/observability.md): adopt the
        # session's hyperspace.obs.* settings (process-global,
        # last-writer-wins — the telemetry doctrine), open the durable
        # query log next to the lake, and export stats() as a live
        # registry view. All three are no-ops/None with obs off.
        self._obs_enabled = obs_trace.configure(session.conf)
        self._querylog = None
        if self._obs_enabled and session.conf.obs_querylog_enabled:
            self._querylog = obs_querylog.QueryLog(
                obs_querylog.obs_root(session.conf),
                max_bytes=session.conf.obs_querylog_max_bytes,
                max_files=session.conf.obs_querylog_max_files,
            )
        self._stats_view = obs_metrics.registry.register_weak_view(
            "serve_frontend", self
        )

    # -- snapshot pinning ---------------------------------------------------
    def _pin(self) -> Optional[Tuple]:
        """The latestStable ACTIVE entries, captured once — the query's
        index snapshot. Transient log-read failures retry inline with
        the serve backoff; a persistent failure degrades to pin=None
        (serve without indexes: correct, slower), because a dead
        metadata store must not take query serving down with it."""
        session = self._session
        if not session.is_hyperspace_enabled() or not session.conf.apply_enabled:
            return ()
        attempts = session.conf.serve_retry_max_attempts
        backoff = session.conf.serve_retry_backoff_ms / 1000.0
        for attempt in range(attempts):
            try:
                with obs_trace.span("pin"):
                    return tuple(
                        session.index_manager.get_indexes([States.ACTIVE])
                    )
            # catch-all IS the contract: pin failure of any shape must
            # degrade to serving without indexes, never fail the query
            except Exception as exc:  # hslint: disable=HS402
                if not _is_transient(exc) or attempt + 1 >= attempts:
                    with self._lock:
                        self._degraded_pins += 1
                    return None
                with self._lock:
                    self._retries += 1
                if backoff > 0:
                    time.sleep(backoff * (1 << attempt))
        return None

    def _register_pins(self, pin: Optional[Tuple]) -> int:
        """Record the pinned snapshot with the recovery plane. The fleet
        frontend (``serve/fleet.py``) overrides this to ALSO publish a
        lease-expiring durable pin file per index, so a GC or vacuum in
        another process sees the pin too."""
        return recovery.register_pins(pin)

    # -- admission ----------------------------------------------------------
    def submit(self, query, slo_class: Optional[str] = None) -> Future:
        """Admit one query (DataFrame or LogicalPlan). Returns a Future
        resolving to the pyarrow Table. Raises
        :class:`ServeOverloadedError` when the pending queue is full —
        nothing is buffered for a shed query.

        ``slo_class`` names a per-tenant admission class
        (``hyperspace.fleet.class.<name>.*``): class queries past the
        class ``maxQueueDepth`` shed BEFORE the global bound bites, and
        at most ``maxConcurrency`` of them run at once — excess
        admissions wait without occupying a worker thread, so a greedy
        batch tier cannot starve the interactive tier's workers. An
        unconfigured (or None) class sees only the global bounds."""
        plan = getattr(query, "logical_plan", query)
        if not isinstance(plan, LogicalPlan):
            raise HyperspaceException(
                f"serve() takes a DataFrame or LogicalPlan, got {type(query)}"
            )
        cls = self._slo_classes.get(slo_class) if slo_class else None
        # shed BEFORE pinning: an overloaded frontend must reject in
        # O(1) with no metadata I/O and no backoff sleeps on the caller
        # thread — that cheap typed rejection is the whole point of the
        # bound. The cost is that a shed query never gets the chance to
        # dedup onto an in-flight twin; under overload that trade is
        # the documented contract. Depth is re-checked at enqueue (the
        # pin read dropped the lock in between).
        with self._lock:
            self._check_admittable_locked(cls)
        # the query ROOT span starts HERE so queue-wait is on the trace;
        # a query that dedups onto an in-flight twin abandons it
        # unfinished (one root span per EXECUTION is the contract —
        # deduped submits share the winner's execution and its trace)
        root = obs_trace.root("serve.query", slo_class=slo_class)
        with obs_trace.activate(root):
            pin = self._pin()
        # register the pinned snapshot's files with the recovery plane:
        # orphan GC (metadata/recovery.gc_orphans) never quarantines a
        # pinned file, so a version that goes unreferenced mid-query
        # stays readable until the query releases it (_run's finally)
        pin_token = self._register_pins(pin)
        fp = (
            plan_fingerprint(plan),
            self._session.conf.version,
            None
            if pin is None
            else tuple((e.name, e.id) for e in pin),
        )
        if root.span_id is not None:
            import hashlib

            root.set(
                "fingerprint",
                hashlib.sha256(repr(fp).encode("utf-8")).hexdigest()[:16],
            )
            root.set("predicate", obs_querylog.predicate_shape(plan))
            if self._session.conf.obs_querylog_record_plans:
                # opt-in: specs carry literals (obs/planspec.py doctrine)
                spec = obs_planspec.to_spec(plan)
                if spec is not None:
                    root.set("replay", spec)
        try:
            with self._lock:
                existing = self._inflight.get(fp)
                if existing is not None:
                    self._deduped += 1
                    recovery.release_pins(pin_token)
                    return existing
                self._check_admittable_locked(cls)
                self._queued += 1
                self._admitted += 1
                if cls is not None:
                    cls.admitted += 1
                if cls is None or cls.has_slot():
                    if cls is not None:
                        cls.running += 1
                    fut = self._pool.submit(
                        self._run, plan, pin, pin_token, cls, root
                    )
                else:
                    # class concurrency cap reached: park the admission;
                    # a finishing class query dispatches it (the caller
                    # holds this outer future either way)
                    fut = Future()
                    cls.pending.append((plan, pin, pin_token, fut, root))
                self._inflight[fp] = fut
        except BaseException:
            recovery.release_pins(pin_token)
            raise
        fut.add_done_callback(lambda _f, fp=fp: self._forget(fp))
        return fut

    def _fleet_class_depth_locked(self, cls: _SloClass) -> int:
        """Peers' contribution to this class's queue depth (called with
        the lock held). The single-process frontend has no peers;
        ``FleetFrontend`` overrides this with gossiped live depths so a
        class bound is enforced FLEET-wide, not per-process."""
        return 0

    def _check_admittable_locked(self, cls: Optional[_SloClass] = None) -> None:
        """Raise unless a new query may enter (call with the lock held).
        The class bound is checked FIRST: a tenant over its own budget
        sheds with its class named, before it can pressure the global
        queue every other tenant shares."""
        if self._closed:
            raise HyperspaceException("ServeFrontend is closed")
        if cls is not None and cls.max_queue_depth > 0:
            fleet_depth = self._fleet_class_depth_locked(cls)
            if (
                len(cls.pending) + cls.running + fleet_depth
                >= cls.max_queue_depth
            ):
                cls.shed += 1
                self._shed += 1
                raise ServeOverloadedError(
                    f"SLO class {cls.name!r} queue full ({cls.running} "
                    f"running + {len(cls.pending)} pending + {fleet_depth} "
                    f"fleet >= maxQueueDepth {cls.max_queue_depth}); shedding"
                )
        if self._max_queue > 0 and self._queued >= self._max_queue:
            self._shed += 1
            raise ServeOverloadedError(
                f"serve queue full ({self._queued} pending >= "
                f"maxQueueDepth {self._max_queue}); shedding"
            )

    def _dispatch_pending_locked(self, cls: _SloClass) -> List[int]:
        """Hand parked class admissions to the pool while slots are free
        (call with the lock held). Returns the pin tokens of CANCELLED
        parked admissions — the caller releases them outside the lock
        (pin release is file I/O in fleet mode)."""
        cancelled: List[int] = []
        while cls.pending and cls.has_slot():
            plan, pin, pin_token, outer, root = cls.pending.popleft()
            # a parked outer future is a bare Future the caller may have
            # cancelled; claim it (RUNNING blocks further cancellation)
            # or drop the admission — a cancelled query must neither
            # ghost-execute nor leak its pin
            if not outer.set_running_or_notify_cancel():
                cancelled.append(pin_token)
                self._queued -= 1
                continue
            cls.running += 1
            inner = self._pool.submit(
                self._run, plan, pin, pin_token, cls, root
            )
            _chain_future(inner, outer)
        return cancelled

    def serve(self, query, slo_class: Optional[str] = None):
        """Blocking convenience: submit and wait."""
        return self.submit(query, slo_class=slo_class).result()

    def _forget(self, fp) -> None:
        with self._lock:
            self._inflight.pop(fp, None)

    # -- execution ----------------------------------------------------------
    def _execute_pinned(self, plan: LogicalPlan, pin: Optional[Tuple]):
        from hyperspace_tpu.execution import execute
        from hyperspace_tpu.rules.apply import apply_hyperspace

        session = self._session
        optimized = plan
        if pin:
            with obs_trace.span("rewrite"):
                optimized = apply_hyperspace(session, plan, entries=list(pin))
            cur = obs_trace.current()
            if cur is not None:
                cur.root.set(
                    "indexes", obs_querylog.indexes_in_plan(optimized)
                )
                cur.root.set("rule", obs_querylog.rule_flavor(plan))
        with obs_trace.span("execute"):
            return execute(optimized, session)

    def _run(
        self,
        plan: LogicalPlan,
        pin: Optional[Tuple],
        pin_token: int,
        cls: Optional[_SloClass] = None,
        root=obs_trace.NOOP,
    ):
        with self._lock:
            self._queued -= 1
        with obs_trace.activate(root):
            if root.span_id is not None:
                # admission -> worker pickup, on the root's own clock
                obs_trace.stage("queue_wait", start_ns=root.start_ns)
            try:
                out = self._run_attempts(plan, pin, pin_token, cls, root)
                if root.span_id is not None:
                    root.set("status", "ok")
                    root.set("rows_returned", int(out.num_rows))
                    self._querylog_append(root)
                return out
            except BaseException:
                if root.span_id is not None:
                    root.set("status", "failed")
                    root.set("rows_returned", 0)
                    self._querylog_append(root)
                raise
            finally:
                root.finish()

    def _run_attempts(
        self,
        plan: LogicalPlan,
        pin: Optional[Tuple],
        pin_token: int,
        cls: Optional[_SloClass],
        root,
    ):
        session = self._session
        attempts = session.conf.serve_retry_max_attempts
        backoff = session.conf.serve_retry_backoff_ms / 1000.0
        t_start = time.perf_counter()
        attempt = 1
        try:
            while True:
                try:
                    out = self._execute_pinned(plan, pin)
                    self._record(t_start)
                    return out
                except Exception as exc:  # classified below; always re-raised
                    if _is_transient(exc) and attempt < attempts:
                        attempt += 1
                        with self._lock:
                            self._retries += 1
                        root.add_event(
                            "retry", attempt=attempt, error=str(exc)[:200]
                        )
                        if backoff > 0:
                            time.sleep(backoff * (1 << (attempt - 2)))
                        # re-pin: a vacuum may have removed the pinned
                        # version's files; the current snapshot serves.
                        # Swap the GC pin along with it.
                        recovery.release_pins(pin_token)
                        pin = self._pin()
                        pin_token = self._register_pins(pin)
                        continue
                    if isinstance(exc, OSError) and pin:
                        # persistent I/O failure of the index-rewritten
                        # query: degrade to the unrewritten plan (source
                        # data; bit-identical result — the covering-index
                        # equivalence the differential suite guarantees)
                        with self._lock:
                            self._degraded += 1
                        root.add_event("degrade", error=str(exc)[:200])
                        try:
                            out = self._execute_pinned(plan, ())
                        except Exception:
                            with self._lock:
                                self._failed += 1
                            raise exc from None
                        self._record(t_start)
                        return out
                    with self._lock:
                        self._failed += 1
                    raise
        finally:
            recovery.release_pins(pin_token)
            if cls is not None:
                with self._lock:
                    cls.running -= 1
                    dropped = self._dispatch_pending_locked(cls)
                for token in dropped:
                    recovery.release_pins(token)

    def _querylog_append(self, root) -> None:
        """One record per executed query (docs/observability.md schema;
        best-effort — an unwritable sidecar never fails the query)."""
        if self._querylog is None:
            return
        rec = {
            "ts_ms": root.start_ms,
            "trace_id": root.trace_id,
            "fingerprint": root.attrs.get("fingerprint", ""),
            "predicate": root.attrs.get("predicate", ""),
            "slo_class": root.attrs.get("slo_class"),
            "indexes": root.attrs.get("indexes", []),
            "rule": root.attrs.get("rule"),
            "duration_s": (time.perf_counter_ns() - root.start_ns) / 1e9,
            "stages": {
                k: round(v, 6) for k, v in root.stage_seconds().items()
            },
            "rows_returned": root.attrs.get("rows_returned", 0),
            # per-execution delta accumulated by the pruning pass onto
            # THIS root (obs_trace.accumulate) — never a module-global
            # read that a concurrent query could have overwritten
            "rows_pruned": int(root.attrs.get("rows_pruned", 0)),
            "events": [
                {k: v for k, v in ev.items()}
                for ev in root.events[-32:]
            ],
            "status": root.attrs.get("status", "ok"),
        }
        spec = root.attrs.get("replay")
        if spec is not None:
            rec["replay"] = spec
        self._querylog.append(rec)

    def _record(self, t_start: float) -> None:
        dt = time.perf_counter() - t_start
        with self._lock:
            self._completed += 1
            self._latencies.append(dt)

    # -- introspection / lifecycle ------------------------------------------
    def stats(self) -> dict:
        """One consistent snapshot of the frontend counters, plus p50/p99
        over the most recent completions (seconds). ``snapshot_at_ms``
        stamps WHEN — merge several frontends'/processes' snapshots
        with ``obs.merge_snapshots`` (it sums counters, maxes
        watermarks, drops percentiles), never by hand."""
        with self._lock:
            lat: List[float] = sorted(self._latencies)
            out = {
                "snapshot_at_ms": int(time.time() * 1000),
                "admitted": self._admitted,
                "completed": self._completed,
                "deduped": self._deduped,
                "shed": self._shed,
                "retries": self._retries,
                "degraded": self._degraded,
                "degraded_pins": self._degraded_pins,
                "failed": self._failed,
                "queued": self._queued,
                "inflight": len(self._inflight),
                "max_concurrency": self.max_concurrency,
            }
            if self._slo_classes:
                out["slo_classes"] = {
                    name: {
                        "admitted": cls.admitted,
                        "shed": cls.shed,
                        "running": cls.running,
                        "pending": len(cls.pending),
                        "max_concurrency": cls.max_concurrency,
                        "max_queue_depth": cls.max_queue_depth,
                    }
                    for name, cls in self._slo_classes.items()
                }
        if lat:
            out["p50_s"] = lat[len(lat) // 2]
            out["p99_s"] = lat[min(len(lat) - 1, (len(lat) * 99) // 100)]
        return out

    @property
    def closed(self) -> bool:
        return self._closed

    def close(self, wait: bool = True) -> None:
        with self._lock:
            self._closed = True
            parked = [
                item for cls in self._slo_classes.values() for item in cls.pending
            ]
            for cls in self._slo_classes.values():
                cls.pending.clear()
        # parked class admissions can never dispatch once closed: fail
        # their futures and release their pins OUTSIDE the lock (a
        # caller-cancelled future takes no exception — the cancel
        # already resolved it)
        for _plan, _pin, pin_token, outer, _root in parked:
            recovery.release_pins(pin_token)
            if outer.set_running_or_notify_cancel():
                outer.set_exception(
                    HyperspaceException("ServeFrontend closed while queued")
                )
        self._pool.shutdown(wait=wait)
        if self._querylog is not None:
            self._querylog.close()
        # provider-matched: closing an OLD frontend must not tear down
        # a newer live frontend's view (last-wins registration)
        obs_metrics.registry.unregister_view(
            "serve_frontend", self._stats_view
        )

    def __enter__(self) -> "ServeFrontend":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
