"""hyperspace_tpu.obs — the unified observability plane.

Three legs (docs/observability.md):

* :mod:`obs.trace` — structured tracing: one root span per lifecycle
  action (always recorded — the build's account) and per frontend
  query (when ``hyperspace.obs.enabled`` turns the serve plane on),
  child spans with real intervals on one clock, context propagated
  across every serve-path thread pool and (via the fleet claim/spool
  plane and bus events) across processes. With the switch off no span
  is live on the serve path and every site there costs one
  ``ContextVar.get``.
* :mod:`obs.metrics` — the typed counter/gauge/stage-timer registry
  that absorbed the scattered telemetry snapshots
  (``last_serve_breakdown`` / ``last_build_breakdown`` are views over
  registered instruments; frontend/cache ``stats()`` export as live
  views), with a Prometheus text exporter and a JSONL sink.
* :mod:`obs.querylog` — the durable per-query JSONL log next to the
  lake (bounded, rotated, fleet-safe) — the workload profile the
  advisor loop (ROADMAP item 5) mines.

Every instrumentation site is declared in :mod:`obs.sites`
(``OBS_SITES``); hslint HS9xx (``analysis/obs.py``) enforces it.
"""

from __future__ import annotations

from hyperspace_tpu.obs import metrics, querylog, sites, trace
from hyperspace_tpu.obs.metrics import merge_snapshots, registry
from hyperspace_tpu.obs.querylog import QueryLog, read_records

__all__ = [
    "trace",
    "metrics",
    "querylog",
    "sites",
    "registry",
    "merge_snapshots",
    "QueryLog",
    "read_records",
]
