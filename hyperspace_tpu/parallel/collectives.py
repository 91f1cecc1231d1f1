"""COLLECTIVE_SITES — the registry of cross-process collective call sites.

The SHARED_STATE doctrine applied to the multi-host plane: every
collective / cross-process barrier call site in the package declares its
*symmetry contract* HERE, so "does every process issue the same
collective program?" is a mechanical question (``hslint`` HS8xx,
``analysis/spmd.py``), not a code-review hope. PR 11's review had to
hand-fix a whole class of collective-symmetry bugs — zero-row processes
skipping the ``all_to_all``, waves planned over per-process file lists,
barriers reachable from only some processes — and Exoshuffle (PAPERS.md)
shows shuffle planes live or die by exactly this property. The runtime
collective witness (``testing/collective_witness.py``) wraps the sites
named here during the multi-host dryrun and cross-checks each process's
*recorded* collective sequence against the others (``hslint
--witness``).

Entry shape::

    "<dotted path of the module-level callable>": (
        "<collective op it issues (all_to_all, ppermute, ...)>",
        "<contract>",
        "<one-line justification — why the contract holds>",
    )

Site paths must name MODULE-LEVEL callables (the witness wraps them by
module-attribute replacement; in-module callers resolve the name through
module globals at call time, so the wrapper is seen everywhere).
Contracts:

``symmetric-all``
    Every process issues the call at the same position in its collective
    sequence with the same payload signature (shapes/dtypes/static
    args). The strictest contract — the SPMD requirement for
    ``shard_map`` collectives, whose compiled programs hang or corrupt
    when any participant diverges.
``per-host-lane``
    Every process issues the call at the same sequence position, but the
    payload is that process's own lane data (per-host row subsets,
    local count matrices), so signatures may differ across processes.
``coordinator-gated``
    Only the coordinator (process 0) issues the call — the metadata
    plane's single-writer seams. The witness treats an occurrence on any
    other process as a hard contract violation, and HS801 accepts
    ``is_coordinator`` branches that gate exactly these sites.

Keep this module stdlib-only and import-cheap: the collective witness
imports it inside dryrun worker processes before jax is initialized, and
the analyzer only ever parses it.
"""

from __future__ import annotations

from typing import Dict, Tuple

#: the known symmetry contracts (HS802 rejects anything else)
CONTRACTS = ("symmetric-all", "per-host-lane", "coordinator-gated")

COLLECTIVE_SITES: Dict[str, Tuple[str, str, str]] = {
    # -- bootstrap ------------------------------------------------------------
    "hyperspace_tpu.parallel.mesh.initialize_distributed": (
        "distributed.initialize",
        "per-host-lane",
        "every process joins the one jax job at the same protocol step "
        "but carries its OWN process_id (the per-host payload); topology "
        "parameters agree, and idempotent re-entry is a no-op everywhere",
    ),
    # -- exchange-strategy device programs (parallel/shuffle.py) -------------
    "hyperspace_tpu.parallel.shuffle._compact_program": (
        "all_to_all",
        "symmetric-all",
        "single-controller shard_map program over host-packed exact-extent "
        "buffers; slot caps derive from the global count matrix (never "
        "reached on a multi-process job)",
    ),
    "hyperspace_tpu.parallel.shuffle._twostage_program": (
        "ppermute",
        "symmetric-all",
        "H-1 ppermute rounds over the dcn axis with STATIC per-round caps "
        "taken from the allgathered count matrix — every process compiles "
        "and issues the identical round sequence",
    ),
    "hyperspace_tpu.parallel.shuffle._twostage_exchange_mp": (
        "process_allgather",
        "per-host-lane",
        "each process contributes its own [H, L] send-count matrix; the "
        "allgather runs at the same position on every process and its "
        "result makes every later shape decision global",
    ),
    # -- build metadata plane (indexes/covering_build.py) --------------------
    "hyperspace_tpu.indexes.covering_build._global_written": (
        "sync_global_devices",
        "per-host-lane",
        "every process reaches the post-write barrier with its own "
        "written-file subset and returns the identical global union "
        "listing; reachable from every write_bucketed exit path, zero-row "
        "stripes included",
    ),
    # -- action protocol (actions/base.py) -----------------------------------
    "hyperspace_tpu.actions.base._action_rendezvous": (
        "process_allgather",
        "per-host-lane",
        "the action protocol's abort-aware rendezvous: every process "
        "allgathers its own step verdict at the same protocol step, so "
        "a one-sided failure aborts the job everywhere instead of "
        "leaving peers blocked, and no worker enters the data plane "
        "before the coordinator's begin entry exists",
    ),
    "hyperspace_tpu.actions.base._publish_log": (
        "log_write",
        "coordinator-gated",
        "operation-log OCC writes are single-writer by design: only the "
        "coordinator publishes begin/commit entries; workers already hold "
        "the global file list via _global_written",
    ),
    "hyperspace_tpu.actions.base._publish_latest_stable": (
        "log_write",
        "coordinator-gated",
        "latestStable pointer publish rides the same single-writer "
        "metadata seam as the log entries themselves",
    ),
}
