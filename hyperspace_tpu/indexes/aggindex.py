"""Aggregate index plane: persisted partial-aggregate state + samples.

ROADMAP item 2 (Partial Partial Aggregates, PAPERS.md): every serve of a
``Filter→Aggregate`` already computes per-chunk partial COUNT/SUM/MIN/
MAX state (``hs_fused_filter_agg``) and throws it away. This module
persists that state at build time so point aggregates become *metadata
reads* (docs/agg-serve.md):

* **capture** — at create/refresh/optimize the actions write an
  ``_aggstate.json`` sidecar into the version directory (underscore
  prefix: invisible to content scans and the data-path filter, the
  zone-map sidecar pattern) holding, per file / per row group, the
  partial-aggregate state of every column — valid counts, wrapped int64
  sums, float sums, replace-on-equal min/max with clean/NaN side
  counts — plus single-key GROUPED partials for every fusable column
  whose per-row-group distinct count stays under
  ``hyperspace.index.agg.maxGroupsPerRowGroup``. A stratified per-row-
  group row sample lands next to it in ``_aggsample.parquet`` for the
  approximate plane (``execution/approx_exec.py``). Partials are swept
  by the SAME kernel the serve sweep runs and snapshot through the same
  public hook (``pipeline_compiler.AggState.partials`` → ``AggPartials``;
  the numpy twin ``partials_from_batch`` where the kernel cannot run),
  so the build-time capture and the serve-time pass share one state
  layout by construction. The files of a version directory are swept
  concurrently; the sidecars keep file order.
* **lazy backfill** — pre-existing indexes (and files whose sidecar
  entry is stale by (size, mtime_ns)) compute the same per-file doc by
  reading the file once, memoized per file identity; a rewritten file
  can never serve stale partials.
* **serve assembly** — ``agg_data_for`` assembles one file set's
  decoded state, cached in the ServeCache under ``("aggstate", fp)``
  (``evict_kind`` support) with a module LRU for cache-off serves;
  ``classify_row_groups`` splits a strictly-lowered conjunction
  (``zonemaps.predicate_intervals_complete``) into FULL / EMPTY /
  PARTIAL row groups, and ``rg_partials`` turns a FULL row group's
  stored state back into :class:`~hyperspace_tpu.execution.
  pipeline_compiler.AggPartials` for the order-preserving fold.

Soundness contract: a row group is FULL only when EVERY row provably
satisfies the whole conjunction — exact per-column min/max computed from
the data itself (never parquet footer statistics, whose NaN handling
diverges from the engine), zero nulls and zero NaNs in every conjunct
column, interval bounds compared in float64 with INWARD directed
rounding (can only demote full → partial, never promote). EMPTY requires
provable non-overlap (outward rounding, the zone-map rule). Everything
else is PARTIAL and gets scanned.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
import json
import logging
import os
import threading
import time as _time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.compute as pa_compute
import pyarrow.parquet as pq

from hyperspace_tpu import constants as C
from hyperspace_tpu.testing import faults

_log = logging.getLogger("hyperspace_tpu.aggindex")

SIDECAR_NAME = "_aggstate.json"
SAMPLE_NAME = "_aggsample.parquet"
_SIDECAR_VERSION = 1

_I64_MIN = -(1 << 63)
_I64_MAX = (1 << 63) - 1


# ---------------------------------------------------------------------------
# Scalar codec: every stored scalar is an int (int64 value, or the int64
# BIT VIEW of a float64 — exact for -0.0 / NaN payloads / infinities,
# which repr/hex round-trips are not) or None ("no valid value here").
# ---------------------------------------------------------------------------


def _enc_f64(v: float) -> int:
    return int(np.float64(v).view(np.int64))


def _dec_f64_arr(vals: List[Optional[int]], identity: float) -> np.ndarray:
    bits = np.array(
        [(_enc_f64(identity) if v is None else v) for v in vals],
        dtype=np.int64,
    )
    return bits.view(np.float64)


def _dec_i64_arr(vals: List[Optional[int]], identity: int) -> np.ndarray:
    return np.array(
        [identity if v is None else v for v in vals], dtype=np.int64
    )


# ---------------------------------------------------------------------------
# Per-file doc computation (shared by capture and lazy backfill)
# ---------------------------------------------------------------------------


def _capture_spec(schema: pa.Schema):
    """(count-only cols, numeric cols with f64 flag, key candidates) for
    one index file's schema, using the fused pipeline's own type lowering
    so capture and serve can never disagree on what is fusable."""
    from hyperspace_tpu.execution.pipeline_compiler import _fusable_f64

    count_only: List[str] = []
    numeric: List[Tuple[str, bool]] = []
    for name in schema.names:
        f64 = _fusable_f64(schema.field(name).type)
        if f64 is None:
            count_only.append(name)
        else:
            numeric.append((name, f64))
    return count_only, numeric


class _OverCap(Exception):
    """A grouped sweep met a group past ``max_groups``: the row group's
    key is over the cardinality cap, whatever the rest of it holds."""


@contextlib.contextmanager
def _outside(turn: Optional[threading.Lock], stats: dict, key: str):
    """Put ``turn`` down for a call that runs outside the interpreter
    lock (a parquet read, a kernel sweep), and take it up again after.

    ``capture_index_dir``'s tasks hold one lock between them while they
    run Python: with 13 of them taking the interpreter lock from one
    another at every small call, the Python part of a file cost 3.5
    times the CPU it costs alone (the chip's 13-core host, PERF.md §6
    PR 26) and the pool lost to a pool of two. Taking turns, only the
    calls in here overlap — which is all that can.

    This is also where a task's seconds are told apart: the call's own
    go to ``stats[key]`` (``read_s`` or ``sweep_s``), and what it then
    waits to have the turn back to ``stats["turn_wait_s"]``
    (:func:`_take`). Two clock reads a call."""
    t0 = _time.perf_counter()
    if turn is not None:
        turn.release()
    try:
        yield
    finally:
        stats[key] += _time.perf_counter() - t0
        if turn is not None:
            _take(turn, stats)


def _take(turn: threading.Lock, stats: dict) -> None:
    """Take the turn; the seconds it was another task's go to
    ``stats["turn_wait_s"]`` — none, and no clock read, where it was
    free, so a capture of one task reads exactly 0."""
    if not turn.acquire(blocking=False):
        t0 = _time.perf_counter()
        turn.acquire()
        stats["turn_wait_s"] += _time.perf_counter() - t0


@functools.lru_cache(maxsize=None)
def _capped_state_cls():
    """``pipeline_compiler.AggState`` with a ceiling on its group table
    (built on first use: importing this module must not import the
    execution stack)."""
    from hyperspace_tpu.execution.pipeline_compiler import AggState

    class CappedState(AggState):
        """The serve sweep's chunk state, for ONE row group of a capture:
        the table starts at ``max_groups`` slots (or ``_INIT_CAP``, if
        that is smaller), and a kernel stop at a full table that may not
        grow abandons the pass at that row — the kernel stops before the
        overflowing row, so what it met is a group past the cap."""

        def __init__(self, plan, max_groups: int):
            self.max_groups = max_groups
            self._INIT_CAP = min(AggState._INIT_CAP, max(1, max_groups))
            super().__init__(plan)

        def _alloc(self, cap: int) -> None:
            super()._alloc(cap)
            # the kernel wants a power-of-two table strictly larger than
            # cap; cap itself (a conf value) need not be a power of two
            self.ht = np.full(1 << (4 * cap - 1).bit_length(), -1, np.int64)

        def _grow(self) -> None:
            if self.cap >= self.max_groups:
                raise _OverCap
            super()._grow()

    return CappedState


def _sweep_plan(PC, schema: pa.Schema, key: Optional[str], ops):
    """The fused kernel's plan for one capture sweep: no filter terms
    (every row passes), ``key`` as the single group key or none, and the
    capture's agg ops. ``partials_from_batch`` reads only ``group_by``
    and ``agg_ops`` of it."""
    group_by = () if key is None else (key,)
    key_types = tuple(schema.field(k).type for k in group_by)
    return PC.FusedAggPlan(
        read_cols=(),
        terms=(),
        term_f64=(),
        bounds=((), (), (), (), ()),
        group_by=group_by,
        key_f64=tuple(bool(PC._fusable_f64(t)) for t in key_types),
        key_types=key_types,
        agg_ops=tuple(ops),
        aggs=(),
        out_types=(),
    )


def _in_factorize_order(pt):
    """Grouped kernel partials (groups in first-occurrence order) put in
    the twin's group order — ``aggregate_exec._factorize``: ascending
    signed key rep, then the null plane — which is the order the sidecar
    stores. The accumulators are per group, so this moves columns and
    re-sums nothing."""
    perm = np.lexsort((pt.g_nulls[0], pt.g_reps[0]))
    return dataclasses.replace(
        pt,
        **{
            f.name: getattr(pt, f.name)[:, perm]
            for f in dataclasses.fields(pt)
            if isinstance(getattr(pt, f.name), np.ndarray)
        },
    )


def _sweep(PC, plan, batch, max_groups: int, stats, turn=None):
    """Partials of one row group under ``plan``, or None when a grouped
    pass is over ``max_groups``: the native one-pass kernel serve runs
    (``hs_fused_filter_agg`` through ``AggState``; outside the turn, its
    seconds in ``stats["sweep_s"]``), else — native not loaded, a
    column outside the fused 8-byte set — its numpy twin
    ``partials_from_batch`` (Python, under the turn). The two are
    bit-identical per group, and float sums keep the kernel's row order
    either way, with one
    exception that the twin therefore decides: a float sum that came out
    NaN. Which NaN — the sign and payload of a data NaN, or of the one
    ``inf - inf`` makes — is the first operand's on x86, and the two
    compilers do not order the operands alike."""
    grouped = bool(plan.group_by)
    state = _capped_state_cls()(plan, max_groups if grouped else 1)
    try:
        with _outside(turn, stats, "sweep_s"):
            swept = state.accumulate(batch)
    except _OverCap:
        stats["sweeps_native"] += 1
        stats["early_rejects"] += 1
        return None
    if swept and not np.isnan(state.acc_f[:, : state.n_groups]).any():
        stats["sweeps_native"] += 1  # (min/max slots hold clean values)
        pt, reorder = state.partials(copy=False), grouped
    else:
        stats["sweeps_twin"] += 1
        pt, reorder = PC.partials_from_batch(plan, batch), False
        if pt is None:  # a column decoded outside the expected set
            raise ValueError("uncapturable column set")
    if grouped and pt.n_groups > max_groups:
        return None
    return _in_factorize_order(pt) if reorder else pt


#: what one ``file_agg_doc`` call adds up in its ``stats`` dict: the
#: seconds of its calls outside the turn (``read_s``: the footer and the
#: row groups from parquet; ``sweep_s``: the kernel's passes), the
#: seconds it waited to take the turn up again after them, and the
#: passes by what became of them
_FILE_STATS = (
    "read_s",
    "sweep_s",
    "turn_wait_s",
    "sweeps_native",
    "sweeps_twin",
    "early_rejects",
)


def _capture_ops(count_only, numeric):
    """The agg-op list capturing every column's full partial state, and
    per-column slot maps back into it."""
    from hyperspace_tpu.execution import pipeline_compiler as PC

    ops: List[Tuple[int, Optional[str]]] = [(PC._OP_COUNT_STAR, None)]
    slots: Dict[str, Dict[str, int]] = {}
    for c in count_only:
        slots[c] = {"cnt": len(ops)}
        ops.append((PC._OP_COUNT_COL, c))
    for c, f64 in numeric:
        if f64:
            slots[c] = {
                "sum": len(ops),
                "min": len(ops) + 1,
                "max": len(ops) + 2,
                "f64": 1,
            }
            ops.extend(
                [(PC._OP_SUM_F64, c), (PC._OP_MIN_F64, c), (PC._OP_MAX_F64, c)]
            )
        else:
            slots[c] = {
                "sum": len(ops),
                "min": len(ops) + 1,
                "max": len(ops) + 2,
                "f64": 0,
            }
            ops.extend(
                [(PC._OP_SUM_I64, c), (PC._OP_MIN_I64, c), (PC._OP_MAX_I64, c)]
            )
    return ops, slots


def _partials_to_cols(pt, slots) -> Dict[str, Dict[str, list]]:
    """Per-column stored arrays (one cell per group) from one partials
    snapshot — the inverse of :func:`rg_partials`' accumulator mapping.
    Floats are stored as their int64 bit views (the scalar codec)."""
    acc_cnt = pt.acc_cnt.tolist()
    acc_aux = pt.acc_aux.tolist()
    acc_i = pt.acc_i.tolist()
    acc_f = pt.acc_f.view(np.int64).tolist()

    def where(vals, present):
        return [v if p else None for v, p in zip(vals, present)]

    cols: Dict[str, Dict[str, list]] = {}
    for name, sl in slots.items():
        if "sum" not in sl:  # count-only column
            cols[name] = {"cnt": acc_cnt[sl["cnt"]]}
            continue
        a_sum, a_min, a_max = sl["sum"], sl["min"], sl["max"]
        cnt = acc_cnt[a_sum]
        if sl["f64"]:
            clean = acc_aux[a_min]
            cols[name] = {
                "cnt": cnt,
                "f64": 1,
                "sum": acc_f[a_sum],
                "min": where(acc_f[a_min], clean),
                "max": where(acc_f[a_max], clean),
                "clean": clean,
                "nan": acc_aux[a_max],
            }
        else:
            cols[name] = {
                "cnt": cnt,
                "f64": 0,
                "sum": acc_i[a_sum],
                "min": where(acc_i[a_min], cnt),
                "max": where(acc_i[a_max], cnt),
            }
    return cols


def _sample_rng(basename: str, rg: int):
    """Deterministic per-(file, row group) generator so capture and lazy
    backfill produce the SAME sample rows."""
    from hyperspace_tpu.utils.hashing import murmur3_64_bytes

    seed = murmur3_64_bytes(f"hs-aggsample:{basename}:{rg}".encode("utf-8"))
    return np.random.default_rng(np.uint64(np.int64(seed)))


def file_agg_doc(
    path: str,
    max_groups: int = C.INDEX_AGG_MAX_GROUPS_DEFAULT,
    sample_rows: int = C.INDEX_AGG_SAMPLE_ROWS_DEFAULT,
    group_keys: Optional[Tuple[str, ...]] = None,
    stats: Optional[Dict[str, float]] = None,
    turn: Optional[threading.Lock] = None,
) -> Tuple[dict, Optional[pa.Table]]:
    """(sidecar entry, stratified sample table) for ONE index data file,
    computed from the file itself — the single definition shared by
    build-time capture and the serve path's lazy backfill. Each row
    group's partials are swept by the kernel serve itself runs
    (:func:`_sweep`: ``hs_fused_filter_agg``, or its numpy twin
    ``pipeline_compiler.partials_from_batch`` where the kernel cannot
    run), so the stored state is bit-identical to what the serve kernel
    would have produced over the same rows.

    ``group_keys`` restricts grouped-partial capture to those columns
    (lowercase match): the serve-path backfill passes the ONE key the
    query groups by, so a first serve over an unsidecar'd index pays one
    grouped sweep instead of one per numeric column; build-time capture
    leaves it None (every fusable candidate). ``stats`` has the
    ``_FILE_STATS`` keys added to: the seconds of the parquet reads and
    of the kernel's sweeps (the calls made outside the turn), the
    seconds waited for the turn after them, the sweeps by the
    implementation that ran them, and the grouped sweeps abandoned at
    the cap. ``turn`` is a lock the caller holds, shared with other
    tasks' calls: it is put down around the reads and the sweeps
    (:func:`_outside`); without one (the serve path's backfill) the
    same two clock reads a call are all the account costs.

    The row groups carry nothing from one to the next, so the file is
    :func:`_range_agg_doc` over all of them and :func:`_whole_file` of
    that; a capture with fewer files than workers runs the same two
    over a file's ranges (:func:`_plan_tasks`)."""
    whole = _range_agg_doc(
        path, None, max_groups, sample_rows, group_keys, stats=stats, turn=turn
    )
    return _whole_file([whole])


def _range_agg_doc(
    path: str,
    row_groups: Optional[range],
    max_groups: int,
    sample_rows: int,
    group_keys: Optional[Tuple[str, ...]] = None,
    stats: Optional[Dict[str, float]] = None,
    turn: Optional[threading.Lock] = None,
) -> Tuple[dict, List[pa.Table]]:
    """(entry, one sample table a row group) over ``row_groups`` of one
    file (None: all of them), through a ``pq.ParquetFile`` of this
    call's own: :func:`file_agg_doc`'s body, short of what is decided
    over the whole file (:func:`_whole_file`). A row group's cells and
    its sample depend on the file's name and the row group's number
    alone (:func:`_sample_rng`), so ranges put end to end are the file."""
    from hyperspace_tpu.execution import pipeline_compiler as PC
    from hyperspace_tpu.io.columnar import ColumnarBatch

    if stats is None:
        stats = dict.fromkeys(_FILE_STATS, 0)
    with _outside(turn, stats, "read_s"):
        pf = pq.ParquetFile(path)
    schema = pf.schema_arrow
    count_only, numeric = _capture_spec(schema)
    ops, slots = _capture_ops(count_only, numeric)
    base = os.path.basename(path)
    entry: dict = {
        "rg_rows": [],
        "cols": {c: {k: [] for k in ("cnt",)} for c in count_only},
        "groups": {},
    }
    for c, f64 in numeric:
        entry["cols"][c] = {
            k: []
            for k in (
                ("cnt", "f64", "sum", "min", "max", "clean", "nan")
                if f64
                else ("cnt", "f64", "sum", "min", "max")
            )
        }
    key_candidates = [c for c, _f in numeric]
    if group_keys is not None:
        wanted = {k.lower() for k in group_keys}
        key_candidates = [c for c in key_candidates if c.lower() in wanted]
    for c in key_candidates:
        entry["groups"][c] = []
    ungrouped = _sweep_plan(PC, schema, None, ops)
    by_key = {kc: _sweep_plan(PC, schema, kc, ops) for kc in key_candidates}
    samples: List[pa.Table] = []
    if row_groups is None:
        row_groups = range(pf.metadata.num_row_groups)
    for gi in row_groups:
        with _outside(turn, stats, "read_s"):
            table = pf.read_row_group(gi)
        batch = ColumnarBatch.from_arrow(table)
        n = batch.num_rows
        entry["rg_rows"].append(n)
        cols = _partials_to_cols(
            _sweep(PC, ungrouped, batch, 1, stats, turn), slots
        )
        for c, cell in cols.items():
            dst = entry["cols"][c]
            for k, vals in cell.items():
                if k == "f64":
                    dst["f64"] = vals
                    continue
                dst[k].append(vals[0] if vals else None)
        # single-key grouped partials per candidate column under the cap.
        # A 4·cap-row PREFIX probe (canonical key_rep over a prefix
        # slice, O(cap) not O(rows)) rejects high-cardinality columns
        # cheaply — a prefix can only UNDER-count distincts, so it never
        # rejects an eligible column; the sweep then decides exactly,
        # and stops at the first group past the cap.
        for kc in key_candidates:
            if n == 0 or max_groups <= 0:
                entry["groups"][kc].append(None)
                continue
            col = batch.column(kc)
            m = min(n, 4 * max_groups)
            probe = col.take(np.arange(m)).key_rep()
            if len(np.unique(probe)) > max_groups:
                entry["groups"][kc].append(None)
                continue
            gpt = _sweep(PC, by_key[kc], batch, max_groups, stats, turn)
            if gpt is None:
                entry["groups"][kc].append(None)
                continue
            gcols = _partials_to_cols(gpt, slots)
            gentry: dict = {
                "kv": gpt.g_kvals[0].tolist(),
                "n": gpt.acc_cnt[0].tolist(),
                "cols": gcols,
            }
            if gpt.key_has_validity[0]:
                gentry["kn"] = gpt.g_kvalid[0].tolist()
            entry["groups"][kc].append(gentry)
        if sample_rows > 0 and n > 0:
            k = min(sample_rows, n)
            idx = np.sort(_sample_rng(base, gi).choice(n, size=k, replace=False))
            sampled = table.take(idx)
            sampled = sampled.add_column(
                0, "__rg", pa.array(np.full(k, gi, dtype=np.int32))
            )
            sampled = sampled.add_column(
                0, "__file", pa.array([base] * k, type=pa.string())
            )
            samples.append(sampled)
    return entry, samples


def _whole_file(
    ranges: List[Tuple[dict, List[pa.Table]]],
) -> Tuple[dict, Optional[pa.Table]]:
    """One file's (entry, sample table) from what :func:`_range_agg_doc`
    gave for its ranges, in row-group order: the per-row-group lists end
    to end (``f64`` is a scalar), then what only the whole file decides
    — a grouped candidate is dropped where it is None in EVERY row
    group; over the cap in some ranges and under it in others, it stays
    — and the one concatenation of the row groups' samples."""
    entry, samples = ranges[0]
    for more, more_samples in ranges[1:]:
        entry["rg_rows"] += more["rg_rows"]
        for c, cell in more["cols"].items():
            dst = entry["cols"][c]
            for k, vals in cell.items():
                if k != "f64":
                    dst[k] += vals
        for kc, cells in more["groups"].items():
            entry["groups"][kc] += cells
        samples += more_samples
    # prune all-None grouped candidates (over-cap everywhere)
    entry["groups"] = {
        k: v for k, v in entry["groups"].items() if any(e is not None for e in v)
    }
    sample_table = (
        pa.concat_tables(samples, promote_options="permissive")
        if samples
        else None
    )
    return entry, sample_table


# ---------------------------------------------------------------------------
# Capture (build/refresh/optimize time)
# ---------------------------------------------------------------------------


def capture_index_dir(dir_path: str, index, conf=None) -> bool:
    """Write the ``_aggstate.json`` + ``_aggsample.parquet`` sidecars for
    one freshly-written index version directory (covering-family indexes
    only, like zone maps). Atomic publish with the crash seam
    ``mid_sidecar_publish`` armed before each replace — a crash here
    fails the surrounding action op(), which recovery rolls back; the
    sidecar is either absent (lazy backfill covers it) or complete."""
    kind = getattr(index, "kind", "")
    if kind not in ("CoveringIndex", "ZOrderCoveringIndex"):
        return False
    if conf is not None and not conf.index_agg_enabled:
        return False
    max_groups = (
        conf.index_agg_max_groups
        if conf is not None
        else C.INDEX_AGG_MAX_GROUPS_DEFAULT
    )
    sample_rows = (
        conf.index_agg_sample_rows
        if conf is not None
        else C.INDEX_AGG_SAMPLE_ROWS_DEFAULT
    )
    from hyperspace_tpu.indexes import covering_build

    # build-tail I/O: one stage of the build's account. Its parts are
    # attrs of the one span, never a span per file: the pool's wall
    # (files_s, on so many workers), what the tasks' seconds went to —
    # Python under the turn, the reads and the sweeps outside it, the
    # wait for it (python_s, read_s, sweep_s, turn_wait_s) — the passes
    # by kind, and the publish (publish_s, bytes); the work is a call of
    # its own so that freeing the document is inside the stage
    with covering_build.stage("sidecar_capture", sidecar="aggstate") as sp:
        return _capture_files(dir_path, max_groups, sample_rows, sp)


#: a range task's grain, ``native._n_threads``' (a thread per ~64k
#: rows): a file is cut only where its footer shows two such tasks
_TASK_ROWS = 1 << 16
#: ranges a worker where files are cut: a few, so that the workers that
#: run out first at the tail wait for a quarter of a share and not a
#: whole one, and files that do not divide the workers still fill them;
#: each costs a ``pq.ParquetFile`` of its own (``read_s``). On the
#: chip's 13-core host, a 16M-row file of 245 row groups (PERF.md §6,
#: PR 37), the pool's wall: 1.04–1.25 s at 1 a worker, 1.13–1.30 at 2
#: and at 4, 1.19–1.40 at 8, 1.40–1.51 at a range a row group (4.1 on
#: one worker): the turn paces the tasks, so no tail shows at 1
_RANGES_PER_WORKER = 4


def _plan_tasks(files: List[str]) -> Tuple[List[Tuple[str, Optional[range]]], int]:
    """(the pool's tasks, files cut into ranges). A task is a file —
    ``(path, None)`` — wherever the files alone fill the workers
    (``native.core_budget()``; no footer is opened then). With fewer
    files than that (a z-order build's one file, an optimize's few
    large ones) the footers say which hold more than a task's rows, and
    such a file becomes contiguous row-group ranges of about equal
    length, ``_RANGES_PER_WORKER`` a worker over all the files; a small
    refresh's few small files stay a task each."""
    from hyperspace_tpu import native

    budget = native.core_budget()
    if len(files) >= budget:
        return [(f, None) for f in files], 0
    most = -(-_RANGES_PER_WORKER * budget // len(files))
    tasks: List[Tuple[str, Optional[range]]] = []
    split_files = 0
    for f in files:
        md = pq.read_metadata(f)
        n = min(md.num_row_groups, md.num_rows // _TASK_ROWS, most)
        if n < 2:
            tasks.append((f, None))
            continue
        split_files += 1
        cuts = [md.num_row_groups * i // n for i in range(n + 1)]
        tasks.extend((f, range(lo, hi)) for lo, hi in zip(cuts, cuts[1:]))
    return tasks, split_files


def _map_tasks(fn, tasks: list):
    """(``[fn(t) for t in tasks]``, workers): on a bounded pool of this
    call's own, as ``io/parquet._pool_map`` is — inline up to 4 tasks (a
    small refresh), and never ``scan_pool``, whose tasks may not wait on
    each other. ``workers`` goes on the capture's span beside
    ``files_s``, the wall of this call: ``workers * files_s`` is the
    pool's thread seconds, which the tasks' ``python_s + turn_wait_s +
    sweep_s + read_s`` fill but for workers idle at the tail."""
    from hyperspace_tpu import native

    if len(tasks) <= 4:
        return [fn(t) for t in tasks], 1
    from concurrent.futures import ThreadPoolExecutor

    workers = min(native.core_budget(), len(tasks))
    with ThreadPoolExecutor(
        max_workers=workers, thread_name_prefix="hs-aggcapture"
    ) as pool:
        return list(pool.map(fn, tasks)), workers


def _capture_files(
    dir_path: str, max_groups: int, sample_rows: int, sp
) -> bool:
    from hyperspace_tpu.indexes import covering_build
    from hyperspace_tpu.io import parquet as pio

    try:
        files = pio.list_format_files(dir_path, "parquet")
    except (OSError, KeyError):
        return False
    if not files:
        return False

    turn = threading.Lock()

    def task_doc(task: Tuple[str, Optional[range]]):
        f, row_groups = task
        stats = dict.fromkeys(_FILE_STATS, 0)
        _take(turn, stats)
        try:
            waited, t0 = stats["turn_wait_s"], _time.perf_counter()
            if row_groups is None:
                part = file_agg_doc(
                    f, max_groups, sample_rows, stats=stats, turn=turn
                )
            else:
                part = _range_agg_doc(
                    f, row_groups, max_groups, sample_rows, stats=stats, turn=turn
                )
            held_s = _time.perf_counter() - t0
        finally:
            turn.release()
        # from its first taking the turn a task's seconds are one of
        # three things: a call outside it, the wait to have it back, or
        # Python with it held — the last is what is left of them
        stats["python_s"] = held_s - (
            stats["read_s"] + stats["sweep_s"] + stats["turn_wait_s"] - waited
        )
        return part, stats

    # the row groups are independent, and their reads and sweeps run
    # outside the interpreter lock: one task a file, or a range of a
    # file's row groups where the files are fewer than the workers, the
    # Python of the tasks by turns; the document and the sample table
    # take the order of ``files`` and of a file's row groups whatever
    # order the tasks finish in
    t_files = _time.perf_counter()
    tasks, split_files = _plan_tasks(files)
    docs, workers = _map_tasks(task_doc, tasks)
    parts, stats = zip(*docs)
    entries, sample_tables = {}, []
    for f, of_file in itertools.groupby(
        zip(tasks, parts), key=lambda task_part: task_part[0][0]
    ):
        of_file = list(of_file)
        (_f, row_groups), part = of_file[0]
        if row_groups is not None:
            part = _whole_file([p for _task, p in of_file])
        entry, sample = part
        st = os.stat(f)
        entry["size"] = st.st_size
        entry["mtime_ns"] = st.st_mtime_ns
        entries[os.path.basename(f)] = entry
        if sample is not None:
            sample_tables.append(sample)
    t_publish = _time.perf_counter()
    doc: dict = {"version": _SIDECAR_VERSION, "files": entries}
    sp.set("files", len(files))
    sp.set("tasks", len(tasks))
    sp.set("split_files", split_files)
    sp.set("workers", workers)
    sp.set("files_s", round(t_publish - t_files, 6))
    # sums over the tasks, like sum_s. python_s, the seconds the turn
    # was held, is serial by construction: a floor of files_s
    for k in ("python_s", *_FILE_STATS):
        sp.set(k, round(sum(st[k] for st in stats), 6))
    side_path = os.path.join(dir_path, SIDECAR_NAME)
    tmp = os.path.join(dir_path, f".{SIDECAR_NAME}.tmp.{os.getpid()}")
    try:
        with open(tmp, "w", encoding="utf-8") as fh:
            # one C-encoder pass: json.dump streams the same text through
            # the Python encoder, five times slower on a 4.5 MB document
            fh.write(json.dumps(doc))
            fh.flush()
            os.fsync(fh.fileno())
        faults.crash("mid_sidecar_publish", side_path)
        os.replace(tmp, side_path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False
    written = [side_path]
    if sample_tables:
        sample_path = os.path.join(dir_path, SAMPLE_NAME)
        stmp = os.path.join(dir_path, f".{SAMPLE_NAME}.tmp.{os.getpid()}")
        try:
            pq.write_table(
                pa.concat_tables(sample_tables, promote_options="permissive"),
                stmp,
            )
            faults.crash("mid_sidecar_publish", sample_path)
            os.replace(stmp, sample_path)
            written.append(sample_path)
        except OSError:
            try:
                os.unlink(stmp)
            except OSError:
                pass
    from hyperspace_tpu.utils.files import fsync_dir

    fsync_dir(dir_path)
    covering_build.sidecar_published(
        sp, written, _time.perf_counter() - t_publish
    )
    return True


def capture_safely(dir_path: str, index, conf=None) -> None:
    """The actions' capture entry: the sidecar is a precomputed
    optimization (the serve path lazily backfills without it), so no
    capture failure may ever fail a build/refresh/optimize."""
    try:
        capture_index_dir(dir_path, index, conf)
    except Exception as exc:  # hslint: disable=HS402
        _log.warning("aggstate capture failed for %s: %s", dir_path, exc)


def prune_missing(dir_path: str) -> None:
    """Vacuum support: rewrite the sidecars of a RETAINED version dir to
    drop entries/rows describing files that no longer exist (the sidecar
    travels with the files it describes; the whole dir's sidecars die
    with the dir). Best-effort — stale entries are also defused by the
    per-file (size, mtime_ns) freshness check at assembly."""
    side_path = os.path.join(dir_path, SIDECAR_NAME)
    try:
        with open(side_path, "r", encoding="utf-8") as fh:
            doc = json.load(fh)
        kept = {
            base: entry
            for base, entry in doc.get("files", {}).items()
            if os.path.exists(os.path.join(dir_path, base))
        }
        if len(kept) != len(doc.get("files", {})):
            if kept:
                doc["files"] = kept
                # the shared fsync-before-replace publish (the
                # calibrate._store_cache pattern, testing/artifacts.py):
                # a crash right after vacuum must not tear the rewrite
                from hyperspace_tpu.testing.artifacts import atomic_write_json

                atomic_write_json(side_path, doc)
            else:
                os.unlink(side_path)
    except (OSError, ValueError):
        pass
    sample_path = os.path.join(dir_path, SAMPLE_NAME)
    try:
        if os.path.exists(sample_path):
            table = pq.read_table(sample_path)
            bases = table.column("__file").to_pylist()
            keep = np.array(
                [os.path.exists(os.path.join(dir_path, b)) for b in bases]
            )
            if not keep.all():
                if keep.any():
                    tmp = sample_path + f".tmp.{os.getpid()}"
                    pq.write_table(table.filter(pa.array(keep)), tmp)
                    os.replace(tmp, sample_path)
                else:
                    os.unlink(sample_path)
    except (OSError, ValueError, KeyError, pa.ArrowInvalid):
        pass


# ---------------------------------------------------------------------------
# Sidecar read + lazy backfill (memoized per file identity)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def _sidecar_cached(path: str, _size: int, _mtime_ns: int) -> Optional[dict]:
    try:
        with open(path, "r", encoding="utf-8") as fh:
            data = json.load(fh)
    except (OSError, ValueError):
        return None
    if data.get("version") != _SIDECAR_VERSION:
        return None
    return data


def _sidecar_for_dir(dirpath: str) -> Optional[dict]:
    path = os.path.join(dirpath, SIDECAR_NAME)
    try:
        st = os.stat(path)
    except OSError:
        return None
    return _sidecar_cached(path, st.st_size, st.st_mtime_ns)


@functools.lru_cache(maxsize=16)
def _backfill_cached(
    path: str,
    _size: int,
    _mtime_ns: int,
    keys: Optional[Tuple[str, ...]] = None,
    max_groups: int = C.INDEX_AGG_MAX_GROUPS_DEFAULT,
    sample_rows: int = C.INDEX_AGG_SAMPLE_ROWS_DEFAULT,
):
    """Lazy backfill for a file without a fresh sidecar entry: compute
    the same doc (and sample) by reading the file once. Keyed by file
    identity — a rewritten file gets a fresh computation — plus the
    grouped-key restriction and the session's capture knobs, so a
    differently-configured serve never reads stale-shaped state."""
    return file_agg_doc(path, max_groups, sample_rows, keys)


def _entry_for_file(
    path: str,
    side: Optional[dict],
    keys: Optional[Tuple[str, ...]],
    max_groups: int,
    sample_rows: int,
):
    """(entry, from_sidecar) — this file's sidecar entry when present
    AND stat-fresh, else the lazily-backfilled computation; (None, False)
    when the file is unreadable (caller scans it as PARTIAL)."""
    try:
        st = os.stat(path)
    except OSError:
        return None, False
    if side is not None:
        entry = side.get("files", {}).get(os.path.basename(path))
        if (
            entry is not None
            and entry.get("size") == st.st_size
            and entry.get("mtime_ns") == st.st_mtime_ns
        ):
            return entry, True
    try:
        entry, _sample = _backfill_cached(
            path, st.st_size, st.st_mtime_ns, keys, max_groups, sample_rows
        )
        return entry, False
    except Exception as exc:  # hslint: disable=HS402
        # backfill is best-effort extra coverage: any failure (exotic
        # dtype, I/O error) must only cost the metadata answer, never
        # the query
        _log.warning("aggstate backfill failed for %s: %s", path, exc)
        return None, False


# ---------------------------------------------------------------------------
# Serve-side assembly
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class AggData:
    """Decoded aggregate-state of one file set, assembled once and
    cached (ServeCache kind ``("aggstate", fp)`` when serve-server mode
    is on, else the module LRU). ``backfill_keys`` records which grouped
    keys any backfilled portion was restricted to (lowercase; None =
    unrestricted) — a cache hit only serves a query whose key that
    covers, so a key-restricted first backfill can never starve a later
    query on a different key."""

    files: Tuple[str, ...]
    per_file: list  # decoded per-file dict, or None (unreadable)
    sidecar_files: int
    backfill_files: int
    nbytes: int
    backfill_keys: Optional[frozenset] = None
    # per file: True when the entry came from a STAT-FRESH sidecar (the
    # sample plane trusts _aggsample.parquet rows only for these — a
    # rewritten file's samples must come from backfill, not the old dir
    # sidecar)
    per_file_sidecar: Tuple[bool, ...] = ()

    def covers_key(self, group_key: Optional[str]) -> bool:
        if self.backfill_files == 0 or group_key is None:
            return True
        if self.backfill_keys is None:
            return True  # unrestricted backfill: all candidates captured
        return group_key.lower() in self.backfill_keys


def _decode_entry(entry: dict) -> Tuple[dict, int]:
    """Runtime (numpy) form of one stored file entry + a byte estimate."""
    rg_rows = [int(r) for r in entry.get("rg_rows", [])]
    cols: Dict[str, dict] = {}
    scalars = 0
    for name, st in entry.get("cols", {}).items():
        cnt = _dec_i64_arr(st.get("cnt", []), 0)
        scalars += len(cnt)
        if "sum" not in st:
            cols[name] = {"cnt": cnt}
            continue
        f64 = bool(st.get("f64"))
        d: dict = {"cnt": cnt, "is_f64": f64}
        if f64:
            d["sum"] = _dec_f64_arr(st["sum"], 0.0)
            d["min"] = _dec_f64_arr(st["min"], np.inf)
            d["max"] = _dec_f64_arr(st["max"], -np.inf)
            d["clean"] = _dec_i64_arr(st.get("clean", []), 0)
            d["nan"] = _dec_i64_arr(st.get("nan", []), 0)
        else:
            d["sum"] = _dec_i64_arr(st["sum"], 0)
            d["min"] = _dec_i64_arr(st["min"], _I64_MAX)
            d["max"] = _dec_i64_arr(st["max"], _I64_MIN)
        scalars += 5 * len(cnt)
        cols[name] = d
    groups: Dict[str, list] = {}
    for kc, per_rg in entry.get("groups", {}).items():
        decoded = []
        for g in per_rg:
            if g is None:
                decoded.append(None)
                continue
            gcols: Dict[str, dict] = {}
            for name, st in g.get("cols", {}).items():
                cnt = _dec_i64_arr(st.get("cnt", []), 0)
                if "sum" not in st:
                    gcols[name] = {"cnt": cnt}
                elif st.get("f64"):
                    gcols[name] = {
                        "cnt": cnt,
                        "is_f64": True,
                        "sum": _dec_f64_arr(st["sum"], 0.0),
                        "min": _dec_f64_arr(st["min"], np.inf),
                        "max": _dec_f64_arr(st["max"], -np.inf),
                        "clean": _dec_i64_arr(st.get("clean", []), 0),
                        "nan": _dec_i64_arr(st.get("nan", []), 0),
                    }
                else:
                    gcols[name] = {
                        "cnt": cnt,
                        "is_f64": False,
                        "sum": _dec_i64_arr(st["sum"], 0),
                        "min": _dec_i64_arr(st["min"], _I64_MAX),
                        "max": _dec_i64_arr(st["max"], _I64_MIN),
                    }
                scalars += 6 * len(cnt)
            decoded.append(
                {
                    "kv": np.array(g["kv"], dtype=np.int64),
                    "kvalid": (
                        np.array(g["kn"], dtype=np.uint8)
                        if "kn" in g
                        else None
                    ),
                    "n": np.array(g["n"], dtype=np.int64),
                    "cols": gcols,
                }
            )
            scalars += 2 * len(g.get("kv", []))
        groups[kc.lower()] = decoded
    return (
        {"rg_rows": rg_rows, "cols": cols, "groups": groups},
        64 + 8 * scalars,
    )


# Module-level bounded LRU for assembled agg data, so the metadata plane
# works at full speed with serve-server mode OFF (the default). Keyed by
# the file fingerprint, same staleness story as the ServeCache entries.
# Bounded in BYTES as well as entries — AggData carries its own decoded
# size (data.nbytes) and grouped partials over wide relations are not
# small, so an entry cap alone is not a residency bound (ALLOC_SITES
# doctrine, memory.py); _local_bytes is the ledger.
# SHARED_STATE-registered ("guarded": every access under _local_lock).
_local_lock = threading.Lock()
_local_cache: "OrderedDict[tuple, AggData]" = OrderedDict()
_local_bytes = 0
_LOCAL_CACHE_ENTRIES = 32
_LOCAL_CACHE_MAX_BYTES = 128 << 20


def _local_put(key, data: "AggData") -> None:
    """Insert into the module LRU, evicting oldest-first until both the
    entry cap and the byte cap hold. Caller must NOT hold _local_lock."""
    global _local_bytes
    nbytes = int(data.nbytes)
    if nbytes > _LOCAL_CACHE_MAX_BYTES:
        return  # larger than the whole fallback cache: not cacheable
    with _local_lock:
        old = _local_cache.pop(key, None)
        if old is not None:
            _local_bytes -= int(old.nbytes)
        while _local_cache and (
            len(_local_cache) >= _LOCAL_CACHE_ENTRIES
            or _local_bytes + nbytes > _LOCAL_CACHE_MAX_BYTES
        ):
            _, victim = _local_cache.popitem(last=False)
            _local_bytes -= int(victim.nbytes)
        _local_cache[key] = data
        _local_bytes += nbytes


def agg_data_for(
    rel, cache=None, conf=None, group_key: Optional[str] = None
) -> Optional[AggData]:
    """Assembled aggregate-state for a relation's file set, from the
    serve cache / module LRU, sidecars, or lazy backfill. ``conf``
    supplies the capture knobs for backfill (defaults otherwise);
    ``group_key`` restricts any backfill's grouped sweep to the one key
    this query needs (a first serve over an unsidecar'd index pays one
    grouped pass, not one per numeric column). None when the files
    cannot be fingerprinted (caller skips the plane)."""
    from hyperspace_tpu.execution.serve_cache import file_fingerprint

    fp = file_fingerprint(rel.files)
    if fp is None:
        return None
    key = ("aggstate", fp)
    if cache is not None:
        hit = cache.get(key)
        if hit is not None and hit.covers_key(group_key):
            return hit
    with _local_lock:
        hit = _local_cache.get(key)
        if hit is not None and hit.covers_key(group_key):
            _local_cache.move_to_end(key)
            return hit
    max_groups = (
        conf.index_agg_max_groups
        if conf is not None
        else C.INDEX_AGG_MAX_GROUPS_DEFAULT
    )
    sample_rows = (
        conf.index_agg_sample_rows
        if conf is not None
        else C.INDEX_AGG_SAMPLE_ROWS_DEFAULT
    )
    bf_keys: Tuple[str, ...] = () if group_key is None else (group_key.lower(),)
    if not bf_keys:
        # no grouped capture wanted: normalize the cap so ungrouped
        # backfills (exact plane and sample assembly) share one memo
        max_groups = 0
    side_by_dir: Dict[str, Optional[dict]] = {}
    per_file: list = []
    provenance: list = []
    nbytes = 256
    sidecar_n = backfill_n = 0
    for path in rel.files:
        d = os.path.dirname(path)
        if d not in side_by_dir:
            side_by_dir[d] = _sidecar_for_dir(d)
        entry, from_sidecar = _entry_for_file(
            path, side_by_dir[d], bf_keys, max_groups, sample_rows
        )
        provenance.append(bool(from_sidecar))
        if entry is None:
            per_file.append(None)
            continue
        decoded, nb = _decode_entry(entry)
        per_file.append(decoded)
        nbytes += nb
        if from_sidecar:
            sidecar_n += 1
        else:
            backfill_n += 1
    data = AggData(
        files=tuple(rel.files),
        per_file=per_file,
        sidecar_files=sidecar_n,
        backfill_files=backfill_n,
        nbytes=nbytes,
        backfill_keys=frozenset(bf_keys) if backfill_n else None,
        per_file_sidecar=tuple(provenance),
    )
    if cache is not None:
        cache.put(key, data, data.nbytes)
    _local_put(key, data)
    return data


def invalidate_local_cache() -> None:
    """Tests / operational tooling: drop the module-level assembled
    cache (sidecar/backfill memos are keyed by file identity and never
    serve stale)."""
    global _local_bytes
    with _local_lock:
        _local_cache.clear()
        _local_bytes = 0


def invalidate_paths_under(root: str) -> int:
    """Drop only the LRU entries whose fingerprint names a file under
    ``root`` — the fleet fanout's scoped invalidation (``serve/bus.py``;
    same contract as ``zonemaps.invalidate_paths_under``): reclaim the
    changed index's dead-version memory without costing other indexes
    their warm assembled state."""
    prefix = root.replace("\\", "/").rstrip("/") + "/"

    def _mentions(obj) -> bool:
        if isinstance(obj, str):
            return obj.replace("\\", "/").startswith(prefix)
        if isinstance(obj, tuple):
            return any(_mentions(x) for x in obj)
        return False

    global _local_bytes
    with _local_lock:
        victims = [k for k in _local_cache if _mentions(k)]
        for k in victims:
            victim = _local_cache.pop(k)
            _local_bytes -= int(victim.nbytes)
        return len(victims)


# ---------------------------------------------------------------------------
# Fleet fanout (docs/fleet-serve.md): metadata answers are tiny and
# version-addressed, so a refresh/optimize PUSHES the new version's
# aggregate state to peer frontends instead of invalidating it — the
# peers' first point aggregate over the new snapshot folds straight from
# RAM without even the sidecar read.
# ---------------------------------------------------------------------------


def fanout_payload(files) -> Optional[dict]:
    """JSON-safe push payload for one committed file set: the raw
    per-file sidecar entries plus the file fingerprint the receivers key
    by. None unless EVERY file has a stat-fresh sidecar entry — a
    partial push would make the receiver's assembly lie about coverage,
    and the lazy re-read path covers the gap anyway."""
    from hyperspace_tpu.execution.serve_cache import file_fingerprint

    files = tuple(files)
    if not files:
        return None
    fp = file_fingerprint(files)
    if fp is None:
        return None
    side_by_dir: Dict[str, Optional[dict]] = {}
    entries: Dict[str, dict] = {}
    for path in files:
        d = os.path.dirname(path)
        if d not in side_by_dir:
            side_by_dir[d] = _sidecar_for_dir(d)
        side = side_by_dir[d]
        if side is None:
            return None
        entry = side.get("files", {}).get(os.path.basename(path))
        try:
            st = os.stat(path)
        except OSError:
            return None
        if (
            entry is None
            or entry.get("size") != st.st_size
            or entry.get("mtime_ns") != st.st_mtime_ns
        ):
            return None
        entries[path] = entry
    return {
        "files": list(files),
        "fp": [[p, s, m] for p, s, m in fp],
        "entries": entries,
    }


def install_fanout_payload(payload: dict, cache=None) -> bool:
    """Install a pushed payload into this process's caches under
    ``("aggstate", fp)``. Validates the fingerprint against the CURRENT
    on-disk stats first — a stale push (the files changed again before
    this frontend polled) would be cached under an unreachable key, so
    it is dropped instead. Returns whether the install happened."""
    from hyperspace_tpu.execution.serve_cache import file_fingerprint

    try:
        files = tuple(str(f) for f in payload["files"])
        fp = tuple((str(p), int(s), int(m)) for p, s, m in payload["fp"])
        raw_entries = payload["entries"]
    except (KeyError, TypeError, ValueError):
        return False
    if not files or file_fingerprint(files) != fp:
        return False
    per_file: list = []
    nbytes = 256
    try:
        for path in files:
            decoded, nb = _decode_entry(raw_entries[path])
            per_file.append(decoded)
            nbytes += nb
    except (KeyError, TypeError, ValueError):
        return False
    data = AggData(
        files=files,
        per_file=per_file,
        sidecar_files=len(files),
        backfill_files=0,
        nbytes=nbytes,
        backfill_keys=None,
        per_file_sidecar=(True,) * len(files),
    )
    key = ("aggstate", fp)
    if cache is not None:
        cache.put(key, data, data.nbytes)
    _local_put(key, data)
    return True


# ---------------------------------------------------------------------------
# Classification: FULL / EMPTY / PARTIAL per selected row group
# ---------------------------------------------------------------------------


def _zone_verdict(st: Optional[dict], gi: int, iv, rows: int) -> str:
    """One conjunct column's verdict for one row group: "empty" (no row
    can satisfy it), "full" (every row provably satisfies it) or
    "partial" (undecidable at this granularity). Directed rounding:
    OUTWARD for the empty test (the zone-map keep rule), INWARD for the
    full test — rounding can only demote toward "partial"."""
    from hyperspace_tpu.indexes.zonemaps import f64_down, f64_up

    if iv.empty:
        return "empty"
    if st is None or "sum" not in st and "min" not in st:
        return "partial"  # count-only column (string/bool/narrow): abstain
    cnt = int(st["cnt"][gi]) if gi < len(st["cnt"]) else None
    if cnt is None:
        return "partial"
    if cnt == 0:
        return "empty"  # all-null group: no row satisfies a comparison
    is_f64 = bool(st.get("is_f64"))
    if is_f64:
        clean = int(st["clean"][gi])
        if clean == 0:
            return "empty"  # every valid value is NaN: all rows fail
    lo_v = st["min"][gi]
    hi_v = st["max"][gi]
    lo_r = f64_down(lo_v.item() if isinstance(lo_v, np.generic) else lo_v)
    hi_r = f64_up(hi_v.item() if isinstance(hi_v, np.generic) else hi_v)
    if iv.lo is not None:
        b = f64_down(iv.lo)
        keep = hi_r > b if iv.lo_strict else hi_r >= b
        if not keep:
            return "empty"
    if iv.hi is not None:
        b = f64_up(iv.hi)
        keep = lo_r < b if iv.hi_strict else lo_r <= b
        if not keep:
            return "empty"
    full = cnt == rows and (not is_f64 or int(st["nan"][gi]) == 0)
    if full and iv.lo is not None:
        b = f64_up(iv.lo)
        full = lo_r > b if iv.lo_strict else lo_r >= b
    if full and iv.hi is not None:
        b = f64_down(iv.hi)
        full = hi_r < b if iv.hi_strict else hi_r <= b
    return "full" if full else "partial"


def _op_available(op: int, cname: Optional[str], cols: Dict[str, dict]) -> bool:
    from hyperspace_tpu.execution import pipeline_compiler as PC

    if op == PC._OP_COUNT_STAR:
        return True
    st = cols.get(cname)
    if st is None or "cnt" not in st:
        return False
    if op == PC._OP_COUNT_COL:
        return True
    return "sum" in st


def classify_row_groups(
    data: AggData, rel, ivs: Dict[str, Any], key: Optional[str], fplan
) -> Optional[List[Tuple[int, Optional[int], str]]]:
    """Per selected (file, row group): "full" | "empty" | "partial", in
    the interpreted chain's read order. A FULL verdict additionally
    requires the stored partials the lowering needs (grouped entry for
    ``key``, per-column state for every agg input) — missing state
    demotes to "partial" (scan), never to a wrong answer. Files without
    usable state classify as one whole-file "partial" cell."""
    key_lower = key.lower() if key is not None else None
    cells: List[Tuple[int, Optional[int], str]] = []
    groups_sel = rel.file_row_groups or (None,) * len(rel.files)
    for fi, path in enumerate(rel.files):
        pf = data.per_file[fi]
        if pf is None:
            cells.append((fi, None, "partial"))
            continue
        n_rg = len(pf["rg_rows"])
        sel = groups_sel[fi]
        rgs = sel if sel is not None else range(n_rg)
        for gi in rgs:
            if gi >= n_rg:
                cells.append((fi, gi, "partial"))
                continue
            rows = pf["rg_rows"][gi]
            if rows == 0:
                cells.append((fi, gi, "empty"))
                continue
            kind = "full"
            for col, iv in ivs.items():
                v = _zone_verdict(pf["cols"].get(col), gi, iv, rows)
                if v == "empty":
                    kind = "empty"
                    break
                if v == "partial":
                    kind = "partial"
            if kind == "full":
                if key_lower is not None:
                    glist = pf["groups"].get(key_lower)
                    g = (
                        glist[gi]
                        if glist is not None and gi < len(glist)
                        else None
                    )
                    if g is None or not all(
                        _op_available(op, c, g["cols"])
                        for op, c in fplan.agg_ops
                    ):
                        kind = "partial"
                elif not all(
                    _op_available(op, c, pf["cols"])
                    for op, c in fplan.agg_ops
                ):
                    kind = "partial"
            cells.append((fi, gi, kind))
    return cells


# ---------------------------------------------------------------------------
# Stored state -> AggPartials (the fold input for FULL row groups)
# ---------------------------------------------------------------------------


def rg_partials(data: AggData, fi: int, gi: int, fplan, key: Optional[str]):
    """One FULL row group's stored partials as
    :class:`~hyperspace_tpu.execution.pipeline_compiler.AggPartials` —
    every row passes, so the stored unfiltered state IS the chunk state
    the sweep would have produced."""
    from hyperspace_tpu.execution import pipeline_compiler as PC
    from hyperspace_tpu.io.columnar import Column

    pf = data.per_file[fi]
    rows = pf["rg_rows"][gi]
    na = len(fplan.agg_ops)
    if key is None:
        G = 1
        g_reps = np.zeros((0, G), dtype=np.int64)
        g_nulls = np.zeros((0, G), dtype=np.uint8)
        g_kvals = np.zeros((0, G), dtype=np.int64)
        g_kvalid = np.ones((0, G), dtype=np.uint8)
        khv: Tuple[bool, ...] = ()

        def cell(col, field):
            return pf["cols"][col][field][gi : gi + 1]

        count_star = np.array([rows], dtype=np.int64)
    else:
        g = pf["groups"][key.lower()][gi]
        G = len(g["n"])
        kvals = g["kv"]
        kvalid = g["kvalid"]
        col = Column(
            "numeric",
            fplan.key_types[0],
            values=kvals.view(np.float64) if fplan.key_f64[0] else kvals,
            validity=None if kvalid is None else kvalid.astype(bool),
        )
        reps = col.key_rep()
        nm = col.null_mask
        g_reps = reps.reshape(1, G)
        g_nulls = (
            nm.astype(np.uint8) if nm is not None else np.zeros(G, np.uint8)
        ).reshape(1, G)
        g_kvals = kvals.reshape(1, G)
        g_kvalid = (
            kvalid if kvalid is not None else np.ones(G, dtype=np.uint8)
        ).reshape(1, G)
        khv = (kvalid is not None,)

        def cell(colname, field):
            return g["cols"][colname][field]

        count_star = g["n"]
    acc_i = np.zeros((na, G), dtype=np.int64)
    acc_f = np.zeros((na, G), dtype=np.float64)
    acc_cnt = np.zeros((na, G), dtype=np.int64)
    acc_aux = np.zeros((na, G), dtype=np.int64)
    for a, (op, c) in enumerate(fplan.agg_ops):
        if op == PC._OP_COUNT_STAR:
            acc_cnt[a] = count_star
            continue
        acc_cnt[a] = cell(c, "cnt")
        if op == PC._OP_COUNT_COL:
            continue
        if op == PC._OP_SUM_I64:
            acc_i[a] = cell(c, "sum")
        elif op == PC._OP_MIN_I64:
            acc_i[a] = cell(c, "min")
        elif op == PC._OP_MAX_I64:
            acc_i[a] = cell(c, "max")
        elif op == PC._OP_MIN_F64:
            acc_f[a] = cell(c, "min")
            acc_aux[a] = cell(c, "clean")
        elif op == PC._OP_MAX_F64:
            acc_f[a] = cell(c, "max")
            acc_aux[a] = cell(c, "nan")
        else:  # pragma: no cover — the lowering filtered ops already
            return None
    return PC.AggPartials(
        n_groups=G,
        rows_scanned=0,
        rows_passed=int(rows),
        g_reps=g_reps,
        g_nulls=g_nulls,
        g_kvals=g_kvals,
        g_kvalid=g_kvalid,
        key_has_validity=khv,
        acc_i=acc_i,
        acc_f=acc_f,
        acc_cnt=acc_cnt,
        acc_aux=acc_aux,
    )


# ---------------------------------------------------------------------------
# Stratified samples for the approximate plane
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=16)
def _sample_table_cached(path: str, _size: int, _mtime_ns: int) -> Optional[pa.Table]:
    try:
        return pq.read_table(path)
    except (OSError, pa.ArrowInvalid):
        return None


def _sample_table_for_dir(dirpath: str) -> Optional[pa.Table]:
    path = os.path.join(dirpath, SAMPLE_NAME)
    try:
        st = os.stat(path)
    except OSError:
        return None
    return _sample_table_cached(path, st.st_size, st.st_mtime_ns)


def sample_data_for(rel, conf=None) -> Optional[dict]:
    """Stratified sample over a relation's file set for the approximate
    plane: ``{"table": pa.Table (sample rows, file order), "stratum":
    int array per sample row, "N": rows per stratum, "n": sampled rows
    per stratum}``. Strata are (file, row group). None when any file has
    neither a sample sidecar nor a computable backfill."""
    data = agg_data_for(rel, None, conf, None)
    if data is None:
        return None
    sample_rows = (
        conf.index_agg_sample_rows
        if conf is not None
        else C.INDEX_AGG_SAMPLE_ROWS_DEFAULT
    )
    tables: List[pa.Table] = []
    stratum_ids: List[np.ndarray] = []
    N: List[int] = []
    n: List[int] = []
    sample_by_dir: Dict[str, Optional[pa.Table]] = {}
    for fi, path in enumerate(rel.files):
        pf = data.per_file[fi]
        if pf is None:
            return None
        d = os.path.dirname(path)
        base = os.path.basename(path)
        if d not in sample_by_dir:
            sample_by_dir[d] = _sample_table_for_dir(d)
        stable = sample_by_dir[d]
        ftable = None
        # trust the dir's sample sidecar only for files whose AGGSTATE
        # entry was stat-fresh: a rewritten file must sample from the
        # backfill read, never from the old dir's rows
        fresh = (
            fi < len(data.per_file_sidecar) and data.per_file_sidecar[fi]
        )
        if fresh and stable is not None and "__file" in stable.column_names:
            mask = pa_compute.equal(stable.column("__file"), base)
            ftable = stable.filter(mask)
        if ftable is None or ftable.num_rows == 0:
            try:
                st = os.stat(path)
                _entry, ftable = _backfill_cached(
                    path, st.st_size, st.st_mtime_ns, (), 0, sample_rows
                )
            except Exception:  # hslint: disable=HS402
                ftable = None
        rg_rows = pf["rg_rows"]
        if ftable is None:
            if sum(rg_rows) == 0:
                continue  # empty file contributes no strata
            return None
        rgs = np.asarray(ftable.column("__rg"))
        for gi, rows in enumerate(rg_rows):
            if rows == 0:
                continue
            sel = np.nonzero(rgs == gi)[0]
            sid = len(N)
            N.append(int(rows))
            n.append(int(len(sel)))
            if len(sel):
                tables.append(
                    ftable.take(sel).drop_columns(["__file", "__rg"])
                )
                stratum_ids.append(np.full(len(sel), sid, dtype=np.int64))
    if not N:
        return None
    if any(v == 0 for v in n):
        return None  # a stratum with rows but no sample: not estimable
    table = pa.concat_tables(tables, promote_options="permissive")
    return {
        "table": table,
        "stratum": np.concatenate(stratum_ids),
        "N": np.asarray(N, dtype=np.int64),
        "n": np.asarray(n, dtype=np.int64),
    }
