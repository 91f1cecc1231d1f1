"""Fused serve-pipeline compiler (docs/serve-compiler.md).

The serve path used to execute a ``Filter→Project→Aggregate`` subtree as
a chain of individually-fast vectorized ops separated by materialized
numpy intermediates: evaluate the mask (2 passes/conjunct), ``nonzero``
it, gather EVERY needed column into a filtered copy, radix-lexsort the
group planes to factorize, then run one ``ufunc.at`` reduction per
aggregate. Flare's argument (PAPERS.md) is that the win comes from
compiling the *query's* pipeline end to end; this module does that for
the hottest serve shape: it detects the subtree over a pruned index
scan in ``executor._exec`` and lowers it to ONE fused native pass per
row-group chunk (``hs_fused_filter_agg``) that evaluates the conjunct
predicates, groups, and folds partial COUNT/SUM/MIN/MAX in a single
sweep — no mask, no filtered batch, no factorize. Partials are carried
across chunks (reads overlap compute on the shared ``scan_pool``) and
merged once at the edge. Plain ``Filter→Project`` lowers to a fused
select (``hs_fused_filter_select``): pass/fail and index compaction in
one pass, with the existing threaded native gathers doing the
projection.

Parity contract (the ``KERNEL_TWINS`` doctrine generalized from single
kernels to whole pipelines): the interpreted chain stays in place as
the differential twin (:func:`interpreted_filter_aggregate` /
:func:`filter_select_interpreted`), the fused pass is bit-identical to
it — including float-sum accumulation order (the kernel is deliberately
sequential over rows, exactly like ``np.add.at``), numpy's
replace-on-equal min/max rule, NULL/NaN/-0.0 group canonicalization
(``Column.key_rep``), group output order (ascending key-rep planes) and
first-occurrence group key values — and
``hyperspace.serve.fusedpipeline.enabled=false`` restores the old
op-at-a-time path. One scoped caveat: above ``_HOST_AGG_MAX_ROWS``
(1M FILTERED rows, ``ops/aggregate.py``) the interpreted chain itself
hands float sums to the device segment ops, which may reassociate —
there fused ≡ interpreted holds exactly for everything except float
SUM/AVG ulps, the same caveat the host/device switch already carries. Dispatch is calibrated per machine like every other
kernel (``native_fused_pipeline_min_rows``, probe v5).

Lowered shapes are cached in the serve cache under ``("fusedplan", …)``
keys (evictable via ``ServeCache.evict_kind``); anything outside the
supported shape — non-conjunct predicates, string/bool/sub-8-byte
group keys or aggregate inputs, hybrid unions, delete compensation —
falls back to the interpreted chain unchanged.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

from hyperspace_tpu import constants as C
from hyperspace_tpu.io.columnar import Column, ColumnarBatch
from hyperspace_tpu.plan import expressions as E
from hyperspace_tpu.plan.nodes import (
    Aggregate,
    Filter,
    Project,
    Scan,
    _agg_output_type,
)

# Telemetry of the LAST fused execution in this process (bench +
# tests assert the fused path actually ran): mode "agg" | "select",
# rows scanned vs rows passed, group count, chunk count, wall seconds.
last_fused_stats: Dict[str, Any] = {}

# Telemetry of the LAST metadata-plane aggregate (docs/agg-serve.md):
# how many row groups were answered from persisted partials vs scanned
# vs provably empty, and how many rows the boundary chunks actually read
# — the smoke gate asserts row_groups_scanned == 0 for a fully-covered
# point aggregate.
last_aggplane_stats: Dict[str, Any] = {}


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------

# At or above this SCANNED-row count the fused native pass dispatches;
# below it the interpreted chain's vectorized numpy twins win on
# kernel-call overhead. FALLBACK DEFAULT: the effective threshold comes
# from the per-machine calibration probe (native/calibrate.py, probe
# v5); an explicit module-attribute override wins (tests, bench A/B).
_NATIVE_FUSED_PIPELINE_MIN_ROWS_DEFAULT = C.NATIVE_FUSED_PIPELINE_MIN_ROWS_DEFAULT
_NATIVE_FUSED_PIPELINE_MIN_ROWS = _NATIVE_FUSED_PIPELINE_MIN_ROWS_DEFAULT


def _native_fused_pipeline_min_rows() -> int:
    if _NATIVE_FUSED_PIPELINE_MIN_ROWS != _NATIVE_FUSED_PIPELINE_MIN_ROWS_DEFAULT:
        return _NATIVE_FUSED_PIPELINE_MIN_ROWS  # explicit override wins
    from hyperspace_tpu.native import calibrate

    return (
        calibrate.thresholds().native_fused_pipeline_min_rows
        or _NATIVE_FUSED_PIPELINE_MIN_ROWS
    )


def fused_pipeline_on(session) -> bool:
    """``hyperspace.serve.fusedpipeline.enabled`` (default on). Like the
    range plane — and unlike the join pipeline's thread fan-out — this
    also applies to sessionless execution: the fused pass is a pure
    compute substitution with identical output."""
    if session is None:
        return C.SERVE_FUSEDPIPELINE_ENABLED_DEFAULT
    return session.conf.serve_fusedpipeline_enabled


# ---------------------------------------------------------------------------
# Type lowering
# ---------------------------------------------------------------------------


def _np_kind(t: pa.DataType) -> str:
    """The decoded numpy dtype KIND a column of arrow type ``t`` gets
    from ``Column.from_arrow`` — the pre-read half of the batch-based
    kind check in ``ops/filter.lower_range_terms``."""
    if pa.types.is_dictionary(t):
        t = t.value_type
    if pa.types.is_string(t) or pa.types.is_large_string(t):
        return "S"
    if pa.types.is_boolean(t):
        return "b"
    if pa.types.is_unsigned_integer(t):
        return "u"
    if pa.types.is_integer(t):
        return "i"
    if pa.types.is_floating(t):
        return "f"
    if pa.types.is_temporal(t):
        return "i"  # datetime64/timedelta64 int views, time32 → int32
    return "O"


def _fusable_f64(t: pa.DataType) -> Optional[bool]:
    """True → decodes to a float64 array, False → an 8-byte int64-view
    array (int64 / datetime64 / timedelta64), None → not fusable (the
    interpreted chain keeps the column). Mirrors ``Column.from_arrow``:
    time32 decodes to int32 (4 bytes), float32 stays float32 — both out."""
    if pa.types.is_float64(t):
        return True
    if pa.types.is_int64(t):
        return False
    if (
        pa.types.is_timestamp(t)
        or pa.types.is_date(t)
        or pa.types.is_duration(t)
        or pa.types.is_time64(t)
    ):
        return False
    return None


def _col_arr_8b(col: Column) -> Optional[np.ndarray]:
    """The contiguous 8-byte kernel view of a numeric column (float64
    as-is, int64/datetime/timedelta as an int64 view), or None."""
    if col.kind != "numeric":
        return None
    v = col.values
    if v.ndim != 1 or v.dtype.itemsize != 8:
        return None
    if v.dtype.kind == "f":
        if v.dtype != np.float64:
            return None
        arr = v
    elif v.dtype.kind in "iMm":
        arr = v.view(np.int64)
    else:
        return None
    if not arr.flags.c_contiguous:
        arr = np.ascontiguousarray(arr)
    return arr


# ---------------------------------------------------------------------------
# Interpreted twins (the KERNEL_TWINS references; hslint HS105 requires
# fused-pipeline exports to register these, not a numpy single op)
# ---------------------------------------------------------------------------


def filter_select_interpreted(batch: ColumnarBatch, terms) -> np.ndarray:
    """The interpreted chain ``hs_fused_filter_select`` replaces: the
    fused numpy mask, then ``np.nonzero`` — ascending passing-row
    indices, what ``ColumnarBatch.filter`` gathers through."""
    from hyperspace_tpu.ops.filter import range_mask_numpy

    return np.nonzero(range_mask_numpy(batch, terms))[0]


def interpreted_filter_aggregate(
    batch: ColumnarBatch, terms, group_by, aggs, child_schema
) -> ColumnarBatch:
    """The interpreted chain ``hs_fused_filter_agg`` replaces: fused
    numpy mask → materialized filtered batch → hash-aggregate
    (factorize + segment reductions). The differential twin every fused
    result is compared against, bit for bit."""
    from hyperspace_tpu.execution.aggregate_exec import execute_aggregate
    from hyperspace_tpu.ops.filter import range_mask_numpy

    fb = batch.filter(range_mask_numpy(batch, terms))
    return execute_aggregate(fb, list(group_by), list(aggs), child_schema)


# ---------------------------------------------------------------------------
# Plan lowering
# ---------------------------------------------------------------------------

# Kernel agg op codes (hs_fused_filter_agg):
_OP_COUNT_STAR = 0
_OP_COUNT_COL = 1
_OP_SUM_I64 = 2
_OP_SUM_F64 = 3
_OP_MIN_I64 = 4
_OP_MAX_I64 = 5
_OP_MIN_F64 = 6
_OP_MAX_F64 = 7


@dataclasses.dataclass(frozen=True)
class FusedAggPlan:
    """A compiled Filter→Aggregate lowering: everything derivable from
    (condition, group_by, aggs, schema) alone — no per-query row state —
    so it is cacheable under a ``("fusedplan", fingerprint, …)`` serve-
    cache key and reusable across serves of the same index version."""

    read_cols: Tuple[str, ...]
    terms: Tuple  # lower_range_terms output
    term_f64: Tuple[bool, ...]
    bounds: Tuple  # (lo_i, hi_i, lo_f, hi_f, flags) — native_range_bounds
    group_by: Tuple[str, ...]
    key_f64: Tuple[bool, ...]
    key_types: Tuple
    agg_ops: Tuple[Tuple[int, Optional[str]], ...]
    aggs: Tuple
    out_types: Tuple

    # what the LRU accounting charges: symbolic lowering only
    nbytes: int = 2048


def _lower_from_terms(
    terms,
    group_by: Sequence[str],
    aggs,
    child_schema,
    rel_col_order: Optional[Sequence[str]] = None,
) -> Optional[FusedAggPlan]:
    """FusedAggPlan from ALREADY-LOWERED range terms (tests and the
    calibration probe construct terms directly), or None when a group
    key / aggregate input / term column is outside the fused type set."""
    if terms is None or len(group_by) > 16:
        return None
    term_f64 = []
    for name, *_rest in terms:
        if name not in child_schema:
            return None
        f64 = _fusable_f64(child_schema[name])
        if f64 is None:
            return None
        term_f64.append(f64)
    from hyperspace_tpu.ops.filter import NEVER_MATCH, native_range_bounds

    bounds = native_range_bounds(terms, term_f64)
    if bounds is None or bounds == NEVER_MATCH:
        # unrepresentable / never-matching bounds: the interpreted chain
        # decides (rare, and an all-pruned scan is already fast)
        return None
    key_f64 = []
    key_types = []
    for c in group_by:
        f64 = _fusable_f64(child_schema[c])
        if f64 is None:
            return None
        key_f64.append(f64)
        key_types.append(child_schema[c])
    agg_ops: List[Tuple[int, Optional[str]]] = []
    out_types = []
    for spec in aggs:
        out_types.append(_agg_output_type(spec, child_schema))
        if spec.func == "count":
            if spec.column is None:
                agg_ops.append((_OP_COUNT_STAR, None))
            else:
                # COUNT(col) only reads the valid mask: any column type
                # (strings included) is countable
                agg_ops.append((_OP_COUNT_COL, spec.column))
            continue
        f64 = _fusable_f64(child_schema[spec.column])
        if f64 is None:
            return None
        if spec.func in ("sum", "avg"):
            agg_ops.append((_OP_SUM_F64 if f64 else _OP_SUM_I64, spec.column))
        elif spec.func == "min":
            agg_ops.append((_OP_MIN_F64 if f64 else _OP_MIN_I64, spec.column))
        else:  # max
            agg_ops.append((_OP_MAX_F64 if f64 else _OP_MAX_I64, spec.column))
    needed = set(group_by) | {t[0] for t in terms} | {
        c for _op, c in agg_ops if c is not None
    }
    order = rel_col_order if rel_col_order is not None else sorted(needed)
    read_cols = tuple(c for c in order if c in needed)
    return FusedAggPlan(
        read_cols=read_cols,
        terms=tuple(terms),
        term_f64=tuple(term_f64),
        bounds=tuple(bounds),
        group_by=tuple(group_by),
        key_f64=tuple(key_f64),
        key_types=tuple(key_types),
        agg_ops=tuple(agg_ops),
        aggs=tuple(aggs),
        out_types=tuple(out_types),
    )


def _lower_fused_agg(
    cond: E.Expr,
    group_by,
    aggs,
    child_schema,
    rel_col_order=None,
) -> Optional[FusedAggPlan]:
    from hyperspace_tpu.ops.filter import lower_range_terms_typed

    cols = {
        name: (_np_kind(t), t) for name, t in child_schema.items()
    }
    terms = lower_range_terms_typed(cond, cols)
    if terms is None:
        return None
    return _lower_from_terms(terms, group_by, aggs, child_schema, rel_col_order)


# ---------------------------------------------------------------------------
# Accumulator state (carried across row-group chunks)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class AggPartials:
    """The PUBLIC snapshot of one fused aggregation's carried chunk
    state — the stable hook through which the build-time sidecar capture
    (``indexes/aggindex.py``), the serve-time metadata merge and the
    kernel sweep all share ONE state layout instead of re-deriving it.

    Arrays are sliced to the live group count ``G``; group order is the
    producer's insertion/first-occurrence order (output ordering happens
    once, in :func:`finalize_partials`). Per agg slot the accumulators
    mean exactly what the kernel's mean: ``acc_cnt`` = valid-row count
    (passing-row count for COUNT(*)), ``acc_i`` = wrapped int64 sums or
    int min/max (identity-filled when the group has no valid rows),
    ``acc_f`` = float sums or min/max over CLEAN (non-NaN valid) values,
    ``acc_aux`` = the float min/max side channel (clean count for MIN,
    NaN count for MAX)."""

    n_groups: int
    rows_scanned: int
    rows_passed: int
    g_reps: np.ndarray  # (nk, G) canonical key reps (Column.key_rep)
    g_nulls: np.ndarray  # (nk, G) uint8 null plane
    g_kvals: np.ndarray  # (nk, G) first-occurrence raw key bits (int64 view)
    g_kvalid: np.ndarray  # (nk, G) uint8 validity of the stored key value
    key_has_validity: Tuple[bool, ...]
    acc_i: np.ndarray  # (na, G) int64 accumulators
    acc_f: np.ndarray  # (na, G) float64 accumulators
    acc_cnt: np.ndarray  # (na, G) valid/pass counts
    acc_aux: np.ndarray  # (na, G) float min/max aux counts


class _AggState:
    """Python-owned state of one fused aggregation: the group hash
    table, per-group key identity + first-occurrence values, and the
    per-agg accumulators, all sized ``cap`` and grown geometrically when
    the kernel reports a full table (it stops BEFORE the overflowing
    row; growth rebuilds the hash table from the stored group hashes
    inside the kernel, so Python never re-implements the hash)."""

    _INIT_CAP = 1024

    def __init__(self, plan: FusedAggPlan):
        self.plan = plan
        self.cap = self._INIT_CAP
        self._alloc(self.cap)
        self.n_groups = 1 if not plan.group_by else 0
        self.rows_passed = 0
        self.rows_scanned = 0
        self.chunks = 0
        self.key_has_validity = [False] * len(plan.group_by)
        self.rebuild = False

    def _alloc(self, cap: int) -> None:
        nk = len(self.plan.group_by)
        na = len(self.plan.agg_ops)
        self.ht = np.full(cap * 4, -1, dtype=np.int64)
        self.g_hash = np.zeros(cap, dtype=np.int64)
        self.g_reps = np.zeros((nk, cap), dtype=np.int64)
        self.g_nulls = np.zeros((nk, cap), dtype=np.uint8)
        self.g_kvals = np.zeros((nk, cap), dtype=np.int64)
        self.g_kvalid = np.zeros((nk, cap), dtype=np.uint8)
        self.acc_i = np.zeros((na, cap), dtype=np.int64)
        self.acc_f = np.zeros((na, cap), dtype=np.float64)
        self.acc_cnt = np.zeros((na, cap), dtype=np.int64)
        self.acc_aux = np.zeros((na, cap), dtype=np.int64)
        self._init_acc(0)

    def _init_acc(self, start: int) -> None:
        for a, (op, _c) in enumerate(self.plan.agg_ops):
            if op == _OP_MIN_I64:
                self.acc_i[a, start:] = np.iinfo(np.int64).max
            elif op == _OP_MAX_I64:
                self.acc_i[a, start:] = np.iinfo(np.int64).min
            elif op == _OP_MIN_F64:
                self.acc_f[a, start:] = np.inf
            elif op == _OP_MAX_F64:
                self.acc_f[a, start:] = -np.inf

    def _grow(self) -> None:
        old = (
            self.g_hash, self.g_reps, self.g_nulls, self.g_kvals,
            self.g_kvalid, self.acc_i, self.acc_f, self.acc_cnt,
            self.acc_aux,
        )
        self.cap *= 4
        self._alloc(self.cap)
        g = self.n_groups
        for dst, src in zip(
            (
                self.g_hash, self.g_reps, self.g_nulls, self.g_kvals,
                self.g_kvalid, self.acc_i, self.acc_f, self.acc_cnt,
                self.acc_aux,
            ),
            old,
        ):
            dst[..., :g] = src[..., :g]
        self.rebuild = True

    def accumulate(self, batch: ColumnarBatch) -> bool:
        """Fold one chunk into the state (False = native unavailable or
        a column fell outside the fused set — caller runs the
        interpreted chain instead)."""
        from hyperspace_tpu import native

        plan = self.plan
        n = batch.num_rows
        self.rows_scanned += n
        self.chunks += 1
        if n == 0:
            return True
        f_cols, f_valids = [], []
        for name, *_rest in plan.terms:
            col = batch.column(name)
            arr = _col_arr_8b(col)
            if arr is None:
                return False
            f_cols.append(arr)
            f_valids.append(col.validity)
        k_cols, k_valids = [], []
        for j, name in enumerate(plan.group_by):
            col = batch.column(name)
            arr = _col_arr_8b(col)
            if arr is None:
                return False
            k_cols.append(arr)
            k_valids.append(col.validity)
            if col.validity is not None:
                self.key_has_validity[j] = True
        a_cols, a_valids, a_ops = [], [], []
        for op, cname in plan.agg_ops:
            a_ops.append(op)
            if cname is None:
                a_cols.append(None)
                a_valids.append(None)
                continue
            col = batch.column(cname)
            if op >= _OP_SUM_I64:
                arr = _col_arr_8b(col)
                if arr is None:
                    return False
                a_cols.append(arr)
            else:
                a_cols.append(None)
            if col.kind == "numeric":
                a_valids.append(col.validity)
            else:
                # string COUNT(col): valid mask from the codes
                nm = col.null_mask
                a_valids.append(None if nm is None else ~nm)
        lo_i, hi_i, lo_f, hi_f, flags = plan.bounds
        row_start = 0
        while row_start < n:
            res = native.fused_filter_agg(
                f_cols, f_valids, plan.term_f64,
                lo_i, hi_i, lo_f, hi_f, flags,
                k_cols, k_valids, plan.key_f64,
                a_cols, a_valids, a_ops,
                n, row_start,
                self.ht, self.g_hash, self.g_reps, self.g_nulls,
                self.g_kvals, self.g_kvalid,
                self.acc_i, self.acc_f, self.acc_cnt, self.acc_aux,
                self.n_groups, self.rows_passed, self.rebuild,
            )
            if res is None:
                return False
            consumed, self.n_groups, self.rows_passed = res
            self.rebuild = False
            row_start += consumed
            if row_start < n:
                self._grow()
        return True

    def partials(self, copy: bool = True) -> AggPartials:
        """Snapshot the carried chunk state as :class:`AggPartials` —
        the stable public hook (the per-chunk partials used to be
        folded away inside the sweep; the sidecar capture and the
        metadata merge consume this instead of re-deriving the layout).
        ``copy=False`` returns VIEWS of the live state for callers that
        discard the state immediately (the fused finalize) — never hold
        such a snapshot across another ``accumulate``."""

        def sl(a):
            s = a[:, : self.n_groups]
            return s.copy() if copy else s

        return AggPartials(
            n_groups=self.n_groups,
            rows_scanned=self.rows_scanned,
            rows_passed=self.rows_passed,
            g_reps=sl(self.g_reps),
            g_nulls=sl(self.g_nulls),
            g_kvals=sl(self.g_kvals),
            g_kvalid=sl(self.g_kvalid),
            key_has_validity=tuple(self.key_has_validity),
            acc_i=sl(self.acc_i),
            acc_f=sl(self.acc_f),
            acc_cnt=sl(self.acc_cnt),
            acc_aux=sl(self.acc_aux),
        )


#: public name of the chunk-state carrier (kept underscore-free for the
#: capture/metadata consumers; the historical private name stays bound)
AggState = _AggState


def partials_from_batch(
    plan, batch: ColumnarBatch, rows_scanned: Optional[int] = None
) -> Optional[AggPartials]:
    """Numpy twin of the kernel chunk sweep at the PARTIALS level: one
    already-filtered batch -> :class:`AggPartials`, bit-identical to
    ``AggState.accumulate(...).partials()`` over the same rows (wrapped
    int sums, +0.0-for-null float sums, replace-on-equal min/max, clean/
    NaN aux counts, first-occurrence key values; the one thing the two
    may differ in is the sign and payload of a float sum that is NaN).
    Shared by the sidecar capture (``indexes/aggindex.py``: the pass of
    a row group the kernel cannot run, or whose float sum came out NaN)
    and the metadata plane's kernel-less boundary chunks. ``plan`` only
    needs ``group_by`` + ``agg_ops``. None when a column falls outside
    the fused 8-byte type set."""
    from hyperspace_tpu.execution.aggregate_exec import _factorize

    n = batch.num_rows
    gid, first, G = _factorize(batch, list(plan.group_by))
    nk = len(plan.group_by)
    na = len(plan.agg_ops)
    g_reps = np.zeros((nk, G), dtype=np.int64)
    g_nulls = np.zeros((nk, G), dtype=np.uint8)
    g_kvals = np.zeros((nk, G), dtype=np.int64)
    g_kvalid = np.ones((nk, G), dtype=np.uint8)
    khv = []
    for j, name in enumerate(plan.group_by):
        col = batch.column(name)
        arr = _col_arr_8b(col)
        if arr is None:
            return None
        g_reps[j] = col.key_rep()[first]
        nm = col.null_mask
        if nm is not None:
            g_nulls[j] = nm[first].astype(np.uint8)
        g_kvals[j] = arr.view(np.int64)[first]
        if col.validity is not None:
            g_kvalid[j] = col.validity[first].astype(np.uint8)
        khv.append(col.validity is not None)
    acc_i = np.zeros((na, G), dtype=np.int64)
    acc_f = np.zeros((na, G), dtype=np.float64)
    acc_cnt = np.zeros((na, G), dtype=np.int64)
    acc_aux = np.zeros((na, G), dtype=np.int64)
    for a, (op, cname) in enumerate(plan.agg_ops):
        if op == _OP_COUNT_STAR:
            acc_cnt[a] = np.bincount(gid, minlength=G)[:G]
            continue
        col = batch.column(cname)
        nm = col.null_mask
        valid = np.ones(n, dtype=bool) if nm is None else ~nm
        acc_cnt[a] = np.bincount(gid[valid], minlength=G)[:G]
        if op == _OP_COUNT_COL:
            continue
        arr = _col_arr_8b(col)
        if arr is None:
            return None
        if op == _OP_SUM_I64:
            v = np.where(valid, arr.view(np.int64), np.int64(0))
            s = np.zeros(G, dtype=np.int64)
            np.add.at(s, gid, v)
            acc_i[a] = s
        elif op == _OP_SUM_F64:
            v = np.where(valid, arr, np.float64(0.0))
            s = np.zeros(G, dtype=np.float64)
            np.add.at(s, gid, v)
            acc_f[a] = s
        elif op in (_OP_MIN_I64, _OP_MAX_I64):
            iv = arr.view(np.int64)
            if op == _OP_MIN_I64:
                fill = np.iinfo(np.int64).max
                red = np.full(G, fill, dtype=np.int64)
                np.minimum.at(red, gid, np.where(valid, iv, fill))
            else:
                fill = np.iinfo(np.int64).min
                red = np.full(G, fill, dtype=np.int64)
                np.maximum.at(red, gid, np.where(valid, iv, fill))
            acc_i[a] = red
        else:  # _OP_MIN_F64 / _OP_MAX_F64
            isn = np.isnan(arr)
            clean = valid & ~isn
            if op == _OP_MIN_F64:
                red = np.full(G, np.inf, dtype=np.float64)
                np.minimum.at(red, gid, np.where(clean, arr, np.inf))
                acc_aux[a] = np.bincount(gid[clean], minlength=G)[:G]
            else:
                red = np.full(G, -np.inf, dtype=np.float64)
                np.maximum.at(red, gid, np.where(clean, arr, -np.inf))
                acc_aux[a] = np.bincount(gid[valid & isn], minlength=G)[:G]
            acc_f[a] = red
    return AggPartials(
        n_groups=G,
        rows_scanned=n if rows_scanned is None else rows_scanned,
        rows_passed=n,
        g_reps=g_reps,
        g_nulls=g_nulls,
        g_kvals=g_kvals,
        g_kvalid=g_kvalid,
        key_has_validity=tuple(khv),
        acc_i=acc_i,
        acc_f=acc_f,
        acc_cnt=acc_cnt,
        acc_aux=acc_aux,
    )


class PartialsAccumulator:
    """Order-preserving fold of :class:`AggPartials` snapshots into one
    group table — the serve-time merge point where sidecar-persisted
    partials and scanned boundary-chunk partials meet.

    Folding is bit-exact ONLY for the merge-associative ops — COUNT,
    int SUM/AVG (wraps mod 2^64), MIN/MAX (``np.minimum``/``maximum``
    binary semantics, so replace-on-equal folds like the row sweep) —
    which is exactly the set the metadata plane admits; float SUM is
    order-sensitive and never reaches a fold (``try_metadata_aggregate``
    declines it up front). Callers must fold in the interpreted chain's
    row order (file order, row-group order within a file): first-
    occurrence group key values and equal-value min/max bit patterns
    depend on it."""

    _INIT_CAP = 64

    def __init__(self, plan):
        self.plan = plan
        self._nk = len(plan.group_by)
        self._na = len(plan.agg_ops)
        self._slots: Dict[tuple, int] = {}
        self._n = 0
        self._alloc(self._INIT_CAP)
        self.rows_scanned = 0
        self.rows_passed = 0
        self.key_has_validity = [False] * self._nk
        if not plan.group_by:
            # ungrouped aggregation always yields exactly one global
            # group, even over zero folded rows (COUNT 0 / NULL min)
            self._slots[()] = 0
            self._n = 1

    def _alloc(self, cap: int) -> None:
        nk, na = self._nk, self._na
        n = self._n
        old = getattr(self, "_g_reps", None)
        self._cap = cap
        for name, dt, fill in (
            ("_g_reps", np.int64, 0),
            ("_g_nulls", np.uint8, 0),
            ("_g_kvals", np.int64, 0),
            ("_g_kvalid", np.uint8, 1),
        ):
            arr = np.full((nk, cap), fill, dtype=dt)
            if old is not None:
                arr[:, :n] = getattr(self, name)[:, :n]
            setattr(self, name, arr)
        acc_i = np.zeros((na, cap), dtype=np.int64)
        acc_f = np.zeros((na, cap), dtype=np.float64)
        acc_cnt = np.zeros((na, cap), dtype=np.int64)
        acc_aux = np.zeros((na, cap), dtype=np.int64)
        for a, (op, _c) in enumerate(self.plan.agg_ops):
            if op == _OP_MIN_I64:
                acc_i[a] = np.iinfo(np.int64).max
            elif op == _OP_MAX_I64:
                acc_i[a] = np.iinfo(np.int64).min
            elif op == _OP_MIN_F64:
                acc_f[a] = np.inf
            elif op == _OP_MAX_F64:
                acc_f[a] = -np.inf
        if old is not None:
            acc_i[:, :n] = self._acc_i[:, :n]
            acc_f[:, :n] = self._acc_f[:, :n]
            acc_cnt[:, :n] = self._acc_cnt[:, :n]
            acc_aux[:, :n] = self._acc_aux[:, :n]
        self._acc_i, self._acc_f = acc_i, acc_f
        self._acc_cnt, self._acc_aux = acc_cnt, acc_aux

    def fold(self, p: Optional[AggPartials]) -> None:
        if p is None:
            return
        self.rows_scanned += p.rows_scanned
        self.rows_passed += p.rows_passed
        for j, hv in enumerate(p.key_has_validity):
            self.key_has_validity[j] |= hv
        G = p.n_groups
        if G == 0:
            return
        while self._n + G > self._cap:
            self._alloc(self._cap * 4)
        # slot resolution is the one per-group Python loop; the
        # accumulation below is vectorized — safe with direct indexed
        # ops because group keys WITHIN one snapshot are distinct, so
        # ``idx`` never repeats a destination
        nk = self._nk
        idx = np.empty(G, dtype=np.int64)
        for g in range(G):
            key = tuple(
                (int(p.g_reps[j, g]), int(p.g_nulls[j, g])) for j in range(nk)
            )
            gi = self._slots.get(key)
            if gi is None:
                gi = self._n
                self._slots[key] = gi
                self._n += 1
                for j in range(nk):
                    self._g_reps[j, gi] = p.g_reps[j, g]
                    self._g_nulls[j, gi] = p.g_nulls[j, g]
                    self._g_kvals[j, gi] = p.g_kvals[j, g]
                    self._g_kvalid[j, gi] = p.g_kvalid[j, g]
            idx[g] = gi
        for a, (op, _c) in enumerate(self.plan.agg_ops):
            self._acc_cnt[a][idx] += p.acc_cnt[a]
            if op == _OP_SUM_I64:
                # int64 two's-complement addition wraps like the
                # kernel's uint64 accumulate
                self._acc_i[a][idx] += p.acc_i[a]
            elif op == _OP_SUM_F64:
                self._acc_f[a][idx] += p.acc_f[a]
            elif op == _OP_MIN_I64:
                self._acc_i[a][idx] = np.minimum(self._acc_i[a][idx], p.acc_i[a])
            elif op == _OP_MAX_I64:
                self._acc_i[a][idx] = np.maximum(self._acc_i[a][idx], p.acc_i[a])
            elif op == _OP_MIN_F64:
                self._acc_f[a][idx] = np.minimum(self._acc_f[a][idx], p.acc_f[a])
                self._acc_aux[a][idx] += p.acc_aux[a]
            elif op == _OP_MAX_F64:
                self._acc_f[a][idx] = np.maximum(self._acc_f[a][idx], p.acc_f[a])
                self._acc_aux[a][idx] += p.acc_aux[a]

    def snapshot(self) -> AggPartials:
        G = self._n
        return AggPartials(
            n_groups=G,
            rows_scanned=self.rows_scanned,
            rows_passed=self.rows_passed,
            g_reps=self._g_reps[:, :G].copy(),
            g_nulls=self._g_nulls[:, :G].copy(),
            g_kvals=self._g_kvals[:, :G].copy(),
            g_kvalid=self._g_kvalid[:, :G].copy(),
            key_has_validity=tuple(self.key_has_validity),
            acc_i=self._acc_i[:, :G].copy(),
            acc_f=self._acc_f[:, :G].copy(),
            acc_cnt=self._acc_cnt[:, :G].copy(),
            acc_aux=self._acc_aux[:, :G].copy(),
        )


def finalize_partials(plan, pt: AggPartials) -> ColumnarBatch:
    """Assemble the output batch from a partials snapshot — the exact
    post-processing of ``aggregate_exec.execute_aggregate`` (shared
    ``finalize_*`` helpers), with groups ordered like ``_factorize``:
    ascending lexicographic key-rep planes (rep major, null plane minor
    per key). The ONE finalization for the fused sweep, the metadata
    merge and the capture round-trip tests."""
    from hyperspace_tpu.execution import aggregate_exec as AE

    G = pt.n_groups
    out: Dict[str, Column] = {}
    if plan.group_by:
        planes: List[np.ndarray] = []
        for j in range(len(plan.group_by)):
            planes.append(pt.g_reps[j])
            planes.append(pt.g_nulls[j].astype(np.int64))
        # np.lexsort keys are minor→major; planes are major→minor
        order = np.lexsort(planes[::-1])
        for j, name in enumerate(plan.group_by):
            raw = pt.g_kvals[j][order]
            vals = raw.view(np.float64) if plan.key_f64[j] else raw
            validity = (
                pt.g_kvalid[j][order].astype(bool)
                if pt.key_has_validity[j]
                else None
            )
            out[name] = Column(
                "numeric", plan.key_types[j], values=vals, validity=validity
            )
    else:
        order = np.arange(G, dtype=np.int64)  # exactly one global group
    for a, (spec, (op, _c), out_type) in enumerate(
        zip(plan.aggs, plan.agg_ops, plan.out_types)
    ):
        cnt = pt.acc_cnt[a][order]
        if op in (_OP_COUNT_STAR, _OP_COUNT_COL):
            out[spec.name] = AE.finalize_count(out_type, cnt)
        elif op in (_OP_SUM_I64, _OP_SUM_F64):
            sums = (pt.acc_i if op == _OP_SUM_I64 else pt.acc_f)[a][order]
            if spec.func == "avg":
                out[spec.name] = AE.finalize_avg(out_type, sums, cnt)
            else:
                out[spec.name] = AE.finalize_sum(out_type, sums, cnt)
        elif op in (_OP_MIN_I64, _OP_MAX_I64):
            red = pt.acc_i[a][order]
            out[spec.name] = AE.finalize_minmax(
                out_type, red, cnt, np.dtype(np.int64)
            )
        elif op == _OP_MIN_F64:
            acc = pt.acc_f[a][order]
            has_clean = pt.acc_aux[a][order] > 0
            red = np.where(has_clean, acc, np.float64(np.nan))
            out[spec.name] = AE.finalize_minmax(
                out_type, red, cnt, np.dtype(np.float64)
            )
        else:  # _OP_MAX_F64
            acc = pt.acc_f[a][order]
            has_nan = pt.acc_aux[a][order] > 0
            red = np.where(has_nan, np.float64(np.nan), acc)
            out[spec.name] = AE.finalize_minmax(
                out_type, red, cnt, np.dtype(np.float64)
            )
    return ColumnarBatch(out)


def _finalize(state: _AggState) -> ColumnarBatch:
    """The fused sweep's finalization: snapshot the carried state and run
    the shared partials finalization (views, not copies — the state is
    discarded right after, and finalize_partials reorders into fresh
    arrays anyway)."""
    return finalize_partials(state.plan, state.partials(copy=False))


def kernel_filter_aggregate(
    batches, terms, group_by, aggs, child_schema
) -> Optional[ColumnarBatch]:
    """The kernel-driven fused pass over one batch or an ordered list of
    chunk batches — the direct counterpart of
    :func:`interpreted_filter_aggregate` for differential tests and the
    calibration probe. Returns None when the native kernel is
    unavailable or the shape is outside the fused set."""
    if isinstance(batches, ColumnarBatch):
        batches = [batches]
    plan = _lower_from_terms(terms, group_by, aggs, child_schema)
    if plan is None:
        return None
    state = _AggState(plan)
    for b in batches:
        if not state.accumulate(b):
            return None
    return _finalize(state)


# ---------------------------------------------------------------------------
# Executor entry points
# ---------------------------------------------------------------------------


def fused_filter_batch(cond: E.Expr, batch: ColumnarBatch, session):
    """Fused Filter(→Project) lowering over an in-memory batch: one
    native pass computes pass/fail AND compacts the passing row indices
    (``hs_fused_filter_select``); the projection gathers through them
    (native threaded gathers). Bit-identical to
    ``batch.filter(mask)`` — ``filter`` IS ``take(nonzero(mask))``.
    Returns None (caller runs the interpreted mask) off the fused shape,
    below the calibrated crossover, or in the device-mask regime."""
    global last_fused_stats
    n = batch.num_rows
    # the select's true crossover is mask-shaped (one-pass compaction vs
    # mask+nonzero), not agg-shaped: gate on the LOWER of the fused and
    # range-mask calibrated thresholds so a machine whose hash-agg
    # crossover lands high still dispatches the select where it wins
    # (and the test/bench module override on the fused threshold still
    # forces dispatch)
    from hyperspace_tpu.ops.filter import _native_range_mask_min_rows

    threshold = min(
        _native_fused_pipeline_min_rows(), _native_range_mask_min_rows()
    )
    if n == 0 or n < threshold:
        return None
    dev_min = (
        session.conf.device_filter_min_rows
        if session is not None
        else C.EXECUTION_DEVICE_FILTER_MIN_ROWS_DEFAULT
    )
    if n >= dev_min:
        return None  # the XLA mask path owns device-resident regimes
    from hyperspace_tpu.ops import filter as F

    terms = F.lower_range_terms(cond, batch)
    if terms is None:
        return None
    t0 = time.perf_counter()
    prep = F.native_terms_for_batch(batch, terms)
    if prep is None:
        return None
    if prep == F.NEVER_MATCH:
        idx = np.zeros(0, dtype=np.int64)
    else:
        from hyperspace_tpu import native

        idx = native.fused_filter_select(*prep, n)
        if idx is None:
            return None
    out = batch.take(idx)
    last_fused_stats = {
        "mode": "select",
        "rows_scanned": n,
        "rows_passed": int(len(idx)),
        "rows_materialized": int(len(idx)),
        "chunks": 1,
        "wall_s": time.perf_counter() - t0,
    }
    return out


def try_fused_aggregate(plan: Aggregate, session) -> Optional[ColumnarBatch]:
    """Serve ``Aggregate(…, [Project(…,)] Filter(cond, Scan))`` over a
    pruned index scan as the fused pipeline. None = any gate failed;
    the caller runs the interpreted chain (bit-identical either way)."""
    global last_fused_stats
    if not fused_pipeline_on(session):
        return None
    node = plan.child
    while isinstance(node, Project):
        node = node.child
    if not isinstance(node, Filter) or not isinstance(node.child, Scan):
        return None
    from hyperspace_tpu import native

    if native.load(wait=False) is None:
        return None
    from hyperspace_tpu.execution import executor as X

    # both pruning passes are memoized (bucket ids per file tuple, zone
    # maps per file identity), so a later bail-out's interpreted re-run
    # repeats only the cheap intersection, not the metadata reads
    pruned = X._bucket_pruned_scan(node.child, node.condition)
    pruned = X._range_pruned_scan(pruned, node.condition, session)
    if not isinstance(pruned, Scan):
        return None
    rel = pruned.relation
    # the clean-index-scan gate is _cacheable_scan's exact condition set
    # (index data, parquet-like, no delete compensation, no injected
    # partition constants): one definition, so a future query-shaped
    # relation field added there excludes the fused pass automatically
    if not X._cacheable_scan(rel):
        return None
    # the Project above the Filter prunes to the aggregate's inputs, so
    # the condition's columns live in the SCAN's schema, not the child's;
    # types agree wherever both carry a column (projection never retypes)
    child_schema = dict(rel.schema)
    child_schema.update(plan.child.schema())
    fplan = _compiled_plan(node.condition, plan, rel, child_schema, session)
    if fplan is None:
        return None
    cache = X._serve_cache(session)
    if cache is not None:  # rel passed _cacheable_scan above
        # serve-server mode keeps the decoded scan in RAM: run the fused
        # pass over the cached batch (no read at all) instead of
        # streaming parquet chunks past a warm cache
        hit = X._scan_cache_entry(rel, set(fplan.read_cols), session)
        if hit is None:
            return None
        entry, _cols = hit
        batch = entry.batch_for(fplan.read_cols)
        if batch is None or batch.num_rows < _native_fused_pipeline_min_rows():
            return None
        t0 = time.perf_counter()
        state = _AggState(fplan)
        if not state.accumulate(batch):
            return None
        out = _finalize(state)
        last_fused_stats = _agg_stats(state, t0)
        return out
    total = _scan_row_total(rel)
    if total < _native_fused_pipeline_min_rows():
        return None
    return _run_chunked(fplan, rel)


def _agg_stats(state: _AggState, t0: float) -> Dict[str, Any]:
    return {
        "mode": "agg",
        "rows_scanned": state.rows_scanned,
        "rows_passed": state.rows_passed,
        # the fused pass materializes GROUPS, never filtered rows — the
        # interpreted chain materializes rows_passed rows per column
        "rows_materialized": int(
            state.n_groups if state.plan.group_by else 1
        ),
        "groups": int(state.n_groups),
        "chunks": state.chunks,
        "wall_s": time.perf_counter() - t0,
    }


def _compiled_plan(
    cond: E.Expr, plan: Aggregate, rel, child_schema, session
) -> Optional[FusedAggPlan]:
    """The lowered plan, served from the serve cache when available
    (``("fusedplan", fingerprint, …)`` kind — evictable like zone maps
    and deltas via ``ServeCache.evict_kind("fusedplan")``)."""
    from hyperspace_tpu.execution import executor as X

    cache = X._serve_cache(session)
    key = None
    if cache is not None:
        from hyperspace_tpu.execution.serve_cache import file_fingerprint

        fp = file_fingerprint(rel.files)
        if fp is not None:
            key = (
                "fusedplan",
                fp,
                repr(cond),
                tuple(plan.group_by),
                tuple(plan.aggs),
            )
            hit = cache.get(key)
            if hit is not None:
                return hit
    fplan = _lower_fused_agg(
        cond, plan.group_by, plan.aggs, child_schema, rel.column_names
    )
    if fplan is not None and key is not None:
        cache.put(key, fplan, fplan.nbytes)
    return fplan


# ---------------------------------------------------------------------------
# Chunked execution (reads overlap the fused compute on scan_pool)
# ---------------------------------------------------------------------------


def _scan_row_total(rel) -> int:
    """Rows the fused pass would scan (surviving row groups), from the
    zone-map plane's memoized footer metadata (``zonemaps.footer_zones``
    — the range-pruning pass has usually just parsed these footers, so
    this is a cache hit, and there is ONE definition of per-row-group
    row counts). Unreadable footers count as large: the read will raise
    the same error the interpreted path would."""
    from hyperspace_tpu.indexes import zonemaps

    total = 0
    groups = rel.file_row_groups or (None,) * len(rel.files)
    for f, g in zip(rel.files, groups):
        zones = zonemaps.footer_zones(f)
        if zones is None:
            return 1 << 62
        rows = zones["rg_rows"]
        if g is None:
            total += sum(rows)
        else:
            total += sum(rows[i] for i in g if i < len(rows))
    return total


def _read_chunk(path: str, groups, cols: List[str]) -> pa.Table:
    """One file's surviving row groups, via the SAME per-file read the
    interpreted chain's ``read_table_row_groups`` uses — a shared
    definition, so the two paths can never read different bytes."""
    from hyperspace_tpu.io.parquet import read_file_row_groups

    return read_file_row_groups(path, groups, cols)


def _run_chunked(fplan: FusedAggPlan, rel) -> Optional[ColumnarBatch]:
    """Stream the pruned scan through the fused pass file by file:
    chunk reads are submitted to the shared scan pool up front, decode +
    the fused kernel run on the consumer thread while later chunks are
    still reading — accumulation order stays exactly file order, which
    is what makes float sums bit-identical to the interpreted chain."""
    global last_fused_stats
    from hyperspace_tpu.io.scan import scan_pool

    t0 = time.perf_counter()
    cols = list(fplan.read_cols)
    groups = (
        list(rel.file_row_groups)
        if rel.file_row_groups is not None
        else [None] * len(rel.files)
    )
    state = _AggState(fplan)
    if len(rel.files) > 1:
        futs = [
            scan_pool().submit(_read_chunk, f, g, cols)
            for f, g in zip(rel.files, groups)
        ]
        tables = (fut.result() for fut in futs)
    else:
        tables = (
            _read_chunk(f, g, cols) for f, g in zip(rel.files, groups)
        )
    for table in tables:
        if not state.accumulate(ColumnarBatch.from_arrow(table)):
            return None  # executor falls back to the interpreted chain
    out = _finalize(state)
    last_fused_stats = _agg_stats(state, t0)
    return out


# ---------------------------------------------------------------------------
# Metadata plane: answer point aggregates from persisted partials
# (docs/agg-serve.md; sidecar capture/assembly in indexes/aggindex.py)
# ---------------------------------------------------------------------------


def agg_plane_on(session) -> bool:
    """``hyperspace.index.agg.enabled`` (default on). Like the fused
    pass, a pure serving substitution with identical output, so it also
    applies to sessionless execution."""
    if session is None:
        return C.INDEX_AGG_ENABLED_DEFAULT
    return session.conf.index_agg_enabled


#: ops whose partials fold associatively bit-for-bit (see
#: PartialsAccumulator): float SUM/AVG is excluded — merging per-row-
#: group float sums would reassociate vs the row-sequential chain
_METADATA_MERGE_OPS = frozenset(
    {
        _OP_COUNT_STAR,
        _OP_COUNT_COL,
        _OP_SUM_I64,
        _OP_MIN_I64,
        _OP_MAX_I64,
        _OP_MIN_F64,
        _OP_MAX_F64,
    }
)


def _chunk_partials(fplan: FusedAggPlan, batch: ColumnarBatch):
    """Partials of one boundary chunk: the fused kernel when available
    (same sweep the fused pass runs), else the numpy twin over the
    masked batch — bit-identical either way (partials-level twin
    contract, differential-tested in tests/test_agg_index.py)."""
    from hyperspace_tpu import native

    if fplan.terms and batch.num_rows and native.load(wait=False) is not None:
        state = _AggState(fplan)
        if state.accumulate(batch):
            return state.partials()
    if fplan.terms:
        from hyperspace_tpu.ops.filter import range_mask_numpy

        fb = batch.filter(range_mask_numpy(batch, fplan.terms))
    else:
        fb = batch
    return partials_from_batch(fplan, fb, rows_scanned=batch.num_rows)


def try_metadata_aggregate(plan: Aggregate, session) -> Optional[ColumnarBatch]:
    """Serve ``Aggregate(…, [Project] [Filter(cond,)] Scan)`` over a
    clean index scan from the persisted partial-aggregate sidecars
    (``_aggstate.json``): row groups whose zone provably satisfies EVERY
    conjunct fold their stored partials without opening a single parquet
    file; boundary row groups are scanned through the fused kernel (or
    its numpy twin) for just those chunks; everything merges through
    :class:`PartialsAccumulator` + :func:`finalize_partials`, so the
    result stays bit-identical to the interpreted chain. None = any gate
    failed; the caller runs the fused pass / interpreted chain instead
    (bit-identical whichever path answers)."""
    global last_aggplane_stats
    if not agg_plane_on(session):
        return None
    node = plan.child
    while isinstance(node, Project):
        node = node.child
    if isinstance(node, Filter) and isinstance(node.child, Scan):
        cond, scan = node.condition, node.child
    elif isinstance(node, Scan):
        cond, scan = None, node
    else:
        return None
    if len(plan.group_by) > 1:
        return None  # grouped partials are captured per single key column
    from hyperspace_tpu.execution import executor as X

    if cond is not None:
        pruned = X._bucket_pruned_scan(scan, cond)
        pruned = X._range_pruned_scan(pruned, cond, session)
        if not isinstance(pruned, Scan):
            return None
    else:
        pruned = scan
    rel = pruned.relation
    if not X._cacheable_scan(rel):
        return None
    t0 = time.perf_counter()
    child_schema = dict(rel.schema)
    child_schema.update(plan.child.schema())
    if cond is None:
        ivs: Dict[str, Any] = {}
        fplan = _lower_from_terms(
            (), plan.group_by, plan.aggs, child_schema, rel.column_names
        )
    else:
        from hyperspace_tpu.indexes import zonemaps

        # STRICT lowering: full-coverage classification is sound only
        # when the intervals ARE the predicate (IN hulls, OR trees, !=
        # etc. abstain and the whole plane declines)
        ivs = zonemaps.predicate_intervals_complete(cond, rel.schema)
        if ivs is None:
            return None
        fplan = _lower_fused_agg(
            cond, plan.group_by, plan.aggs, child_schema, rel.column_names
        )
    if fplan is None:
        return None
    for op, _c in fplan.agg_ops:
        if op not in _METADATA_MERGE_OPS:
            return None
    from hyperspace_tpu.indexes import aggindex

    key = plan.group_by[0] if plan.group_by else None
    data = aggindex.agg_data_for(
        rel,
        X._serve_cache(session),
        session.conf if session is not None else None,
        key,
    )
    if data is None:
        return None
    cells = aggindex.classify_row_groups(data, rel, ivs, key, fplan)
    if cells is None:
        return None
    n_full = sum(1 for _f, _g, kind in cells if kind == "full")
    if n_full == 0:
        # nothing answerable from metadata: no win over the fused pass,
        # and engaging would only shadow its telemetry
        return None
    cols = list(fplan.read_cols)
    partial_cells = [
        (i, fi, gi)
        for i, (fi, gi, kind) in enumerate(cells)
        if kind == "partial"
    ]
    cache = X._serve_cache(session)
    if partial_cells and cache is not None:
        # serve-server mode with a WARM decoded scan: the fused pass
        # serves boundary rows straight from RAM — re-reading them from
        # parquet here would make partial coverage slower than the path
        # it preempts. (A cold cache still favors metadata + boundary
        # disk reads; and full coverage never reads at all.)
        from hyperspace_tpu.execution.serve_cache import file_fingerprint

        fp = file_fingerprint(rel.files)
        if fp is not None:
            entry = cache.peek(("scan", fp))
            if entry is not None and entry.batch_for(cols) is not None:
                return None
    # boundary chunk reads overlap the metadata folds on the scan pool;
    # folding stays strictly in (file, row-group) order — the
    # interpreted chain's row order (see PartialsAccumulator)
    from hyperspace_tpu.io.scan import scan_pool

    reads = {}
    if len(partial_cells) > 1:
        for i, fi, gi in partial_cells:
            reads[i] = scan_pool().submit(
                _read_chunk,
                rel.files[fi],
                None if gi is None else [gi],
                cols,
            )
    acc = PartialsAccumulator(fplan)
    rows_read = 0
    n_empty = n_partial = 0
    for i, (fi, gi, kind) in enumerate(cells):
        if kind == "empty":
            n_empty += 1
            continue
        if kind == "full":
            acc.fold(aggindex.rg_partials(data, fi, gi, fplan, key))
            continue
        n_partial += 1
        fut = reads.get(i)
        table = (
            fut.result()
            if fut is not None
            else _read_chunk(
                rel.files[fi], None if gi is None else [gi], cols
            )
        )
        batch = ColumnarBatch.from_arrow(table)
        rows_read += batch.num_rows
        p = _chunk_partials(fplan, batch)
        if p is None:
            # column outside the fused set mid-stream: bail to the
            # interpreted chain, releasing not-yet-started reads so the
            # pool doesn't keep scanning data nobody will consume
            for j, fut2 in reads.items():
                if j > i:
                    fut2.cancel()
            return None
        acc.fold(p)
    out = finalize_partials(fplan, acc.snapshot())
    last_aggplane_stats = {
        "mode": "agg_metadata",
        "row_groups_total": len(cells),
        "row_groups_metadata": n_full,
        "row_groups_empty": n_empty,
        "row_groups_scanned": n_partial,
        "rows_scanned": rows_read,
        "groups": int(out.num_rows),
        "wall_s": time.perf_counter() - t0,
    }
    return out
