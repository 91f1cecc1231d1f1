"""Plan executor: walks the (optimized) logical plan and produces Arrow.

Equivalent role to Spark's physical planning + execution under the
reference (scan → FileSourceScanExec etc.). Column pruning is pushed into
the scan (the reference gets this from Parquet + Catalyst for free);
predicates are evaluated with the XLA kernel (``ops/filter.py``) with a
host fallback for expressions the device path does not cover.
"""

from __future__ import annotations

import threading as _threading
from functools import lru_cache as _lru_cache
from typing import Dict, Optional, Set

import numpy as np

from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.io import parquet as pio
from hyperspace_tpu.io.columnar import ColumnarBatch
from hyperspace_tpu.obs import trace as _obs_trace
from hyperspace_tpu.ops.filter import Unsupported, device_filter_mask
from hyperspace_tpu.plan import expressions as E
from hyperspace_tpu.plan.nodes import (
    Aggregate,
    Filter,
    Join,
    Limit,
    LogicalPlan,
    Project,
    Scan,
    Sort,
    Union,
)


def execute(plan: LogicalPlan, session=None):
    """Execute -> pyarrow.Table (column order = plan.output)."""
    batch = _exec(plan, set(plan.output), session)
    return batch.select(plan.output).to_arrow()


def _exec(plan: LogicalPlan, needed: Set[str], session) -> ColumnarBatch:
    if isinstance(plan, Scan):
        return _exec_scan(plan, needed, session)
    if isinstance(plan, Filter):
        child = _bucket_pruned_scan(plan.child, plan.condition)
        child = _range_pruned_scan(child, plan.condition, session)
        child_needed = set(needed) | E.references(plan.condition)
        if isinstance(child, Scan):
            cached = _cached_filter(child, plan.condition, child_needed, session)
            if cached is not None:
                return cached
            batch = _exec_scan(
                child,
                child_needed,
                session,
                pushdown=_pushdown_filters(plan.condition, child.relation),
            )
        else:
            batch = _exec(child, child_needed, session)
        if isinstance(child, Scan) and _fused_pipeline_on(session):
            # fused Filter(→Project) lowering (docs/serve-compiler.md):
            # one native pass computes the conjunct mask AND compacts
            # the passing indices — bit-identical to filter(mask),
            # which IS take(nonzero(mask))
            from hyperspace_tpu.execution.pipeline_compiler import (
                fused_filter_batch,
            )

            fused = fused_filter_batch(plan.condition, batch, session)
            if fused is not None:
                return fused
        return batch.filter(_filter_mask(plan.condition, batch, session))
    if isinstance(plan, Project):
        batch = _exec(plan.child, set(plan.columns), session)
        return batch.select(plan.columns)
    if isinstance(plan, Union):
        cols = [c for c in plan.output if c in needed] or plan.output[:1]
        left = _exec(plan.left, set(cols), session).select(cols)
        right = _exec(plan.right, set(cols), session).select(cols)
        return ColumnarBatch.concat([left, right])
    if isinstance(plan, Join):
        return _exec_join(plan, needed, session)
    if isinstance(plan, Aggregate):
        from hyperspace_tpu.execution.pipeline_compiler import (
            try_fused_aggregate,
            try_metadata_aggregate,
        )

        # aggregate index plane (docs/agg-serve.md): a strictly-lowered
        # Filter(→Project)→Aggregate over a clean index scan answers
        # fully-covered row groups from the persisted partial-aggregate
        # sidecar WITHOUT reading them, scans only the boundary chunks,
        # and merges through the shared partials layer — bit-identical
        # to the chains below. The agg/finalize stage span is the only
        # serve-side visibility into these fused passes (OBS_SITES).
        with _obs_trace.span("agg"):
            served = try_metadata_aggregate(plan, session)
            if served is not None:
                return served
            # fused serve-pipeline compiler (docs/serve-compiler.md): a
            # Filter(→Project)→Aggregate subtree over a pruned index scan
            # runs as one fused native pass per row-group chunk —
            # predicate, grouping and partial aggregates in a single
            # sweep, partials merged at the edge; bit-identical to the
            # chain below
            fused = try_fused_aggregate(plan, session)
            if fused is not None:
                return fused
            batch = _exec(plan.child, plan.input_columns, session)
            from hyperspace_tpu.execution.aggregate_exec import (
                execute_aggregate,
            )

            return execute_aggregate(
                batch, plan.group_by, plan.aggs, plan.child.schema()
            )
    if isinstance(plan, Sort):
        from hyperspace_tpu.ops.sort import ordering_permutation

        child_needed = set(needed) | {c for c, _ in plan.keys}
        batch = _exec(plan.child, child_needed, session)
        if batch.num_rows == 0:
            return batch
        return batch.take(ordering_permutation(batch, plan.keys))
    if isinstance(plan, Limit):
        return _exec_limit(plan.n, plan.child, needed, session)
    raise HyperspaceException(f"Unknown plan node: {type(plan).__name__}")


def _exec_limit(n: int, child: LogicalPlan, needed: Set[str], session) -> ColumnarBatch:
    """Limit execution that avoids materializing the full child.

    * Limit∘Sort = top-n: sort the permutation, materialize only n rows;
    * Limit pushes through Project and Union (row order is the child's
      deterministic order, so taking the first n of the left side first
      is exactly what the naive path produced);
    * Limit∘Scan / Limit∘Filter∘Scan stream file-by-file and stop as
      soon as n rows are produced.
    The reference gets all of this from Spark's CollectLimitExec /
    LocalLimit pushdown; the naive path here executed and sorted the
    entire child before truncating.
    """
    import dataclasses

    if n <= 0:
        import pyarrow as pa

        schema = child.schema()
        cols = [c for c in child.output if c in needed] or child.output[:1]
        return ColumnarBatch.from_arrow(
            pa.table({c: pa.array([], type=schema[c]) for c in cols})
        )
    if isinstance(child, Sort):
        from hyperspace_tpu.ops.sort import ordering_permutation

        child_needed = set(needed) | {c for c, _ in child.keys}
        batch = _exec(child.child, child_needed, session)
        if batch.num_rows == 0:
            return batch
        perm = ordering_permutation(batch, child.keys)
        return batch.take(perm[: min(n, batch.num_rows)])
    if isinstance(child, Project):
        return _exec_limit(
            n, child.child, set(child.columns), session
        ).select(child.columns)
    if isinstance(child, Union):
        cols = [c for c in child.output if c in needed] or child.output[:1]
        left = _exec_limit(n, child.left, set(cols), session).select(cols)
        if left.num_rows >= n:
            return left.take(np.arange(n))
        right = _exec_limit(
            n - left.num_rows, child.right, set(cols), session
        ).select(cols)
        return ColumnarBatch.concat([left, right])
    # file-by-file streaming for Scan / Filter(Scan) over footer-counted
    # formats without post-read row filtering
    scan = child.child if isinstance(child, Filter) else child
    streamable = (
        isinstance(scan, Scan)
        and scan.relation.fmt in ("parquet", "delta", "iceberg")
        and scan.relation.excluded_file_ids is None
        and len(scan.relation.files) > 1
    )
    if streamable:
        # geometric group sizes (1, 2, 4, …): a selective filter that ends
        # up reading everything still gets the threaded multi-file read
        # after the first few probes (log-many read_table calls total),
        # while a satisfied limit stops after one small group
        parts: list = []
        got = 0
        files = list(scan.relation.files)
        pos = 0
        group = 1
        while pos < len(files) and got < n:
            chunk = tuple(files[pos : pos + group])
            sub_scan = Scan(dataclasses.replace(scan.relation, files=chunk))
            sub: LogicalPlan = (
                Filter(child.condition, sub_scan)
                if isinstance(child, Filter)
                else sub_scan
            )
            b = _exec(sub, needed, session)
            parts.append(b)
            got += b.num_rows
            pos += len(chunk)
            group *= 2
        batch = ColumnarBatch.concat(parts)
        return batch.take(np.arange(min(n, batch.num_rows)))
    batch = _exec(child, needed, session)
    return batch.take(np.arange(min(n, batch.num_rows)))


def _serve_cache(session):
    """The session's ServeCache, or None when serve-server mode is off."""
    if session is None:
        return None
    return session.serve_cache


def _serve_pipeline_on(session) -> bool:
    """Pipelined serve path enabled (``hyperspace.serve.pipeline.enabled``,
    default on). Sessionless callers run the sequential path — the
    pipeline's thread fan-out is a serve-process feature, not a library
    default for one-shot embedding."""
    return session is not None and session.conf.serve_pipeline_enabled


def _serve_stream_on(session) -> bool:
    """Streaming per-bucket join serve (``hyperspace.serve.stream.enabled``,
    default off — docs/out-of-core.md). Session-gated like the serve
    pipeline: the wave loop's thread fan-out and byte budgeting are a
    serve-process feature, not a library default for one-shot embedding."""
    return session is not None and session.conf.serve_stream_enabled


def _io_mmap_on(session) -> bool:
    """Memory-mapped Arrow reads (``hyperspace.io.mmap.enabled``, default
    off — docs/out-of-core.md): serve-path parquet reads borrow pages from
    the OS file mapping instead of copying onto the heap."""
    return session is not None and session.conf.io_mmap_enabled


# Streaming-serve telemetry (docs/out-of-core.md): wave counters of the
# LAST streamed join, reset at the start of each — the stream analogue of
# ``join_exec.last_serve_breakdown`` (same process-global, last-writer-
# wins diagnostic scope: bench.py and the smoke gate read it between
# queries; concurrent streams only blur this attribution, never results).
last_stream_stats: Dict[str, int] = {}
_stream_stats_lock = _threading.Lock()


def stream_stats_reset() -> None:
    with _stream_stats_lock:
        last_stream_stats.clear()


def _stream_stats_add(key: str, amount: int = 1) -> None:
    with _stream_stats_lock:
        last_stream_stats[key] = last_stream_stats.get(key, 0) + amount


def _serve_shards(session) -> int:
    """Shard count for the device-local serve tail: the session mesh
    size, 1 without a session (single-tail scheduling). The shard
    layout is the build's bucket ownership (``bucket % D``) — each
    worker prepares and merges only the buckets its shard owns, with a
    per-bucket union at the edge (bit-identical output)."""
    if session is None:
        return 1
    return int(session.runtime.mesh.devices.size)


def _cacheable_scan(rel) -> bool:
    """Only clean INDEX scans are cached (index data files are immutable
    and bounded; pinning arbitrary source tables in RAM is not this
    feature): no row-level delete compensation, no injected partition
    constants (both are query-shaped state that must not leak between
    queries)."""
    return (
        rel.index_info is not None
        and rel.fmt in ("parquet", "delta", "iceberg")
        and rel.excluded_file_ids is None
        and not rel.file_partition_values
        and bool(rel.files)
    )


def _scan_cache_entry(rel, needed: Set[str], session):
    """(ScanCacheEntry, cols) for a clean index scan from the serve
    cache — one entry per file set, columns accruing on demand so
    overlapping projections share a single decoded copy per column —
    or None when serve-server mode is off / the scan is not cacheable."""
    cache = _serve_cache(session)
    if cache is None or not _cacheable_scan(rel):
        return None
    from hyperspace_tpu.execution.serve_cache import (
        ScanCacheEntry,
        file_fingerprint,
    )

    fp = file_fingerprint(rel.files)
    if fp is None:
        return None
    cols = tuple(c for c in rel.column_names if c in needed) or (
        rel.column_names[0],
    )
    key = ("scan", fp)
    state = cache.get(key)
    if state is None:
        counts = pio.file_row_counts(list(rel.files))
        segs = []
        pos = 0
        for c in counts:
            segs.append((pos, pos + c))
            pos += c
        state = ScanCacheEntry(segs)
    missing = [c for c in cols if c not in state.columns]
    if missing:
        table = pio.read_table(list(rel.files), missing, rel.fmt)
        from hyperspace_tpu.io.columnar import Column

        new_cols = {c: Column.from_arrow(table.column(c)) for c in missing}
        # copy-on-write publication (ScanCacheEntry concurrency
        # contract): never mutate an entry other threads may hold, and
        # merge onto the FRESHEST published entry (non-counting peek) so
        # a racing thread's just-published columns survive. The union
        # also keeps THIS thread's stale-entry columns — the freshest
        # entry may lack them after an evict/recreate race — so the
        # returned entry always covers ``cols``.
        latest = cache.peek(key)
        base = latest if latest is not None else state
        stale_extra = {
            c: col
            for c, col in state.columns.items()
            if c not in base.columns
        }
        state = base.with_new_columns({**stale_extra, **new_cols})
        cache.put(key, state, state.budget_nbytes)
    return state, cols


def _cached_filter(
    scan: Scan, cond: E.Expr, child_needed: Set[str], session
) -> Optional[ColumnarBatch]:
    """Serve a Filter∘Scan from the serve cache (None = cache off/miss
    path not applicable; caller runs the normal read).

    On a cached key-sorted index bucket a pinned-key conjunct narrows the
    candidate rows by binary search (``ScanCacheEntry``) before the
    full mask runs — the RAM-resident analogue of the parquet row-group
    pruning the cold path gets from ``_pushdown_filters``, but without
    re-reading anything.
    """
    hit = _scan_cache_entry(scan.relation, child_needed, session)
    if hit is None:
        return None
    state, cols = hit
    rel = scan.relation
    batch = state.batch_for(cols)
    idx = _sorted_narrow(state, cond, rel)
    if idx is not None:
        sub = batch.take(idx)
        return sub.filter(_filter_mask(cond, sub, session))
    return batch.filter(_filter_mask(cond, batch, session))


def _sorted_narrow(state, cond: E.Expr, rel) -> Optional[np.ndarray]:
    """Candidate row indices (ascending) from the first conjunct that can
    binary-search a segment-sorted cached column, else None.

    Soundness: the returned set must be a SUPERSET of the rows matching
    the full condition (the caller re-applies the whole mask on the
    subset). Equality/IN search by key rep is a superset for every type
    (value equality ⇒ rep equality). Range conjuncts additionally need
    rep order == value order, which holds for signed ints / temporals /
    bools but NOT floats (sign-bit view) or strings (hashes) — those fall
    through to the full mask.
    """
    cols = {c.lower(): c for c in rel.column_names}
    import pyarrow as pa

    def order_preserving(t: pa.DataType) -> bool:
        return (
            pa.types.is_signed_integer(t)
            or pa.types.is_temporal(t)
            or pa.types.is_boolean(t)
        )

    for cj in E.split_conjuncts(cond):
        col = None
        pts = None  # list of key reps for =/IN
        bound = None  # (op, rep) for range conjuncts
        norm = E.normalize_comparison(cj)
        if norm is not None:
            op, name, lit = norm
            col = cols.get(name.lower())
            if col is None or lit is None:
                continue
            rep = _literal_key_rep(lit, rel.schema[col])
            if rep is None:
                continue
            if op == "=":
                pts = [rep]
            elif op in ("<", "<=", ">", ">=") and order_preserving(
                rel.schema[col]
            ):
                bound = (op, rep)
            else:
                continue
        elif isinstance(cj, E.In) and isinstance(cj.child, E.Col):
            col = cols.get(cj.child.name.lower())
            if col is None:
                continue
            vals = [v for v in cj.values if v is not None]
            if not vals or len(vals) > _MAX_PRUNE_COMBOS:
                continue
            pts = []
            for v in vals:
                rep = _literal_key_rep(v, rel.schema[col])
                if rep is None:
                    pts = None
                    break
                pts.append(rep)
            if pts is None:
                continue
        else:
            continue
        if col not in state.columns:
            continue
        krep, sorted_ok = state.column_state(col)
        if not sorted_ok:
            continue
        parts = []
        for s, e in state.segments:
            seg = krep[s:e]
            if pts is not None:
                for p in set(pts):
                    a = int(np.searchsorted(seg, p, side="left"))
                    b = int(np.searchsorted(seg, p, side="right"))
                    if b > a:
                        parts.append(np.arange(s + a, s + b, dtype=np.int64))
            else:
                op, rep = bound
                if op == "<":
                    a, b = 0, int(np.searchsorted(seg, rep, side="left"))
                elif op == "<=":
                    a, b = 0, int(np.searchsorted(seg, rep, side="right"))
                elif op == ">":
                    a, b = int(np.searchsorted(seg, rep, side="right")), e - s
                else:  # >=
                    a, b = int(np.searchsorted(seg, rep, side="left")), e - s
                if b > a:
                    parts.append(np.arange(s + a, s + b, dtype=np.int64))
        if not parts:
            return np.zeros(0, dtype=np.int64)
        idx = np.concatenate(parts)
        # ascending row order (IN points may interleave within a segment);
        # ranges are disjoint after the per-point dedup, so no unique needed
        return np.sort(idx)
    return None


def _exec_join(plan: Join, needed: Set[str], session) -> ColumnarBatch:
    pairs = E.equi_join_pairs(plan.condition)
    if pairs is None:
        raise HyperspaceException(
            f"Only conjunctive equi-joins are executable: {plan.condition!r}"
        )
    lcols = set(plan.left.output)
    on = []
    for a, b in pairs:
        if a in lcols:
            on.append((a, b))
        else:
            on.append((b, a))
    l_needed = (needed & lcols) | {l for l, _ in on}
    rcols = set(plan.right.output)
    r_needed = (needed & rcols) | {r for _, r in on}
    from hyperspace_tpu.execution.join_exec import (
        co_bucketed_join_prepared,
        inner_join,
    )

    layout = _aligned_bucket_layouts(plan, on)
    if layout is not None:
        # Shuffle-free co-bucketed join (the JoinIndexRule payoff; the
        # physical analogue of Spark SMJ over co-bucketed index scans with
        # no Exchange, JoinIndexRule.scala:619-634): the per-bucket merge
        # runs as one compiled program, buckets sharded across the mesh.
        # Prepared sides (concat + key reps + sortedness) are retained by
        # the serve cache, so a warm serve pays only match + assemble.
        num_buckets, l_bucket_cols, r_bucket_cols = layout
        from hyperspace_tpu.execution.join_exec import serve_breakdown_reset

        serve_breakdown_reset()
        l_keys = [l for l, _ in on]
        r_keys = [r for _, r in on]
        if _serve_stream_on(session):
            # Out-of-core serve (docs/out-of-core.md): buckets stream
            # through in waves sized by hyperspace.serve.stream.maxBytes —
            # prepared sides are produced, matched, expanded and RELEASED
            # per wave instead of materialized whole. Returns None when
            # either side's shape does not stream (this materializing
            # path then runs unchanged).
            streamed = _exec_join_streaming(
                plan, needed, session, layout, on, l_needed, r_needed
            )
            if streamed is not None:
                return streamed
        # Pipelined serve: both sides prepare CONCURRENTLY (each side's
        # per-bucket reads already overlap its prepare via the scan
        # pool). Gated on both children being clean index-scan shapes —
        # exactly the shapes whose execution touches no device kernels
        # and no query-shaped state, so the thread fan-out is safe. A
        # SELF-join whose sides would resolve to the same serve-cache
        # entry stays sequential: racing both sides past the shared miss
        # would double the full read+prepare the second side gets for
        # free from the first side's put.
        rels_l = _joinside_cache_relations(plan.left)
        rels_r = _joinside_cache_relations(plan.right)
        same_cached_side = (
            _serve_cache(session) is not None
            and rels_l is not None
            and rels_l == rels_r
            and l_needed == r_needed
            and l_keys == r_keys
        )
        if (
            _serve_pipeline_on(session)
            and rels_l is not None
            and rels_r is not None
            and not same_cached_side
        ):
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(
                max_workers=2, thread_name_prefix="hs-joinside"
            ) as side_pool:
                # trace.carry: contextvars do not cross pool threads —
                # the side prepares' stage spans must still attach to
                # the query's root span (identity when obs is off)
                fl = side_pool.submit(
                    _obs_trace.carry(_prepared_join_side),
                    plan.left, l_needed, session, l_bucket_cols, l_keys,
                )
                fr = side_pool.submit(
                    _obs_trace.carry(_prepared_join_side),
                    plan.right, r_needed, session, r_bucket_cols, r_keys,
                )
                lp = fl.result()
                rp = fr.result()
        else:
            lp = _prepared_join_side(
                plan.left, l_needed, session, l_bucket_cols, l_keys
            )
            rp = _prepared_join_side(
                plan.right, r_needed, session, r_bucket_cols, r_keys
            )
        mesh = session.runtime.mesh if session is not None else None
        min_rows = (
            session.conf.device_join_min_rows if session is not None else 0
        )
        joined = (
            co_bucketed_join_prepared(
                lp, rp, on, mesh, min_rows, num_shards=_serve_shards(session)
            )
            if lp is not None and rp is not None
            else None
        )
        if joined is not None:
            return joined
        import pyarrow as pa

        schema = plan.schema()
        out_cols = [c for c in plan.output if c in (needed | set(
            [x for p in on for x in p]))]
        return ColumnarBatch.from_arrow(
            pa.table({c: pa.array([], type=schema[c]) for c in out_cols})
        )
    left = _exec(plan.left, l_needed, session)
    right = _exec(plan.right, r_needed, session)
    return inner_join(left, right, on)


def _joinside_cache_relations(plan):
    """Relations whose combined file fingerprints key a cacheable
    prepared join side, or None when the child's shape is not cacheable.

    Two shapes qualify: a clean Project*(Scan) chain over an index scan
    (index-only serve), and a clean Project*(Union(Project*(Scan),
    Project*(Scan))) where the left is an index scan and the right is the
    Hybrid-Scan APPEND compensation over immutable source files — keying
    on both file sets means a further append (new file) or refresh (new
    index version) changes the fingerprint and can never serve stale.
    Delete compensation (excluded_file_ids / lineage filters) breaks the
    shape and stays uncached."""

    def walk(node):
        while isinstance(node, Project):
            node = node.child
        return node

    node = walk(plan)
    if isinstance(node, Scan) and _cacheable_scan(node.relation):
        return [node.relation]
    if isinstance(node, Union):
        left, right = walk(node.left), walk(node.right)
        if (
            isinstance(left, Scan)
            and isinstance(right, Scan)
            and _cacheable_scan(left.relation)
            and right.relation.fmt in ("parquet", "delta", "iceberg")
            and right.relation.excluded_file_ids is None
            and not right.relation.file_partition_values
            and bool(right.relation.files)
        ):
            return [left.relation, right.relation]
    return None


def _prepared_join_side(
    plan: LogicalPlan, needed: Set[str], session, bucket_cols, key_cols
):
    """A PreparedJoinSide for one co-bucketed join child, served from the
    serve cache when the child is a clean Project*(Scan) chain (the plan
    shape of an index-only scan) or a Hybrid-Scan append union of two
    such chains. Returns None for an empty side.

    On a cache miss (or with the cache off) the pipelined serve path
    streams per-bucket batches straight into
    ``prepare_join_side_pipelined``: bucket *i*'s reps/combine run while
    the scan pool is still reading bucket *i+1*, and — on the hybrid
    Union shape — the appended-files delta prepares concurrently with
    the index-side reads. Falls back to the sequential
    ``_exec_bucketed`` + ``prepare_join_side`` whenever the shape or
    caching situation is anything but the clean serve case."""
    from hyperspace_tpu.execution.join_exec import (
        prepare_join_side,
        prepare_join_side_pipelined,
    )

    cache = _serve_cache(session)
    key = None
    if cache is not None:
        rels = _joinside_cache_relations(plan)
        if rels is not None:
            from hyperspace_tpu.execution.serve_cache import file_fingerprint

            fps = tuple(file_fingerprint(r.files) for r in rels)
            if None not in fps:
                key = (
                    "joinside",
                    fps,
                    tuple(sorted(needed)),
                    tuple(key_cols),
                )
                hit = cache.get(key)
                if hit is not None:
                    return hit
    # Pipelined path only when it cannot change caching behavior: with
    # the cache off nothing is cached either way; with a joinside key the
    # raw bucketed batches are deliberately NOT cached (see below). The
    # odd corner — cache on but the file set unfingerprintable — keeps
    # the sequential path and its ("bucketed", …) entries.
    if _serve_pipeline_on(session) and (cache is None or key is not None):
        stream = _bucket_stream(plan, needed, session, bucket_cols)
        if stream is not None:
            prep = prepare_join_side_pipelined(
                stream, key_cols, num_shards=_serve_shards(session)
            )
            if prep is not None and key is not None:
                cache.put(key, prep, prep.nbytes)
            return prep
    # when a joinside entry will be cached, don't ALSO cache the raw
    # bucketed batches — the prepared side contains the same decoded data
    # (a second full copy would halve effective cache capacity)
    bs = _exec_bucketed(plan, needed, session, bucket_cols, cache_scan=key is None)
    if not bs:
        return None
    prep = prepare_join_side(bs, key_cols)
    if key is not None:
        cache.put(key, prep, prep.nbytes)
    return prep


def _stream_side_probe(plan: LogicalPlan, needed: Set[str], session, bucket_cols):
    """Wave-streamable decomposition of one join side, or None when the
    shape does not support streaming (the caller falls back to the
    materializing path). Shape scope mirrors ``_exec_bucketed`` /
    ``_bucket_stream``: a Project* chain over a clean multi-file index
    Scan, optionally through one Hybrid-Scan append Union whose
    appended-files delta is prepared ONCE up front (``_prepare_delta`` —
    ratio-capped, so it is wave-independent fixed residency). The probe
    reads only parquet footers: per-bucket row counts seed the wave
    planner's byte estimates without touching data pages."""
    from hyperspace_tpu.io.parquet import bucket_id_of_file

    sel_chain = []  # Project selects outermost-first; applied reversed
    node = plan
    nd = set(needed)
    while isinstance(node, Project):
        cols = [c for c in node.columns if c in nd] or node.columns
        sel_chain.append(cols)
        nd = set(cols)
        node = node.child
    read_cols = None
    delta_parts = None
    inner_chain = []
    if isinstance(node, Union):
        cols = [c for c in node.output if c in nd] or node.output[:1]
        read_cols = sorted(set(cols) | set(bucket_cols))
        spec = _bucket_layout(node.left)
        if spec is None:
            return None
        delta_parts = _prepare_delta(
            node.right, read_cols, session, bucket_cols, spec[0]
        )
        inner = node.left
        nd = set(read_cols)
        while isinstance(inner, Project):
            cols = [c for c in inner.columns if c in nd] or inner.columns
            inner_chain.append(cols)
            nd = set(cols)
            inner = inner.child
        node = inner
    if not isinstance(node, Scan):
        return None
    rel = node.relation
    groups: dict = {}
    for f in rel.files:
        b = bucket_id_of_file(f)
        groups.setdefault(b, []).append(f)
    streamable = (
        rel.fmt in ("parquet", "delta", "iceberg")
        and rel.excluded_file_ids is None
        and not rel.file_partition_values
        and len(rel.files) > 1
        and None not in groups
    )
    if not streamable:
        return None
    scan_cols = [c for c in rel.column_names if c in nd] or (
        rel.column_names[:1]
    )
    all_files = [f for b in sorted(groups) for f in groups[b]]
    counts = pio.file_row_counts(all_files)
    rows_of = dict(zip(all_files, counts))
    bucket_rows = {b: sum(rows_of[f] for f in groups[b]) for b in groups}
    return {
        "rel": rel,
        "groups": groups,
        "scan_cols": scan_cols,
        "bucket_rows": bucket_rows,
        "sel_chain": sel_chain,
        "inner_chain": inner_chain,
        "read_cols": read_cols,
        "delta_parts": delta_parts,
    }


def _stream_side_bytes(state) -> Dict[int, int]:
    """Estimated decoded bytes per bucket for wave packing: footer row
    counts × projected column count × 8 for the scan part (strings cost
    more than 8 bytes/row — the budget is a planning estimate, and the
    prepared side's reps/combined overhead rides on top; see
    docs/out-of-core.md for tuning), plus the real size of any delta
    part landing in the bucket."""
    est = {
        b: r * len(state["scan_cols"]) * 8
        for b, r in state["bucket_rows"].items()
    }
    if state["delta_parts"]:
        from hyperspace_tpu.execution.serve_cache import batch_nbytes

        for b, part in state["delta_parts"].items():
            est[b] = est.get(b, 0) + batch_nbytes(part)
    return est


def _stream_wave_side(state, wave, session):
    """One wave's worth of one side: the clean-scan shape returns
    ``(contiguous_batch, buckets, sizes)`` — a single threaded read whose
    decoded table IS the bucket-ordered concatenation, handed to
    ``prepare_join_side_contiguous`` with no per-bucket copies — while
    the hybrid Union shape returns a per-bucket dict (index slices merged
    with the precomputed delta parts, exactly the ``_exec_bucketed``
    Union recipe)."""
    import time as _t

    from hyperspace_tpu.execution import join_exec as _je

    groups = state["groups"]
    rel = state["rel"]
    in_scan = [b for b in wave if b in groups]
    table = None
    if in_scan:
        files = [f for b in in_scan for f in groups[b]]
        t0 = _t.perf_counter()
        table = pio.read_table(
            files, state["scan_cols"], rel.fmt,
            memory_map=_io_mmap_on(session),
        )
        _je._stage_add("scan", t0)
    if state["read_cols"] is None:
        # clean index scan: decode the wave read once, select once
        t0 = _t.perf_counter()
        batch = ColumnarBatch.from_arrow(table)
        for cols in reversed(state["sel_chain"]):
            batch = batch.select(
                [c for c in cols if c in batch.column_names]
            )
        _je._stage_add("prepare", t0)
        sizes = [state["bucket_rows"][b] for b in in_scan]
        return batch, in_scan, sizes
    # hybrid shape: per-bucket slices like _exec_bucketed's fast path,
    # inner selects, merge delta parts, outer selects
    t0 = _t.perf_counter()
    out = {}
    pos = 0
    for b in in_scan:
        c = state["bucket_rows"][b]
        bb = ColumnarBatch.from_arrow(table.slice(pos, c))
        pos += c
        for cols in reversed(state["inner_chain"]):
            bb = bb.select([x for x in cols if x in bb.column_names])
        out[b] = bb.select(state["read_cols"])
    for b in wave:
        part = state["delta_parts"].get(b)
        if part is None:
            continue
        if b in out:
            out[b] = ColumnarBatch.concat([out[b], part])
        else:
            out[b] = part
    for cols in reversed(state["sel_chain"]):
        out = {
            b: bb.select([x for x in cols if x in bb.column_names])
            for b, bb in out.items()
        }
    _je._stage_add("prepare", t0)
    return out


def _stream_wave_prepared(state, wave, key_cols, session):
    """PreparedJoinSide for one side's wave (None for an empty wave)."""
    from hyperspace_tpu.execution.join_exec import (
        prepare_join_side,
        prepare_join_side_contiguous,
    )

    side = _stream_wave_side(state, wave, session)
    if isinstance(side, dict):
        return prepare_join_side(side, key_cols) if side else None
    batch, buckets, sizes = side
    return prepare_join_side_contiguous(batch, tuple(buckets), sizes, key_cols)


def _exec_join_streaming(
    plan: Join, needed: Set[str], session, layout, on, l_needed, r_needed
):
    """Streaming per-bucket join serve: the bucket is the unit of
    residency (docs/out-of-core.md). Common buckets are packed into WAVES
    whose estimated decoded bytes across both sides fit the
    ``hyperspace.serve.stream.maxBytes`` budget (an oversized bucket runs
    as its own wave — correctness never depends on the estimate); each
    wave is read, prepared, matched, expanded, and RELEASED before the
    next wave's read begins, so peak prepared-side residency is one wave
    instead of the whole join. Wave outputs concatenate in ascending
    bucket order — bit-identical to the materializing path: buckets are
    independent, per-wave null sentinels are re-verified exactly like the
    full-side ones, and the presorted-bucket native fast path applies per
    wave whenever it applied to the full side. Returns None when either
    side's shape does not stream (caller falls back). This path
    deliberately skips the joinside/bucketed serve-cache entries: the
    point of streaming is sides too large to pin, and a wave-sized cache
    entry would alias the materializing path's keys."""
    import time as _t

    from hyperspace_tpu.execution import join_exec as _je
    from hyperspace_tpu.execution.join_exec import co_bucketed_join_prepared

    num_buckets, l_bucket_cols, r_bucket_cols = layout
    l_state = _stream_side_probe(plan.left, l_needed, session, l_bucket_cols)
    if l_state is None:
        return None
    r_state = _stream_side_probe(plan.right, r_needed, session, r_bucket_cols)
    if r_state is None:
        return None
    stream_stats_reset()
    l_keys = [l for l, _ in on]
    r_keys = [r for _, r in on]
    l_est = _stream_side_bytes(l_state)
    r_est = _stream_side_bytes(r_state)
    # only buckets present on BOTH sides can produce pairs; one-sided
    # buckets are never read at all (the materializing path reads them
    # and then drops them at the common-bucket subset)
    common = sorted(set(l_est) & set(r_est))
    budget = session.conf.serve_stream_max_bytes
    waves = []
    cur: list = []
    cur_bytes = 0
    for b in common:
        nb = l_est.get(b, 0) + r_est.get(b, 0)
        if cur and cur_bytes + nb > budget:
            waves.append(cur)
            cur, cur_bytes = [], 0
        cur.append(b)
        cur_bytes += nb
    if cur:
        waves.append(cur)
    mesh = session.runtime.mesh if session is not None else None
    min_rows = (
        session.conf.device_join_min_rows if session is not None else 0
    )
    parts = []
    if waves:
        from concurrent.futures import ThreadPoolExecutor

        # both sides of a wave read+prepare concurrently (the same
        # 2-worker side fan-out as the materializing pipelined path;
        # trace.carry keeps their stage spans on the query's root span)
        with ThreadPoolExecutor(
            max_workers=2, thread_name_prefix="hs-stream"
        ) as side_pool:
            for wave in waves:
                t0 = _t.perf_counter()
                fl = side_pool.submit(
                    _obs_trace.carry(_stream_wave_prepared),
                    l_state, wave, l_keys, session,
                )
                fr = side_pool.submit(
                    _obs_trace.carry(_stream_wave_prepared),
                    r_state, wave, r_keys, session,
                )
                lp = fl.result()
                rp = fr.result()
                joined = (
                    co_bucketed_join_prepared(
                        lp, rp, on, mesh, min_rows,
                        num_shards=_serve_shards(session),
                    )
                    if lp is not None and rp is not None
                    else None
                )
                if joined is not None:
                    parts.append(joined)
                # lp/rp (and their reps/combined) release here — the wave
                # is the residency high-water mark, not the join
                lp = rp = None
                _stream_stats_add("stream_waves")
                _stream_stats_add("stream_buckets", len(wave))
                _je._stage_add("stream_wave", t0)
    if parts:
        return ColumnarBatch.concat(parts)
    import pyarrow as pa

    schema = plan.schema()
    out_cols = [c for c in plan.output if c in (needed | set(
        [x for p in on for x in p]))]
    return ColumnarBatch.from_arrow(
        pa.table({c: pa.array([], type=schema[c]) for c in out_cols})
    )


def _literal_key_rep(value, arrow_type):
    """The literal's int64 key rep under the same path data takes
    (Column.key_rep), or None when it cannot be represented losslessly."""
    import pyarrow as pa

    from hyperspace_tpu.io.columnar import Column

    try:
        arr = pa.array([value], type=arrow_type)
    except (pa.ArrowInvalid, pa.ArrowTypeError, OverflowError, TypeError):
        return None
    col = Column.from_arrow(arr)
    if col.null_mask is not None:
        return None
    return int(col.key_rep()[0])


_MAX_PRUNE_COMBOS = 64


def _bucket_pruned_scan(plan: LogicalPlan, cond: E.Expr) -> LogicalPlan:
    """Bucket pruning: when a filter over a bucketed index scan pins every
    bucket column to literals (Eq / In conjuncts), drop the bucket files
    that cannot contain matching rows.

    The executor-side payoff of FilterIndexRule's bucketSpec — the
    reference gets this from Spark's bucket pruning when
    ``index.filterRule.useBucketSpec`` is on (IndexConstants.scala:56-57);
    here it turns a point lookup into a read of 1/num_buckets of the index.
    """
    import dataclasses
    import itertools

    from hyperspace_tpu.ops.hash import bucket_ids_np

    if not isinstance(plan, Scan) or plan.relation.bucket_spec is None:
        return plan
    rel = plan.relation
    num_buckets, bucket_cols = rel.bucket_spec
    schema = rel.schema
    conjuncts = E.split_conjuncts(cond)
    value_lists = []
    for bc in bucket_cols:
        vals = None
        for cj in conjuncts:
            norm = E.normalize_comparison(cj)
            if norm is not None:
                op, name, lit = norm
                if op == "=" and name.lower() == bc.lower():
                    vals = [lit]
                    break
            elif (
                isinstance(cj, E.In)
                and isinstance(cj.child, E.Col)
                and cj.child.name.lower() == bc.lower()
            ):
                vals = [v for v in cj.values if v is not None]
                break
        if not vals:
            return plan  # bucket column not pinned: no pruning
        value_lists.append(vals)
    n_combos = 1
    for vl in value_lists:
        n_combos *= len(vl)
    if n_combos > _MAX_PRUNE_COMBOS:
        return plan
    rep_lists = []
    for bc, vals in zip(bucket_cols, value_lists):
        reps = []
        for v in vals:
            rep = _literal_key_rep(v, schema[bc])
            if rep is None:
                return plan
            reps.append(rep)
        rep_lists.append(reps)
    # one kernel dispatch over all combinations: [k, n_combos]
    combos = np.array(
        list(itertools.product(*rep_lists)), dtype=np.int64
    ).T.reshape(len(bucket_cols), -1)
    keep_buckets = set(bucket_ids_np(combos, num_buckets).tolist())
    bucket_of = _bucket_ids_of_files(rel.files)
    kept = tuple(
        f
        for f, b in zip(rel.files, bucket_of)
        if b is None or b in keep_buckets
    )
    if len(kept) == len(rel.files):
        return plan
    return Scan(dataclasses.replace(rel, files=kept))


@_lru_cache(maxsize=1024)
def _bucket_ids_of_files(files) -> tuple:
    """Per-file bucket ids for a relation's file tuple, memoized.

    ``_bucket_pruned_scan`` used to re-run the filename regex over every
    file on every query; a relation's file tuple is its content identity
    for this purpose (bucket ids are a pure function of the immutable
    file NAMES, and a refresh/optimize changes the file set and thereby
    the key), so one parse per distinct file set suffices."""
    from hyperspace_tpu.io.parquet import bucket_id_of_file

    return tuple(bucket_id_of_file(f) for f in files)


def _fused_pipeline_on(session) -> bool:
    """Fused serve-pipeline compiler
    (``hyperspace.serve.fusedpipeline.enabled``, default on). Applies to
    sessionless execution too — a pure compute substitution with
    bit-identical output, like range pruning."""
    from hyperspace_tpu.execution.pipeline_compiler import fused_pipeline_on

    return fused_pipeline_on(session)


def _rangeprune_on(session) -> bool:
    """Zone-map range pruning (``hyperspace.serve.rangeprune.enabled``,
    default on). Unlike the serve pipeline this also applies to
    sessionless execution — pruning is a pure read-side narrowing with no
    thread fan-out of its own."""
    from hyperspace_tpu import constants as C

    if session is None:
        return C.SERVE_RANGEPRUNE_ENABLED_DEFAULT
    return session.conf.serve_rangeprune_enabled


def _range_pruned_scan(
    plan: LogicalPlan, cond: E.Expr, session
) -> LogicalPlan:
    """Zone-map pruning for index scans under a Filter: drop index files
    (and narrow survivors to matching row groups) that the predicate's
    range/Eq/In conjuncts cannot touch, per ``indexes/zonemaps.py``. The
    executor-side payoff the reference gets from Spark's parquet min/max
    pruning — generalized to whole-file drops, a vectorized pass over
    all files at once, and z-address range decomposition for z-order
    relations (docs/range-serve.md). Recurses through Project/Union so
    the Hybrid-Scan index side prunes too; non-index relations (e.g. the
    appended-files side) pass through untouched."""
    if not _rangeprune_on(session):
        return plan

    from hyperspace_tpu.indexes import zonemaps

    cache = _serve_cache(session)

    def walk(node):
        if isinstance(node, Scan):
            if cache is not None and _cacheable_scan(node.relation):
                # serve-server mode keeps FULL decoded files in RAM keyed
                # by the complete file set, shared across predicates and
                # narrowed by binary search — pruning a cacheable scan
                # would only fragment that entry into per-predicate file
                # subsets. Cold serves (cache off) and uncacheable index
                # scans (e.g. hybrid delete compensation) still prune.
                return node
            return zonemaps.prune_scan_relation(node, cond, cache)
        if isinstance(node, Project):
            child = walk(node.child)
            return node if child is node.child else Project(node.columns, child)
        if isinstance(node, Union):
            left, right = walk(node.left), walk(node.right)
            if left is node.left and right is node.right:
                return node
            return Union(left, right)
        return node

    return walk(plan)


def _pushable_literal(value, arrow_type):
    """Literal in a form pyarrow's parquet filters accept for a column of
    ``arrow_type``, or None when it must not be pushed (type-mismatched
    literals would make the dataset filter error at read time; the
    engine's own mask treats them as never-matching instead)."""
    import pyarrow as pa

    if value is None or arrow_type is None:
        return None
    if isinstance(value, (np.integer, np.floating, np.bool_)):
        value = value.item()
    if pa.types.is_temporal(arrow_type):
        if pa.types.is_duration(arrow_type):
            # duration filters are not pushed: arrow's scalar coercion for
            # timedelta literals does not mirror the engine's tick
            # lowering; skipping pushdown is always superset-safe
            return None
        if getattr(arrow_type, "tz", None) is not None:
            # tz-aware columns: arrow refuses naive-vs-aware comparisons
            return None
        # only literals exactly representable in the column type: ±inf
        # clamps and between-tick values would overflow/err in arrow's cast
        if not isinstance(E.lower_literal(value, arrow_type), np.int64):
            return None
        return E.normalize_temporal_literal(value, arrow_type)
    if pa.types.is_boolean(arrow_type):
        return value if isinstance(value, bool) else None
    if pa.types.is_integer(arrow_type) or pa.types.is_floating(arrow_type):
        if isinstance(value, bool):
            return int(value)  # engine: flag == True matches 1
        if isinstance(value, int):
            # arrow converts through C long: out-of-int64-range literals
            # raise there; the engine treats them as never-matching
            if not (-(2**63) <= value < 2**63):
                return None
            return value
        return value if isinstance(value, float) else None
    t = arrow_type
    if pa.types.is_dictionary(t):
        t = t.value_type
    if pa.types.is_string(t) or pa.types.is_large_string(t):
        return value if isinstance(value, str) else None
    return None


def _pushdown_filters(cond: E.Expr, rel):
    """Pyarrow DNF filter (single conjunction) from the predicate's
    simple conjuncts.

    Sound by construction under the ROW-LEVEL-superset invariant
    (``io/parquet.read_table``): pyarrow >= 14 applies these filters per
    row via the dataset API, so every pushed conjunct's pyarrow
    evaluation must keep a row-level superset of the rows the engine's
    own mask keeps — merely row-group-safe conjuncts (e.g. literals
    rounded toward engine semantics) must NOT be pushed. Today only
    plain col-op-literal and IN with exactly-representable literals
    qualify (null/NaN drop the same rows in both engines;
    ``_pushable_literal`` refuses lossy literal conversions), and the
    executor re-applies the full mask after the read. On a key-sorted
    index bucket this turns a point lookup into a read of the one row
    group whose min/max covers the key.
    """
    if rel.fmt not in ("parquet", "delta", "iceberg"):
        return None
    cols = {c.lower(): c for c in rel.column_names}
    out = []
    for cj in E.split_conjuncts(cond):
        norm = E.normalize_comparison(cj)
        if norm is not None:
            op, name, lit = norm
            col = cols.get(name.lower())
            if col is None:
                continue
            lit = _pushable_literal(lit, rel.schema[col])
            if lit is None:
                continue
            out.append((col, op if op != "=" else "==", lit))
        elif isinstance(cj, E.In) and isinstance(cj.child, E.Col):
            col = cols.get(cj.child.name.lower())
            if col is None:
                continue
            vals = [
                lv
                for v in cj.values
                if v is not None
                for lv in [_pushable_literal(v, rel.schema[col])]
                if lv is not None
            ]
            if not vals or len(vals) != len(
                [v for v in cj.values if v is not None]
            ):
                continue  # partial lists would under-keep: skip
            out.append((col, "in", vals))
    return out or None


def _bucket_layout(plan: LogicalPlan):
    """(num_buckets, bucket_cols) if the subtree preserves a bucketed scan
    layout (Scan with bucket_spec under Filter/Project/Union)."""
    if isinstance(plan, Scan):
        return plan.relation.bucket_spec
    if isinstance(plan, Filter):
        return _bucket_layout(plan.child)
    if isinstance(plan, Project):
        spec = _bucket_layout(plan.child)
        if spec and all(c in plan.columns for c in spec[1]):
            return spec
        return None
    if isinstance(plan, Union):
        # hybrid scan: the index side (left) defines the layout; the
        # appended side is re-bucketed at execution time
        return _bucket_layout(plan.left)
    return None


def _aligned_bucket_layouts(plan: Join, on):
    """Both sides bucketed, same count, and bucket columns positionally
    aligned through the join mapping (order matters: the bucket hash chains
    over columns in order — mirroring Spark's order-sensitive
    HashPartitioning compatibility)."""
    l_spec = _bucket_layout(plan.left)
    r_spec = _bucket_layout(plan.right)
    if not l_spec or not r_spec:
        return None
    (ln, lcols), (rn, rcols) = l_spec, r_spec
    if ln != rn or len(lcols) != len(rcols):
        return None
    mapping = {l: r for l, r in on}
    for lc, rc in zip(lcols, rcols):
        if mapping.get(lc) != rc:
            return None
    return ln, tuple(lcols), tuple(rcols)


def _exec_bucketed(
    plan: LogicalPlan, needed: Set[str], session, bucket_cols,
    cache_scan: bool = True,
):
    """Execute a linear subtree into per-bucket batches.

    Index scans recover the bucket id from file names; appended (hybrid)
    rows are hashed on device — the execution-time equivalent of the
    reference's on-the-fly shuffle of appended data
    (CoveringIndexRuleUtils.transformPlanToShuffleUsingBucketSpec:357-417).
    """
    import dataclasses

    from hyperspace_tpu.io.parquet import bucket_id_of_file
    from hyperspace_tpu.ops.hash import bucket_ids_np

    if isinstance(plan, Scan):
        rel = plan.relation
        groups = {}
        for f in rel.files:
            b = bucket_id_of_file(f)
            groups.setdefault(b, []).append(f)
        fast = (
            rel.fmt in ("parquet", "delta", "iceberg")
            and rel.excluded_file_ids is None
            and not rel.file_partition_values
            and len(rel.files) > 1
            and None not in groups
        )
        if fast:
            # one threaded read over every bucket's files, sliced back into
            # buckets via footer row counts — N small per-bucket reads pay
            # a per-call cost that dominates serve latency otherwise
            cols = [c for c in rel.column_names if c in needed] or (
                rel.column_names[:1]
            )
            cache = _serve_cache(session)
            key = None
            if cache_scan and cache is not None and _cacheable_scan(rel):
                from hyperspace_tpu.execution.serve_cache import (
                    file_fingerprint,
                )

                fp = file_fingerprint(rel.files)
                if fp is not None:
                    key = ("bucketed", fp, tuple(cols))
                    hit = cache.get(key)
                    if hit is not None:
                        return dict(hit)
            ordered = [(b, f) for b in sorted(groups) for f in groups[b]]
            counts = pio.file_row_counts([f for _, f in ordered])
            table = pio.read_table(
                [f for _, f in ordered], cols, rel.fmt,
                memory_map=_io_mmap_on(session),
            )
            per_bucket = {}
            for (b, _f), c in zip(ordered, counts):
                per_bucket[b] = per_bucket.get(b, 0) + c
            out = {}
            pos = 0
            for b in sorted(groups):
                c = per_bucket[b]
                # zero-copy arrow slice per bucket, decoded directly —
                # one decode copy total instead of decode-everything plus
                # a gather per bucket
                out[b] = ColumnarBatch.from_arrow(table.slice(pos, c))
                pos += c
            if key is not None:
                from hyperspace_tpu.execution.serve_cache import batch_nbytes

                cache.put(
                    key,
                    dict(out),
                    sum(batch_nbytes(b) for b in out.values()),
                )
            return out
        out = {}
        for b, files in groups.items():
            sub = Scan(dataclasses.replace(rel, files=tuple(files)))
            out[b] = _exec_scan(sub, needed, session)
        return out
    if isinstance(plan, Filter):
        child_needed = set(needed) | E.references(plan.condition)
        out = {}
        for b, batch in _exec_bucketed(
            plan.child, child_needed, session, bucket_cols, cache_scan
        ).items():
            out[b] = batch.filter(_filter_mask(plan.condition, batch, session))
        return out
    if isinstance(plan, Project):
        cols = [c for c in plan.columns if c in needed] or plan.columns
        return {
            b: batch.select([c for c in cols if c in batch.column_names])
            for b, batch in _exec_bucketed(
                plan.child, set(cols), session, bucket_cols, cache_scan
            ).items()
        }
    if isinstance(plan, Union):
        cols = [c for c in plan.output if c in needed] or plan.output[:1]
        read_cols = sorted(set(cols) | set(bucket_cols))
        left = {
            b: batch.select(read_cols)
            for b, batch in _exec_bucketed(
                plan.left, set(read_cols), session, bucket_cols, cache_scan
            ).items()
        }
        spec = _bucket_layout(plan.left)
        num_buckets = spec[0]
        appended = _exec(plan.right, set(read_cols), session).select(read_cols)
        if appended.num_rows:
            reps = appended.key_reps(list(bucket_cols))
            bids = bucket_ids_np(reps, num_buckets)
            for b in np.unique(bids):
                part = appended.filter(bids == b)
                key = int(b)
                if key in left:
                    left[key] = ColumnarBatch.concat([left[key], part])
                else:
                    left[key] = part
        return left
    raise HyperspaceException(
        f"Node not supported in bucketed execution: {type(plan).__name__}"
    )


def _bucket_stream(plan: LogicalPlan, needed: Set[str], session, bucket_cols):
    """Ordered ``[(bucket, fetch)]`` pairs for a clean linear subtree —
    the pipelined twin of :func:`_exec_bucketed` (docs/serve-pipeline.md).

    Per-bucket parquet reads are submitted to the shared scan pool
    (``io/scan.scan_pool``) up front; each ``fetch()`` blocks until its
    bucket's decoded batch is ready, so the consumer
    (``prepare_join_side_pipelined``) overlaps bucket *i*'s prepare with
    the reads of buckets *i+1…*. On the hybrid Union shape the
    appended-files delta prepare runs on the pool concurrently with the
    index-side reads (``_prepare_delta``). Batches are produced by the
    same select/concat calls as the sequential path, per bucket — the
    two paths are differential-tested bit-identical. Returns None when
    the shape/format does not support streaming (caller falls back)."""
    import time as _t

    from hyperspace_tpu.execution import join_exec as _je
    from hyperspace_tpu.io.parquet import bucket_id_of_file
    from hyperspace_tpu.io.scan import scan_pool

    if isinstance(plan, Scan):
        rel = plan.relation
        groups: dict = {}
        for f in rel.files:
            b = bucket_id_of_file(f)
            groups.setdefault(b, []).append(f)
        streamable = (
            rel.fmt in ("parquet", "delta", "iceberg")
            and rel.excluded_file_ids is None
            and not rel.file_partition_values
            and len(rel.files) > 1
            and None not in groups
        )
        if not streamable:
            return None
        cols = [c for c in rel.column_names if c in needed] or (
            rel.column_names[:1]
        )
        fmt = rel.fmt

        def read_bucket(files):
            # pure Arrow read in the worker — the C++ readers run on
            # Arrow's own pool and release the GIL, so N in-flight reads
            # genuinely overlap; the (GIL-bound) SoA decode happens on
            # the consumer thread as the first step of that bucket's
            # prepare, not here where it would serialize the workers
            t0 = _t.perf_counter()
            table = pio.read_table(files, cols, fmt)
            _je._stage_add("scan", t0)
            return table

        def decode(fut):
            def run():
                table = fut.result()
                t0 = _t.perf_counter()
                batch = ColumnarBatch.from_arrow(table)
                _je._stage_add("prepare", t0)
                return batch

            return run

        pool = scan_pool()
        # scan-pool workers record the "scan" stage span; carry the
        # query's trace context across the pool boundary (no-op obs-off)
        read_traced = _obs_trace.carry(read_bucket)
        return [
            (b, decode(pool.submit(read_traced, list(groups[b]))))
            for b in sorted(groups)
        ]
    if isinstance(plan, Project):
        cols = [c for c in plan.columns if c in needed] or plan.columns
        child = _bucket_stream(plan.child, set(cols), session, bucket_cols)
        if child is None:
            return None

        def project(fetch, cols=cols):
            def run():
                batch = fetch()
                return batch.select(
                    [c for c in cols if c in batch.column_names]
                )

            return run

        return [(b, project(fetch)) for b, fetch in child]
    if isinstance(plan, Union):
        cols = [c for c in plan.output if c in needed] or plan.output[:1]
        read_cols = sorted(set(cols) | set(bucket_cols))
        spec = _bucket_layout(plan.left)
        if spec is None:
            return None
        # the delta prepare is submitted FIRST so it takes a pool worker
        # immediately and runs concurrently with the index-side bucket
        # reads queued right after — off the serve critical path; with
        # the serve cache on, repeat queries skip it entirely
        # (fingerprint-keyed ("delta", …) entry)
        delta_fut = scan_pool().submit(
            _obs_trace.carry(_prepare_delta), plan.right, read_cols, session,
            bucket_cols, spec[0],
        )
        left = _bucket_stream(
            plan.left, set(read_cols), session, bucket_cols
        )
        if left is None:
            # rare fallback (e.g. single-file index side): surface any
            # delta read error here — the sequential path would hit the
            # same files — and let its cache entry warm the retry
            delta_fut.result()
            return None

        def select_left(fetch, read_cols=read_cols):
            return lambda: fetch().select(read_cols)

        left_map = {b: select_left(fetch) for b, fetch in left}

        def merged(b):
            def run():
                parts = delta_fut.result()
                part = parts.get(b)
                if b not in left_map:
                    return part
                batch = left_map[b]()
                if part is None:
                    return batch
                return ColumnarBatch.concat([batch, part])

            return run

        # Delta-only buckets (appended keys hashing into buckets the
        # index side has no file for) interleave by bucket id, exactly
        # like the sequential Union branch's dict after sorting. When the
        # index side already covers EVERY bucket of the layout — the
        # normal covering-index state — the delta cannot create new
        # buckets, so the bucket list is known without blocking on the
        # delta future and per-bucket prepare starts immediately (each
        # merged fetch blocks on the delta only when its own bucket
        # prepares). Only an index with empty buckets pays the upfront
        # wait for the delta's bucket set.
        if len(left_map) == spec[0]:
            all_buckets = sorted(left_map)
        else:
            all_buckets = sorted(
                set(left_map) | set(delta_fut.result().keys())
            )
        return [(b, merged(b)) for b in all_buckets]
    return None


def _prepare_delta(
    plan: LogicalPlan, read_cols, session, bucket_cols, num_buckets: int
):
    """Per-bucket parts of the hybrid-scan appended-files delta: read the
    appended source rows, hash them into the index's bucket layout, and
    split — the execution-time equivalent of the reference's on-the-fly
    shuffle of appended data, hoisted off the serve critical path.

    With serve-server mode on, the result is cached keyed by the delta
    FILE FINGERPRINT (+ columns, bucket columns, bucket count): appended
    source files are immutable once written, a further append changes
    the file set and therefore the key, so repeated hybrid queries pay
    only the per-bucket merge."""
    import time as _t

    from hyperspace_tpu.execution import join_exec as _je

    t0 = _t.perf_counter()
    cache = _serve_cache(session)
    key = None
    if cache is not None:
        node = plan
        while isinstance(node, Project):
            node = node.child
        if (
            isinstance(node, Scan)
            and node.relation.excluded_file_ids is None
            and not node.relation.file_partition_values
            and node.relation.files
        ):
            from hyperspace_tpu.execution.serve_cache import file_fingerprint

            fp = file_fingerprint(node.relation.files)
            if fp is not None:
                key = (
                    "delta",
                    fp,
                    tuple(read_cols),
                    tuple(bucket_cols),
                    num_buckets,
                )
                hit = cache.get(key)
                if hit is not None:
                    return hit
    appended = _exec(plan, set(read_cols), session).select(read_cols)
    parts = {}
    if appended.num_rows:
        from hyperspace_tpu.ops.hash import bucket_ids_np

        reps = appended.key_reps(list(bucket_cols))
        bids = bucket_ids_np(reps, num_buckets)
        for b in np.unique(bids):
            parts[int(b)] = appended.filter(bids == b)
    if key is not None:
        from hyperspace_tpu.execution.serve_cache import batch_nbytes

        cache.put(
            key, dict(parts), sum(batch_nbytes(p) for p in parts.values())
        )
    _je._stage_add("delta", t0)
    return parts


def _filter_mask(
    cond: E.Expr, batch: ColumnarBatch, session=None
) -> np.ndarray:
    from hyperspace_tpu import constants as C

    min_rows = (
        session.conf.device_filter_min_rows
        if session is not None
        else C.EXECUTION_DEVICE_FILTER_MIN_ROWS_DEFAULT
    )
    if batch.num_rows < min_rows:
        # host-resident batch below the device threshold: numpy beats the
        # host->device->host round trip (see constants.py rationale).
        # A pure range/Eq conjunction takes the fused single-pass mask
        # (native hs_range_mask / numpy twin, ops/filter.py) instead of
        # the per-conjunct interpreter chain — bit-identical output,
        # gated with the rest of the range serve plane.
        if _rangeprune_on(session):
            from hyperspace_tpu.ops.filter import fused_range_mask

            fused = fused_range_mask(cond, batch)
            if fused is not None:
                return fused
        return E.filter_mask(cond, batch)
    try:
        return device_filter_mask(cond, batch)
    except Unsupported:
        return E.filter_mask(cond, batch)


def _exec_scan(
    plan: Scan, needed: Set[str], session, pushdown=None
) -> ColumnarBatch:
    rel = plan.relation
    cols = [c for c in rel.column_names if c in needed] or rel.column_names[:1]
    read_cols = list(cols)
    # Hybrid-Scan delete compensation: the lineage column must be read to
    # apply the NOT-IN filter (CoveringIndexRuleUtils.scala:244-253), even
    # if the query does not project it.
    from hyperspace_tpu.constants import DATA_FILE_NAME_ID

    if rel.excluded_file_ids is not None and DATA_FILE_NAME_ID not in read_cols:
        read_cols.append(DATA_FILE_NAME_ID)
    if not rel.files:
        import pyarrow as pa

        empty = pa.table(
            {c: pa.array([], type=rel.schema[c]) for c in cols}
        )
        return ColumnarBatch.from_arrow(empty)
    if rel.file_row_groups is not None:
        # zone-map row-group narrowing (executor._range_pruned_scan):
        # read only the surviving row groups; the residual mask the
        # caller applies makes over-reading harmless and under-reading
        # impossible (superset contract, indexes/zonemaps.py). Pyarrow
        # pushdown filters don't compose with explicit row-group reads —
        # the narrowing already did the row-group half of their job.
        table = pio.read_table_row_groups(
            list(rel.files), list(rel.file_row_groups), read_cols, rel.fmt
        )
    else:
        table = pio.read_table(
            list(rel.files), read_cols, rel.fmt, filters=pushdown,
            memory_map=_io_mmap_on(session),
        )
    batch = ColumnarBatch.from_arrow(table)
    if rel.excluded_file_ids is not None:
        lineage = batch.column(DATA_FILE_NAME_ID).values
        mask = ~np.isin(lineage, np.array(rel.excluded_file_ids, dtype=np.int64))
        batch = batch.filter(mask)
    return batch.select(cols)
