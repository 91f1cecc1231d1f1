"""Covering-index build pipeline (device data plane).

TPU-native re-design of ``CoveringIndex.createIndexData:140-192`` +
``write:56-71`` + ``CoveringIndexTrait`` refresh/optimize (:32-135):

    host scan (arrow, per source file)  →  SoA batches w/ lineage column
      →  murmur3 bucket hash                      [ops/hash, XLA]
      →  all-to-all over the mesh (>1 device)     [parallel/shuffle]
      →  lexsort by (bucket, keys)                [ops/sort, XLA]
      →  one parquet file per bucket under the new v__=N dir

Lineage (`_data_file_id`) is attached as a constant int64 column per source
file during the scan — the moral equivalent of the reference's
``input_file_name()`` ⋈ broadcast(fileId map) join
(CoveringIndex.scala:177-186) without needing a join at all, because our
scan is already per-file.

Single-host note: after the device exchange all shards live in this
process, so one host writes every bucket. On a multi-host mesh each host
writes only the buckets its local shards own; the layout (one file per
bucket, bucket id in the file name) is identical.

Datasets larger than the configured memory budget
(``hyperspace.index.build.memoryBudgetBytes``) never materialize whole:
``create_covering_index`` hands back a lazy :class:`SourceScan` and
``_write_bucketed_streaming`` runs the pipeline in waves with per-bucket
disk spill and a final per-bucket merge sort (peak memory = one wave +
one bucket). Incremental refresh streams BOTH sides the same way: the
appended source files and — via ``SourceScan.excluded_lineage_ids`` —
the previous index data minus deleted-lineage rows.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import os
import threading as _threading
import time as _time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

from hyperspace_tpu.constants import (
    DATA_FILE_NAME_ID,
    INDEX_FILE_PREFIX as C_INDEX_FILE_PREFIX,
    LINEAGE_PROPERTY,
)
from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.indexes.base import UpdateMode
from hyperspace_tpu.io import parquet as pio
from hyperspace_tpu.obs import metrics as _obs_metrics
from hyperspace_tpu.obs import trace as _obs_trace
from hyperspace_tpu.io.columnar import Column, ColumnarBatch
from hyperspace_tpu.ops.hash import bucket_ids_np
from hyperspace_tpu.ops.sort import sort_permutation
from hyperspace_tpu.utils import resolver

_log = logging.getLogger("hyperspace_tpu.build")


# ---------------------------------------------------------------------------
# Scan side: build index data from source files
# ---------------------------------------------------------------------------


def _scan_with_lineage(
    files: Sequence[str],
    fmt: str,
    columns: List[str],
    file_ids: Optional[Dict[str, int]],
) -> ColumnarBatch:
    """Read the projection from each source file; attach `_data_file_id`
    when lineage is on (CoveringIndex.createIndexData:177-186).

    The four phases — the parquet read, the decode to columns, the
    lineage fill, the one concat of the lot — are timed here, where they
    run, and added to the span live in the caller's context
    (``read_s``, ``decode_s``, ``lineage_s``, ``concat_s``: sums over
    the files, which are read one after another, so they add up to a
    ``scan`` stage but for the loop around them; ``max_read_s`` is the
    slowest file's read). That span is the ``scan`` stage of an
    in-memory build; the streamed build opens none around its waves, so
    there they sum on the action's root."""
    now = _time.perf_counter_ns
    read_ns = decode_ns = lineage_ns = max_read_ns = 0
    batches = []
    for f in files:
        t0 = now()
        t = pio.read_table([f], columns, fmt)
        t1 = now()
        b = ColumnarBatch.from_arrow(t)
        t2 = now()
        if file_ids is not None:
            fid = np.full(b.num_rows, file_ids[f], dtype=np.int64)
            b = b.with_column(
                DATA_FILE_NAME_ID, Column("numeric", pa.int64(), values=fid)
            )
        batches.append(b)
        read_ns += t1 - t0
        max_read_ns = max(max_read_ns, t1 - t0)
        decode_ns += t2 - t1
        lineage_ns += now() - t2
    if not batches:
        raise HyperspaceException("No source files to index")
    t0 = now()
    out = ColumnarBatch.concat(batches)
    sp = _obs_trace.current()
    if sp is not None:
        _add_seconds(
            sp,
            read_s=read_ns,
            decode_s=decode_ns,
            lineage_s=lineage_ns,
            concat_s=now() - t0,
        )
        sp.set(
            "max_read_s",
            max(sp.attrs.get("max_read_s", 0.0), round(max_read_ns / 1e9, 6)),
        )
    return out


def _add_seconds(sp, **ns: int) -> None:
    """Add each ``key=nanoseconds`` into the span's attr of that name,
    as seconds: a pass that runs more than once under one span (a
    composite scan's parts) sums there, as ``sum_s`` does."""
    for key, value in ns.items():
        sp.set(key, round(sp.attrs.get(key, 0.0) + value / 1e9, 6))


@dataclasses.dataclass
class SourceScan:
    """Lazy build-side input: what to read, not the rows themselves.

    The carrier of the >HBM streaming build — when the estimated
    materialized size exceeds ``hyperspace.index.build.memoryBudgetBytes``
    the build keeps this descriptor and ``write_bucketed`` streams it in
    waves instead of materializing one giant batch (the role Spark's
    disk-backed shuffle plays for the reference,
    covering/CoveringIndex.scala:58-61).
    """

    files: Tuple[str, ...]
    fmt: str
    columns: Tuple[str, ...]  # projection to read
    file_ids: Optional[Dict[str, int]]  # lineage ids (None = lineage off)
    select_cols: Optional[Tuple[str, ...]] = None  # output column order
    # per-file estimated materialized bytes, computed once at create time
    # (footer parses are a round trip each on object stores)
    file_sizes: Optional[Tuple[int, ...]] = None
    # rows whose stored lineage id is listed are dropped at materialize
    # time — lets refresh's delete compensation stream previous index
    # data instead of materializing it whole
    excluded_lineage_ids: Optional[Tuple[int, ...]] = None

    def process_local(self) -> "SourceScan":
        """This process's file subset (``files[p::P]``) — the multi-host
        build feed (docs/MULTIHOST.md): each host scans, hashes and
        exchanges only its own rows (the exchange moves them straight to
        their owner host via ``make_array_from_process_local_data``, no
        round-trip through process 0). Global row order becomes
        process-major; identity on a single-process job."""
        import jax

        nproc = jax.process_count()
        if nproc <= 1:
            return self
        p = jax.process_index()
        return dataclasses.replace(
            self,
            files=self.files[p::nproc],
            file_sizes=(
                self.file_sizes[p::nproc]
                if self.file_sizes is not None
                else None
            ),
        )

    def empty_batch(self) -> ColumnarBatch:
        """Zero-row batch with this scan's exact output structure — the
        stripe a process contributes when a wave (or the whole job) has
        no files for it. Parquet-family sources read only the first
        file's footer schema (no row reads); anything else falls back to
        materializing one file and slicing it to zero rows."""
        if self.fmt in ("parquet", "delta", "iceberg"):
            try:
                import pyarrow.parquet as pq

                t = pq.read_schema(self.files[0]).empty_table()
                b = ColumnarBatch.from_arrow(t.select(list(self.columns)))
                if self.file_ids is not None:
                    b = b.with_column(
                        DATA_FILE_NAME_ID,
                        Column(
                            "numeric",
                            pa.int64(),
                            values=np.zeros(0, dtype=np.int64),
                        ),
                    )
                if self.select_cols is not None:
                    b = b.select(list(self.select_cols))
                return b
            except (
                OSError,
                KeyError,
                pa.ArrowInvalid,
                pa.ArrowNotImplementedError,
            ):  # nested/exotic schema or unreadable footer: pay the row read
                pass
        b = self.materialize(list(self.files[:1]))
        return b.filter(np.zeros(b.num_rows, dtype=bool))

    def materialize(self, files: Optional[Sequence[str]] = None) -> ColumnarBatch:
        batch = _scan_with_lineage(
            files if files is not None else self.files,
            self.fmt,
            list(self.columns),
            self.file_ids,
        )
        if self.excluded_lineage_ids:
            lineage = batch.column(DATA_FILE_NAME_ID).values
            keep = ~np.isin(
                lineage, np.array(self.excluded_lineage_ids, dtype=np.int64)
            )
            batch = batch.filter(keep)
        if self.select_cols is not None:
            batch = batch.select(list(self.select_cols))
        return batch

    def select(self, cols: Sequence[str]) -> "SourceScan":
        return dataclasses.replace(self, select_cols=tuple(cols))

    def stats_view(self, stat_cols: Sequence[str]) -> "SourceScan":
        """A projection of this scan reading only ``stat_cols`` (plus the
        lineage column when delete exclusion applies, so excluded rows do
        not contribute to encoding statistics)."""
        cols = tuple(stat_cols)
        read = cols
        if self.excluded_lineage_ids and DATA_FILE_NAME_ID not in read:
            read = read + (DATA_FILE_NAME_ID,)
        return dataclasses.replace(
            self, columns=read, file_ids=None, select_cols=cols
        )

    def estimated_bytes(self) -> int:
        if self.file_sizes is not None:
            return sum(self.file_sizes)
        return estimated_materialized_bytes(self.files, self.fmt)


@dataclasses.dataclass
class CompositeScan:
    """Several :class:`SourceScan` parts streamed as one input.

    Incremental refresh mixes heterogeneous inputs — appended SOURCE
    files (projection + lineage attach) and previous INDEX files
    (lineage-filtered for deletes). Each keeps its own read semantics;
    wave planning and materialization see one ordered file list. All
    parts must select the same output columns."""

    scans: Tuple[SourceScan, ...]

    @property
    def files(self) -> Tuple[str, ...]:
        return tuple(f for s in self.scans for f in s.files)

    @property
    def fmt(self) -> str:
        return self.scans[0].fmt

    @property
    def file_sizes(self) -> Tuple[int, ...]:
        out: List[int] = []
        for s in self.scans:
            out.extend(
                s.file_sizes
                if s.file_sizes is not None
                else per_file_materialized_bytes(s.files, s.fmt)
            )
        return tuple(out)

    def materialize(self, files: Optional[Sequence[str]] = None) -> ColumnarBatch:
        wanted = set(self.files if files is None else files)
        parts = []
        # scans are ordered and wave file lists are contiguous slices of
        # self.files, so per-scan grouping preserves global row order
        for s in self.scans:
            sub = [f for f in s.files if f in wanted]
            if sub:
                parts.append(s.materialize(sub))
        if not parts:
            raise HyperspaceException("No files to materialize")
        return ColumnarBatch.concat(parts)

    def process_local(self) -> "CompositeScan":
        return CompositeScan(tuple(s.process_local() for s in self.scans))

    def empty_batch(self) -> ColumnarBatch:
        # all parts select the same output columns (class contract)
        return self.scans[0].empty_batch()

    def select(self, cols: Sequence[str]) -> "CompositeScan":
        return CompositeScan(tuple(s.select(cols) for s in self.scans))

    def stats_view(self, stat_cols: Sequence[str]) -> "CompositeScan":
        return CompositeScan(
            tuple(s.stats_view(stat_cols) for s in self.scans)
        )

    def estimated_bytes(self) -> int:
        return sum(s.estimated_bytes() for s in self.scans)


def per_file_materialized_bytes(files: Sequence[str], fmt: str) -> List[int]:
    """Per-file rough in-memory size: parquet uncompressed data size from
    footers; other formats via on-disk size with an expansion factor."""
    if fmt in ("parquet", "delta", "iceberg"):
        import pyarrow.parquet as pq

        def uncompressed(p):
            md = pq.ParquetFile(p).metadata
            return sum(
                md.row_group(i).total_byte_size for i in range(md.num_row_groups)
            )

        return [uncompressed(f) for f in files]
    return [os.path.getsize(f) * 2 for f in files]


def estimated_materialized_bytes(files: Sequence[str], fmt: str) -> int:
    return sum(per_file_materialized_bytes(files, fmt))


def plan_waves(
    files: Sequence[str],
    fmt: str,
    budget: int,
    file_sizes: Optional[Sequence[int]] = None,
) -> List[List[str]]:
    """Greedy pack files into waves of estimated materialized size <=
    ``budget`` (always at least one file per wave — a single file larger
    than the budget still has to be read whole). ``file_sizes`` reuses
    estimates computed at create time instead of re-parsing footers."""
    if file_sizes is None:
        file_sizes = per_file_materialized_bytes(files, fmt)
    waves: List[List[str]] = []
    cur: List[str] = []
    cur_bytes = 0
    for f, sz in zip(files, file_sizes):
        if cur and cur_bytes + sz > budget:
            waves.append(cur)
            cur, cur_bytes = [], 0
        cur.append(f)
        cur_bytes += sz
    if cur:
        waves.append(cur)
    return waves


def resolve_index_schema(rel, config, properties: Dict[str, str]):
    """(indexed, included, lineage, schema_json) — shared by data-building
    ``create_covering_index`` and data-free ``describe_index`` so the
    begin-phase and final log entries can never diverge."""
    import json

    nested = resolver.nested_available_from(rel.column_names)
    indexed = [
        rc.normalized_name
        for rc in resolver.require_resolve(
            config.indexed_columns, rel.column_names, nested_available=nested
        )
    ]
    included = [
        rc.normalized_name
        for rc in resolver.require_resolve(
            config.included_columns, rel.column_names, nested_available=nested
        )
    ]
    lineage = str(properties.get(LINEAGE_PROPERTY, "false")).lower() == "true"
    schema = rel.schema
    schema_json = json.dumps(
        [[c, str(schema[c])] for c in indexed + included]
        + ([[DATA_FILE_NAME_ID, "int64"]] if lineage else [])
    )
    return indexed, included, lineage, schema_json


def describe_covering_index(ctx, source_df, config, properties: Dict[str, str]):
    """CoveringIndex object without scanning data (begin-phase log entry)."""
    from hyperspace_tpu.indexes.covering import CoveringIndex

    rel = _single_relation(source_df)
    indexed, included, _lineage, schema_json = resolve_index_schema(
        rel, config, properties
    )
    return CoveringIndex(
        indexed, included, schema_json, ctx.session.conf.num_buckets,
        dict(properties),
    )


def _single_relation(source_df):
    leaves = source_df.logical_plan.collect_leaves()
    if len(leaves) != 1:
        raise HyperspaceException(
            f"Index source must have exactly one relation; got {len(leaves)}"
        )
    return leaves[0].relation


def prepare_covering_index(ctx, source_df, config, properties: Dict[str, str]):
    """(CoveringIndex, lazy SourceScan) — the resolution + lineage-id
    registration half of index creation, with the data side still lazy
    (callers that stream — the z-order incremental refresh — compose the
    scan further before any row is read)."""
    from hyperspace_tpu.indexes.covering import CoveringIndex

    reset_build_breakdown()
    with stage("resolve"):
        rel = _single_relation(source_df)
        indexed, included, lineage, schema_json = resolve_index_schema(
            rel, config, properties
        )
        file_ids = None
        if lineage:
            # Key file ids by the PROVIDER's (path,size,mtime) view — the
            # same keys create_metadata_relation records — or lineage ids
            # and the log entry's ids diverge for lake sources (Delta
            # mtimes come from the log, Iceberg pins mtime=0).
            file_ids = {}
            for path, size, mtime in source_file_infos(ctx.session, rel):
                file_ids[path] = ctx.file_id_tracker.add_file(
                    path, size, mtime
                )
        index = CoveringIndex(
            indexed_columns=indexed,
            included_columns=included,
            schema_json=schema_json,
            num_buckets=ctx.session.conf.num_buckets,
            properties=dict(properties),
        )
        budget = ctx.session.conf.build_memory_budget
        sizes = (
            per_file_materialized_bytes(rel.files, rel.fmt) if budget else None
        )
        scan = SourceScan(
            files=tuple(rel.files),
            fmt=rel.fmt,
            columns=tuple(indexed + included),
            file_ids=file_ids,
            file_sizes=tuple(sizes) if sizes is not None else None,
        )
    return index, scan


# Per-stage wall times of the most recent build, reset at each
# create/refresh data op — the bench publishes these so the throughput
# story names its bottleneck (SURVEY §7 hard part #4: measure before
# moving parquet decode on-device). Keys are the names ``stage`` below
# was entered with: the four long-standing ones (scan / hash_shuffle /
# sort / write) and whatever else the op ran (resolve, dict_probe,
# sidecar_capture), plus — on a mesh — the exchange's exchange_plan /
# pack / exchange / unpack, whose seconds are those of the same-named
# spans ``parallel/shuffle.py`` records under ``hash_shuffle``
# (``_record_shuffle_telemetry``). Under the sharded tail the sort/write
# stages run per shard concurrently, so those values are BUSY time
# summed across shards (may exceed wall time — the excess over
# ``tail_wall`` is the sharding win); ``tail_shards`` records how many
# shard tails ran.
#
# Obs plane (docs/observability.md): this dict is the backing storage
# of a REGISTERED instrument — ``registry.stage_timer`` below adopts
# the exact dict + lock, so the registry's Prometheus snapshot and
# every legacy reader share one storage (SHARED_STATE unchanged). It is
# process-global and last-writer-wins; the account that is safe under
# two concurrent actions is the action's trace, which ``stage`` feeds
# with the same measurement.
last_build_breakdown: Dict[str, float] = {}
_build_bd_lock = _threading.Lock()
_obs_metrics.registry.stage_timer(
    "hs_build_stage_seconds",
    "build stage busy seconds (breakdown view)",
    data=last_build_breakdown,
    lock=_build_bd_lock,
)

# Telemetry of the most recent build beside the stage seconds: the
# exchange plane's snapshot (``parallel/shuffle.last_shuffle_stats`` —
# chosen strategy, capacity, per-(shard, peer) skew, and the seconds and
# bytes of its spans: ``shuffle_plan_s`` / ``pack_s`` / ``exchange_s`` /
# ``unpack_s``, ``shuffle_wire_bytes`` / ``slot_bytes`` / ``h2d_bytes`` /
# ``d2h_bytes``), folded in per exchange by ``_record_shuffle_telemetry``
# (seconds and bytes summed across waves, skew carried as max/mean +
# wave count) so the bench and operators read one coherent snapshot.
# Every second in it is a span's: the action trace holds the intervals.
last_build_telemetry: Dict[str, object] = {}


def _breakdown_add(name: str, seconds: float) -> None:
    with _build_bd_lock:
        last_build_breakdown[name] = (
            last_build_breakdown.get(name, 0.0) + seconds
        )


@contextlib.contextmanager
def stage(name: str, **attrs):
    """THE build stage hook — the only way a build-plane stage is
    recorded: ``with stage("sort") as sp:`` opens a child span of the
    running action's trace (``obs/trace.span``, which also enters the
    ``hs.<name>`` profiler annotation, so a profiled run holds the stage
    on the device trace's clock) and on exit adds the SAME seconds to
    ``last_build_breakdown[name]`` — one measurement, two views. Yields
    the span, for attrs that summarize repeated work (``buckets``,
    ``sum_s``, ``max_s``: never a span per bucket or per file). Every
    stage span also says what CPU its seconds had: ``cpu_s``, the
    process's CPU seconds over the stage (``time.process_time_ns``: all
    threads, pyarrow's and the native pools' included), so ``cpu_s /
    seconds`` is the cores the stage kept busy and a stage that waited
    reads wall up and ``cpu_s`` flat — or, on a span that names its
    ``shard`` (the mesh's tails run side by side, and the process's
    clock would count the neighbours), ``thread_cpu_s``, the task's own
    ``time.thread_time_ns``. Outside an action the span is the no-op
    singleton and only the breakdown is fed."""
    t0 = _time.perf_counter_ns()
    # the shard tails run side by side: a span that names its shard
    # holds its own thread's CPU seconds, under a name of their own
    cpu_key, cpu_clock = (
        ("thread_cpu_s", _time.thread_time_ns)
        if "shard" in attrs
        else ("cpu_s", _time.process_time_ns)
    )
    sp = _obs_trace.NOOP
    try:
        with _obs_trace.span(name, **attrs) as sp:
            cpu0 = cpu_clock()
            try:
                yield sp
            finally:
                sp.set(cpu_key, round((cpu_clock() - cpu0) / 1e9, 6))
    finally:
        seconds = sp.duration_s
        if seconds is None:  # no live trace: the stage's own clock
            seconds = (_time.perf_counter_ns() - t0) / 1e9
        _breakdown_add(name, seconds)


def sidecar_published(sp, paths: Sequence[str], publish_s: float) -> None:
    """What a ``sidecar_capture`` stage published — its seconds and
    bytes on the stage's span, the bytes also summed onto the action's
    root (``sidecar_bytes``)."""
    n_bytes = _files_bytes(paths)
    sp.set("publish_s", round(publish_s, 6))
    sp.set("bytes", n_bytes)
    _obs_trace.accumulate("sidecar_bytes", n_bytes)


def _repeat_attrs(
    sp, timings: Sequence[Tuple[float, float]], count_key: str
) -> None:
    """Summarize repeated work on its enclosing span — count, sum and
    the slowest one — so a single stalled bucket shows as ``max_s`` far
    above ``sum_s / count`` without a span apiece. ``timings`` holds a
    task's (seconds, CPU seconds of its own thread): ``cpu_sum_s`` is
    the latter summed, so ``sum_s - cpu_sum_s`` is the seconds the tasks
    were off a CPU (a native sort's helper threads are not the task's
    thread: their CPU is in the stage's ``cpu_s`` alone)."""
    seconds = [sec for sec, _cpu in timings]
    sp.set(count_key, len(seconds))
    sp.set("sum_s", round(float(sum(seconds)), 6))
    sp.set("max_s", round(float(max(seconds, default=0.0)), 6))
    sp.set("cpu_sum_s", round(float(sum(cpu for _s, cpu in timings)), 6))


def reset_build_breakdown() -> None:
    """Called at the entry of every data op (create via
    prepare_covering_index; refresh/optimize call it directly) so the
    breakdown never mixes two ops' stage times. Takes the breakdown
    lock: a reset must never interleave with a sharded-tail worker's
    ``stage`` read-modify-write (HS602, SHARED_STATE). Also rearms
    the shuffle's once-per-build skew warning (the streaming build runs
    one exchange per wave; the warning fires at most once per op while
    telemetry records every wave)."""
    from hyperspace_tpu.parallel import shuffle as _shuffle

    _shuffle.reset_skew_warning()
    with _build_bd_lock:
        last_build_breakdown.clear()
        last_build_telemetry.clear()


def lazy_or_materialized(ctx, scan):
    """THE build memory-budget rule, in one place: keep the scan lazy
    (streamed at write time through the wave loop) when its estimated
    materialized size exceeds ``hyperspace.index.build.memoryBudgetBytes``,
    else materialize now. Accepts SourceScan or CompositeScan. On a
    multi-process job each process materializes only its own file subset
    (``process_local``) — the exchange routes rows to their owner host."""
    budget = ctx.session.conf.build_memory_budget
    if budget and scan.estimated_bytes() > budget:
        return scan
    with stage("scan") as sp:
        local = scan.process_local()
        if local.files:
            out = local.materialize()
        else:
            # more hosts than files: this process contributes zero rows
            # but must still know the schema (and later join every
            # exchange collective) — a zero-row batch from the footer
            # schema
            out = scan.empty_batch()
        sp.set("files", len(local.files))
        sp.set("rows", int(out.num_rows))
        _obs_trace.accumulate("rows", int(out.num_rows))
        _obs_trace.accumulate("source_bytes", _files_bytes(local.files))
    return out


def _files_bytes(paths) -> int:
    """On-disk bytes of ``paths`` (0 for one that cannot be stat'ed — a
    counter never fails a build)."""
    total = 0
    for p in paths:
        try:
            total += os.path.getsize(p)
        except OSError:
            pass
    return total


def previous_index_scan(
    ctx, previous_content, schema_cols, deleted_source_file_ids
):
    """Lazy scan of a previous index version's data files minus
    deleted-lineage rows (the refresh delete-compensation input). File
    sizes are computed once here when a budget is set — footer parses
    are a round trip each on object stores."""
    files = tuple(previous_content.files)
    sizes = (
        tuple(per_file_materialized_bytes(files, "parquet"))
        if ctx.session.conf.build_memory_budget
        else None
    )
    return SourceScan(
        files=files,
        fmt="parquet",
        columns=tuple(schema_cols),
        file_ids=None,
        select_cols=tuple(schema_cols),
        file_sizes=sizes,
        excluded_lineage_ids=tuple(deleted_source_file_ids),
    )


def create_covering_index(ctx, source_df, config, properties: Dict[str, str]):
    """(CoveringIndex, index_data batch) — the reference's
    ``CoveringIndexConfig.createIndex:43-61``."""
    index, scan = prepare_covering_index(ctx, source_df, config, properties)
    return index, lazy_or_materialized(ctx, scan)


def source_file_infos(session, plan_relation) -> List[Tuple[str, int, int]]:
    """(path, size, mtime) via the source provider SPI — restricted to the
    plan relation's current file subset (refresh passes appended-only
    relations)."""
    provider_rel = session.source_manager.get_relation(plan_relation)
    subset = set(plan_relation.files)
    return [
        (p, size, mtime)
        for p, size, mtime in provider_rel.all_file_infos()
        if p in subset
    ]


# ---------------------------------------------------------------------------
# Shuffle + sort + bucketed write
# ---------------------------------------------------------------------------


def _decompose(batch: ColumnarBatch):
    """Flatten a batch into device-movable arrays + reassembly spec."""
    arrays: List[np.ndarray] = []
    spec = []
    for name, col in batch.columns.items():
        if col.kind == "string":
            arrays.append(col.codes)
            spec.append(("string", name, col.arrow_type, col.dictionary, False))
        else:
            arrays.append(col.values)
            has_validity = col.validity is not None
            if has_validity:
                arrays.append(col.validity)
            spec.append(("numeric", name, col.arrow_type, None, has_validity))
    return arrays, spec


def _reassemble(spec, arrays: List[np.ndarray]) -> ColumnarBatch:
    cols = {}
    it = iter(arrays)
    for kind, name, atype, dictionary, has_validity in spec:
        if kind == "string":
            cols[name] = Column(
                "string", atype, codes=next(it).astype(np.int32),
                dictionary=dictionary,
            )
        else:
            values = next(it)
            validity = next(it) if has_validity else None
            cols[name] = Column("numeric", atype, values=values, validity=validity)
    return ColumnarBatch(cols)


def _hash_shuffle(
    ctx, batch: ColumnarBatch, indexed_cols: List[str], num_buckets: int
):
    """Bucket-id half of the pipeline: murmur3 bucket ids over the key
    reps (+ mesh all-to-all when >1 device). Returns ``(buckets, reps,
    batch, shard_offsets)`` in post-exchange row order; ``shard_offsets``
    is the ``[D+1]`` per-shard row extent of the exchanged batch (rows
    ``offsets[s]:offsets[s+1]`` hold exactly the buckets shard ``s``
    owns), or None when no exchange ran (single device / tiny batch)."""
    import jax

    # how many columns the key has: on the span that encodes them and,
    # set and not summed (a streamed build hashes once a wave), on the
    # action's root
    live = _obs_trace.current()
    if live is not None:
        live.root.set("key_columns", len(indexed_cols))
    with stage("hash_shuffle"):
        with _obs_trace.span(
            "key_reps", key_columns=len(indexed_cols)
        ) as reps_sp:
            reps = batch.key_reps(indexed_cols)
            # key columns whose bytes were copied on the way: none where
            # the reps are a view of the one column that already held them
            reps_sp.set(
                "copied", len(indexed_cols) if reps.flags.owndata else 0
            )
        mesh = ctx.mesh
        shard_offs = None
        # multi-process: ALWAYS exchange, even a zero/tiny local batch —
        # the exchange is a collective and every process must take the
        # same number of steps (a peer may be feeding this wave real rows)
        if mesh.devices.size > 1 and (
            batch.num_rows >= mesh.devices.size or jax.process_count() > 1
        ):
            from hyperspace_tpu.parallel import shuffle as _shuffle

            arrays, spec = _decompose(batch)
            k = reps.shape[0]
            conf = ctx.session.conf
            buckets, moved, shard_offs = _shuffle.bucket_shuffle(
                mesh, reps, list(reps) + arrays, num_buckets,
                with_shard_offsets=True,
                strategy=conf.build_exchange_strategy,
                twostage_hosts=conf.build_exchange_twostage_hosts,
            )
            if k == 1:  # the exchanged column holds the reps: no copy
                reps = moved[0][None, :]
            elif k:
                reps = np.stack(moved[:k])
            else:
                reps = np.zeros((0, len(buckets)))
            batch = _reassemble(spec, moved[k:])
            _record_shuffle_telemetry(_shuffle.last_shuffle_stats)
        else:
            buckets = bucket_ids_np(reps, num_buckets)
    return buckets, reps, batch, shard_offs


def _record_shuffle_telemetry(stats: Dict) -> None:
    """Fold one exchange's snapshot into the build telemetry: latest
    value for every ``shuffle_<key>``, the stage seconds and the bytes
    SUMMED across waves, and the per-wave skew carried as a max/mean
    pair plus the wave count (a streaming build runs one exchange per
    wave; a single hot wave must stay visible in the max while the mean
    says whether it was the rule or the exception). The stage seconds
    are the exchange's own spans' (``shuffle._timed``): they also feed
    ``last_build_breakdown`` under the spans' names, as ``stage`` does
    for the build's stages — one measurement, no second timer."""
    from hyperspace_tpu.parallel.shuffle import BYTES_KEYS, STAGE_SECONDS_KEYS

    with _build_bd_lock:
        t = last_build_telemetry
        waves = t.get("shuffle_waves", 0.0) + 1.0
        for k, v in stats.items():
            key = "shuffle_" + k
            if k in STAGE_SECONDS_KEYS or k in BYTES_KEYS:
                t[key] = t.get(key, 0.0) + float(v)
            else:
                t[key] = v
        skew = float(stats.get("skew_ratio", 1.0))
        prev_mean = t.get("shuffle_skew_ratio_mean", 0.0)
        t["shuffle_waves"] = waves
        t["shuffle_skew_ratio_max"] = max(
            t.get("shuffle_skew_ratio_max", 0.0), skew
        )
        t["shuffle_skew_ratio_mean"] = round(
            prev_mean + (skew - prev_mean) / waves, 3
        )
    for k, span_name in STAGE_SECONDS_KEYS.items():
        if stats.get(k):
            _breakdown_add(span_name, float(stats[k]))


def _sharded_tail_offsets(shard_offs):
    """The shard offsets when the device-local tail applies, else None:
    an exchange ran and more than one shard holds rows."""
    if shard_offs is None:
        return None
    occupied = int(np.count_nonzero(np.diff(shard_offs)))
    return shard_offs if occupied > 1 else None


def bucketize(ctx, batch: ColumnarBatch, indexed_cols: List[str], num_buckets: int):
    """Route rows to buckets -> (bucket_ids, batch) in bucket-grouped,
    key-sorted order. Uses the mesh all-to-all when >1 device.

    The sort half runs partition-first (stable counting scatter into
    per-bucket runs, then per-bucket key sorts on a thread pool —
    working set ≈ rows/num_buckets per sort) and produces the stable
    lexsort permutation by (bucket, keys...)
    (``ops/sort.sort_permutation``: the tests' reference). When the
    exchange left more than one shard holding rows, each shard's slice
    sorts CONCURRENTLY
    (``ops/sort.sharded_sort_permutation``): row order is then
    shard-major rather than globally bucket-ascending, but each bucket's
    rows and their key-sorted order are identical — the bucketed writers
    (``pio.bucket_runs`` / per-bucket spill) only ever observe per-bucket
    runs."""
    from hyperspace_tpu.ops.sort import (
        partitioned_sort_permutation,
        sharded_sort_permutation,
    )

    buckets, reps, batch, shard_offs = _hash_shuffle(
        ctx, batch, indexed_cols, num_buckets
    )
    with stage("sort"):
        shard_offs = _sharded_tail_offsets(shard_offs)
        if shard_offs is not None:
            perm = sharded_sort_permutation(
                reps, buckets, num_buckets, shard_offs
            )
        else:
            perm = partitioned_sort_permutation(reps, buckets, num_buckets)
        out = buckets[perm], batch.take(perm)
    return out


def write_bucketed(
    ctx,
    data,
    indexed_cols: List[str],
    num_buckets: int,
    file_idx_offset: int = 0,
) -> List[str]:
    """The full build pipeline tail: shuffle, sort-within-bucket, write one
    parquet per bucket (CoveringIndex.write:56-71 + saveWithBuckets).

    ``data`` is a ColumnarBatch, a :class:`SourceScan` (streamed in waves),
    or a list mixing both (incremental refresh: appended scan + rewritten
    old data).

    One chooser: a :class:`SourceScan` among the sources → streaming
    waves; else hash and exchange, then the sharded tail if the exchange
    returned more than one occupied shard, else the pipelined tail.

    The parquet dictionary-encoding decision is computed ONCE here, on
    the pre-sort input, and passed to whichever tail runs: the sharded
    and the single tail must write byte-identical files, so they cannot
    each sample a differently-ordered table.
    """
    sources = data if isinstance(data, list) else [data]
    if any(isinstance(s, SourceScan) for s in sources):
        return _global_written(
            ctx,
            _write_bucketed_streaming(
                ctx, sources, indexed_cols, num_buckets, file_idx_offset
            ),
        )
    batch = sources[0] if len(sources) == 1 else ColumnarBatch.concat(sources)
    if batch.num_rows == 0 and _single_process():
        # multi-process never takes this shortcut: a zero-row LOCAL
        # batch still owes its peers the exchange collectives and the
        # _global_written barrier (its devices may RECEIVE rows)
        os.makedirs(ctx.index_data_path, exist_ok=True)
        return []
    with stage("dict_probe"):
        use_dict = pio.dictionary_columns_for_batch(batch)
    return _global_written(
        ctx,
        _write_bucketed_pipelined(
            ctx, batch, indexed_cols, num_buckets, file_idx_offset, use_dict
        ),
    )


def count_written(sp, paths: List[str]) -> None:
    """Files and bytes a write stage produced: on its span, and summed
    onto the action's root."""
    n_bytes = _files_bytes(paths)
    sp.set("files", len(paths))
    sp.set("bytes", n_bytes)
    _obs_trace.accumulate("index_files", len(paths))
    _obs_trace.accumulate("index_bytes", n_bytes)


def _timed_write_bucket_file(*args) -> Tuple[str, float, float, float, float]:
    """``pio.write_bucket_file`` -> (path, its seconds on the writer
    thread, that thread's CPU seconds over them, the seconds of its
    gather, those of its parquet write): the per-file unit behind a
    write stage's ``sum_s`` / ``max_s`` / ``cpu_sum_s`` and ``take_s``
    / ``encode_s``."""
    t0, cpu0 = _time.perf_counter(), _time.thread_time_ns()
    path = pio.write_bucket_file(*args)
    take_s, encode_s = pio.last_bucket_file_phases()
    return (
        path,
        _time.perf_counter() - t0,
        (_time.thread_time_ns() - cpu0) / 1e9,
        take_s,
        encode_s,
    )


def _written_attrs(sp, done: Sequence[Tuple], columns: int) -> List[str]:
    """What a write stage's files took, on its span: ``done`` holds
    ``_timed_write_bucket_file``'s tuples. ``sum_s`` / ``max_s`` /
    ``cpu_sum_s`` over the files as every repeated task has them;
    ``take_s`` and ``encode_s``, thread seconds as ``sum_s`` is, split
    it into the rows' gather and the parquet write; ``columns`` is what
    each file holds. -> the files' paths."""
    _repeat_attrs(sp, [(sec, cpu) for _p, sec, cpu, _t, _e in done], "buckets")
    sp.set("take_s", round(float(sum(take for *_, take, _e in done)), 6))
    sp.set("encode_s", round(float(sum(enc for *_, enc in done)), 6))
    sp.set("columns", columns)
    written = [path for path, *_ in done]
    count_written(sp, written)
    return written


def _single_process() -> bool:
    import jax

    return jax.process_count() <= 1


def _global_written(ctx, written: List[str]) -> List[str]:
    """The written-file list a build hands to the metadata plane. On a
    single-process job this is the writer's own list; on a multi-process
    job every host wrote only the buckets its shards own, so after a
    cross-host barrier the (deterministically named, bucket-id-ordered)
    union is listed from the data dir — every process returns the same
    global list for the coordinator's log entry.

    Registered in ``COLLECTIVE_SITES`` (``parallel/collectives.py``,
    contract ``per-host-lane``): every ``write_bucketed`` exit path must
    reach this barrier on every process — zero-row stripes included —
    or the peers hang (hslint HS8xx enforces the shape)."""
    import jax

    if jax.process_count() <= 1:
        return written

    from jax.experimental import multihost_utils as mhu

    mhu.sync_global_devices("hs_build_bucketed_write")
    d = ctx.index_data_path
    return [
        os.path.join(d, f)
        for f in sorted(os.listdir(d))
        if f.startswith(C_INDEX_FILE_PREFIX) and f.endswith(".parquet")
    ]


def _write_bucketed_pipelined(
    ctx,
    batch: ColumnarBatch,
    indexed_cols: List[str],
    num_buckets: int,
    file_idx_offset: int,
    use_dict,
) -> List[str]:
    """Partition-first, pipelined tail for in-memory builds.

    1. counting-scatter rows into contiguous per-bucket runs (native
       ``hs_partition_by_bucket``; sequential histogram + scatter);
    2. per-bucket key lexsorts on a thread pool, bucket plane dropped
       (constant within a bucket) — each sort's working set is ~one
       bucket instead of the whole table, which is what collapsed the
       64M-row global lexsort (BASELINE.md: TLB-bound gathers over
       512MB);
    3. bucket *i*'s parquet write is handed to a pool of writers
       (``_bucket_writers``: the core budget, at most one a non-empty
       bucket, fewer when a memory budget is set and that many of the
       largest buckets would not fit it) while bucket *i+1* is still
       sorting; no two writers ever share a file.

    The composed permutation equals the stable lexsort by (bucket,
    keys...) (``ops/sort.sort_permutation``), so each file holds what
    ``pio.write_bucket_files`` would write from that order under the
    same encoding decision — the layout the differential tests hold
    this tail to.

    Stage accounting: "sort" spans ``partition`` (counting scatter +
    order words), ``to_arrow`` and ``bucket_sorts`` (all per-bucket
    sorts, summarized as ``buckets``/``sum_s``/``max_s``); "write"
    records only the drain after the last sort — the overlapped portion
    of the writes hides inside the sort stage, which is the point of
    the pipeline — while its ``sum_s``/``max_s`` count every file's
    seconds on its writer's thread, overlapped or not, and ``writers``
    is the pool's size: ``sum_s / (writers * the stage's seconds)`` is
    the pool's occupancy, and ``sum_s`` against what the same files
    take on one writer is what the writers cost each other.

    The first writer that raises surfaces from here; files not yet
    started are then cancelled, not written after the build has failed.

    Datasets beyond the memory budget never reach here; they stream
    through ``_write_bucketed_streaming``'s wave/spill loop, whose
    per-wave ``bucketize`` uses the same partition-first sort.
    """
    from concurrent.futures import ThreadPoolExecutor

    from hyperspace_tpu.ops.sort import (
        _order_words_np,
        bucket_key_sort_runs,
        partition_by_bucket,
    )

    buckets, reps, batch, shard_offs = _hash_shuffle(
        ctx, batch, indexed_cols, num_buckets
    )
    os.makedirs(ctx.index_data_path, exist_ok=True)
    shard_offs = _sharded_tail_offsets(shard_offs)
    if shard_offs is not None:
        written = _write_bucketed_sharded(
            ctx, buckets, reps, batch, file_idx_offset, use_dict,
            num_buckets, shard_offs,
        )
        if written is not None:
            return written
    sort_s: List[Tuple[float, float]] = []
    futures = []
    with contextlib.ExitStack() as cleanup:
        with stage("sort"):
            with _obs_trace.span("partition"):
                order, offsets = partition_by_bucket(buckets, num_buckets)
                planes = _order_words_np(reps.astype(np.int64, copy=False))
            with _obs_trace.span("to_arrow"):
                table = batch.to_arrow()
            writers = _bucket_writers(ctx, table, offsets)
            pool = ThreadPoolExecutor(
                max_workers=writers, thread_name_prefix="hs-bucketwrite"
            )
            # on the way out after a failure, the files no writer has
            # started are dropped, not written
            cleanup.callback(pool.shutdown, cancel_futures=True)
            with _obs_trace.span("bucket_sorts") as sorts_sp:
                for b, final_idx in bucket_key_sort_runs(
                    planes, order, offsets, seconds_out=sort_s
                ):
                    futures.append(
                        pool.submit(
                            _timed_write_bucket_file,
                            ctx.index_data_path,
                            b,
                            file_idx_offset,
                            table,
                            final_idx,
                            use_dict,
                        )
                    )
                _repeat_attrs(sorts_sp, sort_s, "buckets")
                # the order words a sort compares (two a key column) and
                # the largest bucket's rows: one sort's working set
                sorts_sp.set("planes", int(planes.shape[0]))
                sorts_sp.set("max_rows", int(np.diff(offsets).max()))
        with stage("write", writers=writers) as write_sp:
            # in submission order: ascending bucket id
            done = [f.result() for f in futures]
            pool.shutdown()  # the writers' way out is the drain's too
            # every file's seconds on its writer's thread, those that
            # ran under the sort stage included
            written = _written_attrs(write_sp, done, table.num_columns)
    return written


def _bucket_writers(ctx, table, offsets: np.ndarray) -> int:
    """Writers of the pipelined tail's pool: the core budget, at most one
    a non-empty bucket. A running writer holds one gathered bucket
    (``table.take``), so with few, large buckets the pool can approach a
    second copy of the table: under a build memory budget only as many
    writers as the LARGEST bucket's bytes fit it, the rule the streaming
    merge has for its concurrent merges."""
    from hyperspace_tpu import native

    counts = np.diff(offsets)
    writers = min(native.core_budget(), int(np.count_nonzero(counts)))
    budget = ctx.session.conf.build_memory_budget
    if budget and table.num_rows:
        biggest = int(counts.max()) * table.nbytes // table.num_rows
        writers = min(writers, int(budget // max(biggest, 1)))
    return max(1, writers)


class _ForeignBuckets(Exception):
    """A shard's slice holds rows of a bucket another shard owns."""


def _write_bucketed_sharded(
    ctx,
    buckets: np.ndarray,
    reps: np.ndarray,
    batch: ColumnarBatch,
    file_idx_offset: int,
    use_dict,
    num_buckets: int,
    shard_offs: np.ndarray,
) -> Optional[List[str]]:
    """Device-local tail of the in-memory sharded build: each mesh
    shard's post-exchange slice (exactly the buckets it owns) runs the
    partition-first pipeline — counting scatter, per-bucket key sorts,
    per-bucket parquet writes with sort/write overlap — CONCURRENTLY
    with the other shards'. Sort working set and write bandwidth scale
    with the shard count; nothing serializes through one global
    permutation.

    Bit-identical files to the single-tail layout: a bucket lives wholly
    inside one shard slice, slices are contiguous in post-exchange row
    order, and the per-shard stable sort restricted to a bucket equals
    the global stable (bucket, keys...) sort restricted to that bucket.
    The encoding decision (``use_dict``) was computed once by the caller
    on the shared pre-sort input.

    One writer a bucket file rests on that ownership. A slice that holds
    rows of a bucket its shard does not own (bucket ids that diverged
    from the exchange's plan) would have two shards write one file at
    once, so such a shard writes nothing, what the others wrote is taken
    away and None is returned: the caller's single tail then writes what
    the ids say, as a one-device build does.

    Stage accounting: "sort"/"write" accumulate per-shard BUSY time
    (their sum can exceed wall time — the excess is the sharding win);
    "tail_wall" is the wall time of the whole sharded tail and
    "tail_shards" the number of concurrent shard tails.
    """
    from concurrent.futures import ThreadPoolExecutor

    from hyperspace_tpu.ops.sort import (
        _order_words_np,
        bucket_key_sort_runs,
        partition_by_bucket,
        shard_tail_plan,
    )

    t_tail = _time.perf_counter()
    n_shards = len(shard_offs) - 1
    with _obs_trace.span("partition"):
        planes = _order_words_np(reps.astype(np.int64, copy=False))
    with _obs_trace.span("to_arrow"):
        table = batch.to_arrow()
    shards, threads = shard_tail_plan(shard_offs)

    def run_shard(s: int) -> List[Tuple[int, str]]:
        lo, hi = int(shard_offs[s]), int(shard_offs[s + 1])
        sort_s: List[Tuple[float, float]] = []
        # one writer thread per shard: bucket i+1 sorts while bucket i
        # writes, exactly the single-tail pipeline, D of them in flight
        with ThreadPoolExecutor(max_workers=1) as writer:
            futures = []
            with stage("sort", shard=s) as sort_sp:
                order, offsets = partition_by_bucket(
                    buckets[lo:hi], num_buckets
                )
                held = np.flatnonzero(np.diff(offsets))
                if (held % n_shards != s).any():
                    raise _ForeignBuckets(s)
                order += lo  # global row coordinates into planes/table
                for b, final_idx in bucket_key_sort_runs(
                    planes, order, offsets, workers=1, n_threads=threads,
                    seconds_out=sort_s,
                ):
                    futures.append(
                        (
                            b,
                            writer.submit(
                                _timed_write_bucket_file,
                                ctx.index_data_path,
                                b,
                                file_idx_offset,
                                table,
                                final_idx,
                                use_dict,
                            ),
                        )
                    )
                _repeat_attrs(sort_sp, sort_s, "buckets")
            with stage("write", shard=s) as write_sp:
                done = [f.result() for _b, f in futures]
                paths = _written_attrs(write_sp, done, table.num_columns)
                out = [(b, path) for (b, _f), path in zip(futures, paths)]
        return out

    # the shard tails run on pool threads: hand them the action's span
    run_shard = _obs_trace.carry(run_shard)
    results, foreign = [], []
    with ThreadPoolExecutor(
        max_workers=len(shards), thread_name_prefix="hs-shardtail"
    ) as pool:
        for fut in [pool.submit(run_shard, s) for s in shards]:
            try:
                results.append(fut.result())
            except _ForeignBuckets as e:
                foreign.append(e.args[0])
    if foreign:
        _log.warning(
            "sharded tail: the slices of shards %s hold rows of buckets "
            "they do not own (bucket ids diverged from the exchange's "
            "plan); writing through the single tail",
            foreign,
        )
        for _b, path in (p for r in results for p in r):
            os.remove(path)
        return None
    with _build_bd_lock:
        last_build_breakdown["tail_wall"] = (
            last_build_breakdown.get("tail_wall", 0.0)
            + _time.perf_counter()
            - t_tail
        )
        last_build_breakdown["tail_shards"] = float(len(shards))
    # ascending bucket id, matching the single-tail writers' output order
    return [path for _b, path in sorted(p for r in results for p in r)]


def _write_bucketed_streaming(
    ctx,
    sources,
    indexed_cols: List[str],
    num_buckets: int,
    file_idx_offset: int = 0,
) -> List[str]:
    """The >HBM wave loop (SURVEY §7 hard part #1).

    Bounded peak memory: the build never materializes more than one wave
    (<= the configured budget) plus, at merge time, one bucket. Phases:

    1. **Waves**: chunk each source's files into waves within the memory
       budget; per wave, run the normal device pipeline (hash -> all-to-all
       -> bucket-grouped order) and spill each bucket's run to
       ``_spill_/b<b>-w<i>.parquet`` (flat, no ``=`` in any path component
       — Arrow's dataset reader hive-infers partition columns from
       ``key=value`` directories, which would graft phantom columns onto
       the merge read).
    2. **Merge**: per bucket, read that bucket's spilled parts (~1/num_buckets
       of the data), key-sort on device, write the final bucket file.

    The reference leans on Spark's disk-backed ``repartition`` shuffle for
    exactly this (covering/CoveringIndex.scala:58-61).
    """
    import shutil

    budget = ctx.session.conf.build_memory_budget or (1 << 62)
    import jax

    nproc = jax.process_count()
    # outside the v__=N data dir (also a key=value name) but inside the
    # index dir; the leading underscore keeps it out of data listings and
    # the sanitized name keeps "=" out of every spill path component.
    # Multi-process: the index dir is a SHARED filesystem and each
    # process spills + merges only its own owned buckets, so the spill
    # dir is per-process — a peer finishing early must never rmtree
    # parts another process is still merging
    suffix = f"-p{jax.process_index()}" if nproc > 1 else ""
    spill_root = os.path.join(
        os.path.dirname(ctx.index_data_path),
        "_spill_"
        + os.path.basename(ctx.index_data_path).replace("=", "_")
        + suffix,
    )
    os.makedirs(spill_root, exist_ok=True)
    wave_idx = 0
    bucket_parts: Dict[int, List[str]] = {}
    try:
        for src in sources:
            if isinstance(src, SourceScan):
                # waves are planned over the GLOBAL file list on every
                # process (the SPMD requirement: identical wave count =
                # identical number of per-wave exchange collectives);
                # multi-process, each host materializes only its stripe
                # of a wave — an empty stripe still joins the wave's
                # exchange with a zero-row, schema-correct slice
                waves = plan_waves(
                    src.files, src.fmt, budget, src.file_sizes
                )
                if nproc > 1:
                    index_of = {f: i for i, f in enumerate(src.files)}
                    pid = jax.process_index()

                    def stripes(src=src, waves=waves, index_of=index_of):
                        for w in waves:
                            mine = [
                                f for f in w if index_of[f] % nproc == pid
                            ]
                            if mine:
                                yield src.materialize(mine)
                            else:
                                yield src.empty_batch()

                    wave_batches = stripes()
                else:
                    wave_batches = (src.materialize(w) for w in waves)
            else:
                wave_batches = iter([src])
            for batch in wave_batches:
                if batch.num_rows == 0 and nproc == 1:
                    continue
                buckets, batch = bucketize(
                    ctx, batch, indexed_cols, num_buckets
                )
                table = batch.to_arrow()
                for b, idx in pio.bucket_runs(buckets):
                    path = os.path.join(
                        spill_root, f"b{b:05d}-w{wave_idx:05d}.parquet"
                    )
                    pio.write_table(path, table.take(pa.array(idx)))
                    bucket_parts.setdefault(b, []).append(path)
                wave_idx += 1
        # merge: per bucket, read parts, key-sort, write the final file.
        # On a >1-device mesh each shard's bucket range (bucket % D)
        # merges on its own worker — the streaming build's waves already
        # sorted per shard (bucketize), and this keeps the merge tail
        # device-local too.
        def merge_bucket(b: int) -> List[str]:
            merged = ColumnarBatch.from_arrow(
                pio.read_table(bucket_parts[b], None)
            )
            perm = sort_permutation(merged.key_reps(indexed_cols))
            merged = merged.take(perm)
            return pio.write_bucket_files(
                ctx.index_data_path,
                np.full(merged.num_rows, b, dtype=np.int32),
                merged,
                num_buckets,
                file_idx_offset,
            )

        ordered = sorted(bucket_parts)
        D = ctx.mesh.devices.size
        written: List[str] = []
        merge_workers = 1
        if D > 1 and len(ordered) > 1:
            # The streaming build's contract is bounded peak memory (one
            # wave + one bucket); concurrent per-shard merges may only
            # widen that to k buckets when k of the LARGEST fit the wave
            # budget — estimated from the spilled parts' own footers.
            biggest = max(
                sum(per_file_materialized_bytes(bucket_parts[b], "parquet"))
                for b in ordered
            )
            fit = int(budget // max(biggest, 1))
            merge_workers = max(1, min(D, fit))
        if merge_workers > 1:
            from concurrent.futures import ThreadPoolExecutor

            from hyperspace_tpu.parallel.mesh import bucket_owner_groups

            groups = bucket_owner_groups(ordered, D)

            def merge_shard(g: List[int]) -> Dict[int, List[str]]:
                return {ordered[i]: merge_bucket(ordered[i]) for i in g}

            with ThreadPoolExecutor(
                max_workers=merge_workers, thread_name_prefix="hs-shardmerge"
            ) as pool:
                merged_maps = list(pool.map(merge_shard, groups))
            by_bucket = {b: fs for m in merged_maps for b, fs in m.items()}
            for b in ordered:
                written.extend(by_bucket[b])
        else:
            for b in ordered:
                written.extend(merge_bucket(b))
        return written
    finally:
        shutil.rmtree(spill_root, ignore_errors=True)


# ---------------------------------------------------------------------------
# Optimize / refresh data plane
# ---------------------------------------------------------------------------


def rewrite_files(
    ctx, files_to_optimize: List[str], indexed_cols: List[str], num_buckets: int
) -> List[str]:
    """Optimize: read the listed index files and rewrite them compacted
    (CoveringIndexTrait.optimize:130-134 — 'read files → write'). On a
    multi-process job each host reads a disjoint subset; the exchange
    routes rows back to their owner host before the write."""
    import jax

    # a data op like create/refresh: fresh stage breakdown, telemetry
    # accumulators and skew-warn latch (the exchange stats now SUM
    # across waves — without the reset they would mix two ops)
    reset_build_breakdown()
    nproc = jax.process_count()
    subset = files_to_optimize
    if nproc > 1:
        subset = files_to_optimize[jax.process_index()::nproc]
    if subset:
        batch = ColumnarBatch.from_arrow(pio.read_table(subset, None))
    else:
        # more hosts than files: still owe peers the exchange
        # collectives + write barrier — a zero-row batch from the first
        # index file's footer schema (index files are always parquet)
        import pyarrow.parquet as pq

        batch = ColumnarBatch.from_arrow(
            pq.read_schema(files_to_optimize[0]).empty_table()
        )
    return write_bucketed(ctx, batch, indexed_cols, num_buckets)


def refresh_incremental(
    ctx,
    index,
    appended_df,
    deleted_source_file_ids: List[int],
    previous_content,
):
    """CoveringIndexTrait.refreshIncremental:57-106.

    * appended source files -> index their rows into the new version dir;
    * deleted source files  -> previous index data rewritten minus rows
      whose lineage id is among the deleted (anti-filter), also into the
      new version dir.
    Returns (index, UpdateMode.MERGE | OVERWRITE).
    """
    reset_build_breakdown()
    schema_cols = list(index.indexed_columns) + list(index.included_columns)
    if index.lineage_enabled:
        schema_cols.append(DATA_FILE_NAME_ID)
    # parts: ColumnarBatch or SourceScan (large appends stream in waves)
    parts: List = []
    if appended_df is not None:
        _index2, appended_data = create_covering_index(
            ctx,
            appended_df,
            _config_of(index),
            dict(index.properties),
        )
        parts.append(appended_data.select(schema_cols))
    if deleted_source_file_ids:
        if not index.lineage_enabled:
            raise HyperspaceException(
                "Cannot handle deleted source files without lineage"
            )
        # previous index data minus deleted-lineage rows, as a LAZY scan:
        # beyond the memory budget it streams through the wave loop like
        # the appended side instead of materializing whole
        old_scan = previous_index_scan(
            ctx, previous_content, schema_cols, deleted_source_file_ids
        )
        parts.append(lazy_or_materialized(ctx, old_scan))
        mode = UpdateMode.OVERWRITE
    else:
        mode = UpdateMode.MERGE
    if parts:
        write_bucketed(ctx, parts, index.indexed_columns, index.num_buckets)
    return index, mode


def refresh_full(ctx, index, df):
    """Rebuild the whole index from the current source
    (CoveringIndexTrait.refreshFull:108-126). Returns the REBUILT index —
    its schema_json reflects the current source types, which may have
    changed since the original build."""
    new_index, batch = create_covering_index(
        ctx, df, _config_of(index), dict(index.properties)
    )
    write_bucketed(ctx, batch, new_index.indexed_columns, new_index.num_buckets)
    return new_index


def _config_of(index):
    from hyperspace_tpu.indexes.covering import CoveringIndexConfig

    return CoveringIndexConfig(
        "__refresh__", index.indexed_columns, index.included_columns
    )
