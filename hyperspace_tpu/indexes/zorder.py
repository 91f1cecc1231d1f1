"""Z-order covering index.

Reference: ``zordercovering/ZOrderCoveringIndex.scala:32-189`` — a covering
index whose rows are globally sorted by interleaved-bit **z-address**
instead of hash-bucketed: multi-column range queries touch few files.
Build = z-address kernel (``ops/zorder.py``) + global device sort + write
split into ~targetSourceBytesPerPartition files (the reference's
``repartitionByRange`` on ``_zaddr``, `:139-153`).
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from hyperspace_tpu.constants import DATA_FILE_NAME_ID, LINEAGE_PROPERTY
from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.indexes.base import Index, IndexConfigTrait, UpdateMode
from hyperspace_tpu.indexes.registry import register_index
from hyperspace_tpu.io import parquet as pio
from hyperspace_tpu.io.columnar import ColumnarBatch


@register_index
class ZOrderCoveringIndex(Index):
    kind = "ZOrderCoveringIndex"
    kind_abbr = "ZOCI"

    def __init__(
        self,
        indexed_columns: List[str],
        included_columns: List[str],
        schema_json: str,
        target_bytes_per_partition: int,
        properties: Optional[Dict[str, str]] = None,
    ):
        self._indexed_columns = list(indexed_columns)
        self._included_columns = list(included_columns)
        self.schema_json = schema_json
        self.target_bytes_per_partition = int(target_bytes_per_partition)
        self.properties: Dict[str, str] = dict(properties or {})
        # what the last in-memory write into a version directory knows of
        # its files' z-spans, until the zone-map capture of that
        # directory takes it: of this object only, never of the index
        # (not in to_dict, __eq__ or __hash__)
        self._written_zspans: Optional[WrittenZSpans] = None

    def take_written_zspans(self) -> Optional["WrittenZSpans"]:
        """Hand over, once, what the last write / optimize / refresh of
        THIS object learned of the files it wrote (None after a streamed
        or an empty write, and once taken): the zone-map capture that
        follows in the same action (``zonemaps.capture_safely(dir,
        index)``) is its one reader."""
        written, self._written_zspans = self._written_zspans, None
        return written

    def __eq__(self, other):
        return (
            isinstance(other, ZOrderCoveringIndex)
            and self._indexed_columns == other._indexed_columns
            and self._included_columns == other._included_columns
            and self.schema_json == other.schema_json
        )

    def __hash__(self):
        return hash(tuple(self._indexed_columns))

    @property
    def indexed_columns(self) -> List[str]:
        return list(self._indexed_columns)

    @property
    def included_columns(self) -> List[str]:
        return list(self._included_columns)

    @property
    def lineage_enabled(self) -> bool:
        return str(self.properties.get(LINEAGE_PROPERTY, "false")).lower() == "true"

    @property
    def can_handle_deleted_files(self) -> bool:
        return self.lineage_enabled

    # -- serialization ------------------------------------------------------
    def to_dict(self) -> dict:
        return {
            "type": self.kind,
            "kindAbbr": self.kind_abbr,
            "indexedColumns": self._indexed_columns,
            "includedColumns": self._included_columns,
            "schemaJson": self.schema_json,
            "targetBytesPerPartition": self.target_bytes_per_partition,
            "properties": dict(self.properties),
        }

    @classmethod
    def from_dict(cls, d: dict) -> "ZOrderCoveringIndex":
        return cls(
            d["indexedColumns"],
            d.get("includedColumns", []),
            d.get("schemaJson", ""),
            d.get("targetBytesPerPartition", 1 << 30),
            d.get("properties", {}),
        )

    # -- data plane ---------------------------------------------------------
    def write(self, ctx, index_data: ColumnarBatch) -> None:
        """Z-sort + size-targeted split write
        (ZOrderCoveringIndex.write:97-154)."""
        self._written_zspans = _write_zordered(
            ctx, index_data, self._indexed_columns, self.target_bytes_per_partition
        )

    def optimize(self, ctx, files_to_optimize: List[str]) -> None:
        batch = ColumnarBatch.from_arrow(pio.read_table(files_to_optimize, None))
        self._written_zspans = _write_zordered(
            ctx, batch, self._indexed_columns, self.target_bytes_per_partition
        )

    def refresh_incremental(
        self, ctx, appended_df, deleted_source_file_ids, previous_content
    ) -> Tuple["ZOrderCoveringIndex", UpdateMode]:
        """Like the covering index, but the new data is z-sorted on its own
        (a merged global re-sort would be a full rebuild; the reference
        likewise z-sorts only the delta).

        The refresh input — appended source files and, for deletes, the
        lineage-filtered previous index data — is assembled LAZILY
        (SourceScan/CompositeScan) and only materialized when it fits the
        build memory budget; otherwise it streams through the same
        two-pass wave loop as create/full refresh."""
        from hyperspace_tpu.indexes.covering_build import (
            CompositeScan,
            lazy_or_materialized,
            prepare_covering_index,
            previous_index_scan,
            reset_build_breakdown,
        )

        reset_build_breakdown()
        schema_cols = self._indexed_columns + self._included_columns
        if self.lineage_enabled:
            schema_cols = schema_cols + [DATA_FILE_NAME_ID]
        scans = []
        if appended_df is not None:
            _idx, scan = prepare_covering_index(
                ctx, appended_df, self._config(), dict(self.properties)
            )
            scans.append(scan.select(schema_cols))
        if deleted_source_file_ids:
            if not self.lineage_enabled:
                raise HyperspaceException(
                    "Cannot handle deleted source files without lineage"
                )
            scans.append(
                previous_index_scan(
                    ctx, previous_content, schema_cols, deleted_source_file_ids
                )
            )
            mode = UpdateMode.OVERWRITE
        else:
            mode = UpdateMode.MERGE
        if scans:
            combined = scans[0] if len(scans) == 1 else CompositeScan(tuple(scans))
            self._written_zspans = _write_zordered(
                ctx,
                lazy_or_materialized(ctx, combined),
                self._indexed_columns,
                self.target_bytes_per_partition,
            )
        return self, mode

    def refresh_full(self, ctx, df) -> "ZOrderCoveringIndex":
        from hyperspace_tpu.indexes import covering_build

        new_index, batch = covering_build.create_covering_index(
            ctx, df, self._config(), dict(self.properties)
        )
        # a SourceScan flows straight into write (streamed two-pass build)
        # create_covering_index builds a CoveringIndex; re-wrap with our kind
        rebuilt = ZOrderCoveringIndex(
            new_index.indexed_columns,
            new_index.included_columns,
            new_index.schema_json,
            self.target_bytes_per_partition,
            dict(self.properties),
        )
        rebuilt.write(ctx, batch)
        return rebuilt

    def _config(self) -> "ZOrderCoveringIndexConfig":
        return ZOrderCoveringIndexConfig(
            "__refresh__", self._indexed_columns, self._included_columns
        )

    def statistics(self, extended: bool = False) -> Dict[str, str]:
        return {
            "indexedColumns": ",".join(self._indexed_columns),
            "includedColumns": ",".join(self._included_columns),
            "targetBytesPerPartition": str(self.target_bytes_per_partition),
            "schema": self.schema_json if extended else "",
        }


# bits of the z-address a column gets (ops/zorder's layout)
_Z_BITS = 16


@dataclasses.dataclass(frozen=True)
class WrittenZSpans:
    """What an in-memory z-order write knows of the files it has just
    written, kept for the zone-map capture that follows
    (``zonemaps._capture_zspans``) so that it need not read them back:
    the frozen encoder spec, and for every file the rows of each row
    group as the write cut them with the packed z-address of the row
    group's first and last row. The rows were written in sorted order, so
    those two ARE the row group's least and greatest address. A few
    hundred integers: the planes themselves are not kept."""

    bits: int
    nplanes: int
    specs: list  # ZOrderEncoder.specs, one a column
    # absolute path -> (rows of each row group, (z_lo, z_hi) of each)
    files: Dict[str, Tuple[List[int], List[Tuple[int, int]]]]


def _write_zordered(
    ctx, data, indexed_cols: List[str], target_bytes: int
) -> Optional[WrittenZSpans]:
    """Global z-sort then split into ~equal files sized to hit the target
    partition bytes — bytes of the index's columns as they lie IN MEMORY
    (``table.nbytes``), not of the source on disk as the key
    ``targetSourceBytesPerPartition`` says (ROADMAP.md, Design: "z-order
    file sizing"). ``data`` is a ColumnarBatch or (for datasets beyond
    the build memory budget) a lazy SourceScan streamed in two passes.

    Returns what the zone-map capture can use in place of a second read
    (:class:`WrittenZSpans`) where one sort ordered every row written —
    the in-memory path; None from the streamed build and an empty batch,
    whose directories the capture reads back.

    Build stages under the action's root (``covering_build.stage``):
    ``zorder_encode`` (order encodings + min/max), ``zorder_interleave``
    (host words, both transfers and the kernel: ``ops/zorder``),
    ``zorder_sort`` (``ops/sort.lexsort_perm``; ``h2d``/``kernel``/
    ``d2h`` under it when it takes the device arm), ``take``,
    ``to_arrow``, ``write``."""
    from hyperspace_tpu.indexes import covering_build
    from hyperspace_tpu.indexes.covering_build import CompositeScan, SourceScan
    from hyperspace_tpu.ops.sort import lexsort_perm
    from hyperspace_tpu.ops.zorder import ZOrderEncoder

    os.makedirs(ctx.index_data_path, exist_ok=True)
    if isinstance(data, (SourceScan, CompositeScan)):
        _write_zordered_streaming(ctx, data, indexed_cols, target_bytes)
        return None
    batch = data
    if batch.num_rows == 0:
        return None
    conf = ctx.session.conf
    with covering_build.stage("zorder_encode"):
        encoder, encs = ZOrderEncoder.fit(
            [batch.column(c) for c in indexed_cols],
            _Z_BITS,
            conf.zorder_quantile_enabled,
            conf.zorder_quantile_relative_error,
        )
    with covering_build.stage("zorder_interleave"):
        planes = encoder.planes_from_encodings(encs)
    with covering_build.stage("zorder_sort"):
        perm = lexsort_perm(planes)
    with covering_build.stage("take"):
        batch = batch.take(perm)
    with covering_build.stage("to_arrow"):
        table = batch.to_arrow()
    written = _write_parts(ctx, table, target_bytes, 0)
    return _written_zspans(
        encoder, planes, perm, written, _part_cuts(table, target_bytes)
    )


def _part_cuts(table, target_bytes: int) -> Iterator[Tuple[int, int, int]]:
    """(part number, first row, rows) of each file a z-sorted table is
    cut into: ~equal files of about ``target_bytes`` of ``table.nbytes``
    each, the empty ones left out."""
    num_parts = max(1, math.ceil(max(table.nbytes, 1) / target_bytes))
    rows_per_part = math.ceil(table.num_rows / num_parts)
    for i in range(num_parts):
        start = i * rows_per_part
        rows = min(rows_per_part, table.num_rows - start)
        if rows > 0:
            yield i, start, rows


def _write_parts(ctx, table, target_bytes: int, first_idx: int) -> List[str]:
    """One ``write`` stage (attrs ``files``, ``bytes``, ``rows``): a
    z-sorted table cut into files (:func:`_part_cuts`), named from
    ``first_idx`` on."""
    from hyperspace_tpu.indexes import covering_build

    written = []
    with covering_build.stage("write") as sp:
        for i, start, rows in _part_cuts(table, target_bytes):
            path = os.path.join(
                ctx.index_data_path,
                f"part-{first_idx + i:05d}-zorder.parquet",
            )
            pio.write_table(path, table.slice(start, rows))
            written.append(path)
        covering_build.count_written(sp, written)
        sp.set("rows", int(table.num_rows))
    return written


def _written_zspans(encoder, planes, perm, written: List[str], cuts) -> WrittenZSpans:
    """The hand-off of one sorted write: for each file (``written`` beside
    its cut of the sorted order) the row groups ``pio.write_table`` cuts
    (``pio.INDEX_ROW_GROUP_SIZE`` rows, the last one shorter) and, from
    the planes through the permutation, the packed address of each row
    group's first and last row — two look-ups a row group."""
    from hyperspace_tpu.ops.zorder import planes_z_at

    group = pio.INDEX_ROW_GROUP_SIZE
    files = {}
    for path, (_i, start, rows) in zip(written, cuts):
        firsts = np.arange(start, start + rows, group)
        lasts = np.minimum(firsts + group, start + rows) - 1
        z = planes_z_at(planes, perm[np.concatenate([firsts, lasts])])
        files[os.path.abspath(path)] = (
            (lasts - firsts + 1).tolist(),
            list(zip(z[: len(firsts)], z[len(firsts) :])),
        )
    return WrittenZSpans(encoder.bits, int(planes.shape[0]), encoder.specs, files)


# range-partition count for the streamed z-order spill: top bits of the
# most-significant z-address plane (64 contiguous z-ranges; peak merge
# memory ~= total/64 for a balanced address space)
_ZORDER_SPILL_BITS = 6


def _write_zordered_streaming(
    ctx, scan, indexed_cols: List[str], target_bytes: int
) -> List[str]:
    """The >memory-budget z-order build (two passes over the waves):

    1. **Stats pass** (indexed columns only): accumulate each column's
       order-encodings — global min/max, plus a bounded stride sample
       when quantile encoding is on — and FREEZE the encoding spec
       (``ZOrderEncoder``). A fixed spec makes z-addresses identical in
       every later step, so local order == global order.
    2. **Spill pass**: per wave, compute z-address planes under the
       frozen spec and spill rows into 2^_ZORDER_SPILL_BITS contiguous
       z-RANGES (top bits of the most significant plane) — the streamed
       equivalent of the reference's ``repartitionByRange`` on ``_zaddr``
       (ZOrderCoveringIndex.scala:139-153).
    3. **Merge**: per range in ascending order, re-encode + lexsort (a
       range holds ~1/64 of the data) and write size-targeted files.

    The spill's and the merge's steps record the in-memory build's
    stages where they run the same step (``zorder_interleave`` — here
    with the re-encode inside it —, ``zorder_sort``, ``take``,
    ``to_arrow``, ``write``): once a wave or a range, never a row group.
    """
    import shutil

    from hyperspace_tpu.indexes import covering_build
    from hyperspace_tpu.indexes.covering_build import plan_waves
    from hyperspace_tpu.io.columnar import ColumnarBatch
    from hyperspace_tpu.ops.sort import lexsort_perm
    from hyperspace_tpu.ops.zorder import ZOrderEncoder, order_u64_np

    conf = ctx.session.conf
    budget = conf.build_memory_budget or (1 << 62)
    quantile = conf.zorder_quantile_enabled
    rel_err = conf.zorder_quantile_relative_error
    waves = plan_waves(scan.files, scan.fmt, budget, scan.file_sizes)

    # pass 1: frozen encoding spec from a stats-only scan
    stats_scan = scan.stats_view(indexed_cols)
    k = len(indexed_cols)
    mins = [None] * k
    maxs = [None] * k
    samples: List[List] = [[] for _ in range(k)]
    dicts: List = [None] * k  # string columns: global dictionary union
    max_sample = max(int(1.0 / max(rel_err, 1e-4) ** 2), 1024)
    per_wave = max(max_sample // max(len(waves), 1), 64)
    for w in waves:
        b = stats_scan.materialize(w)
        for j, c in enumerate(indexed_cols):
            col = b.column(c)
            if col.kind == "string":
                # batch-local dictionary ranks are NOT stable across
                # waves; freeze a GLOBAL dictionary instead
                if dicts[j] is None:
                    dicts[j] = set()
                dicts[j].update(col.dictionary)
                continue
            e = order_u64_np(col)
            if not len(e):
                continue
            lo, hi = e.min(), e.max()
            mins[j] = lo if mins[j] is None else min(mins[j], lo)
            maxs[j] = hi if maxs[j] is None else max(maxs[j], hi)
            if quantile:
                samples[j].append(e[:: max(1, len(e) // per_wave)])
    specs = []
    for j in range(k):
        if dicts[j] is not None:
            specs.append(("dict", sorted(dicts[j])))
        elif quantile:
            s = (
                np.sort(np.concatenate(samples[j]))
                if samples[j]
                else np.zeros(1, dtype=np.uint64)
            )
            specs.append(("quantile", s))
        else:
            specs.append(
                (
                    "range",
                    mins[j] if mins[j] is not None else np.uint64(0),
                    maxs[j] if maxs[j] is not None else np.uint64(0),
                )
            )
    encoder = ZOrderEncoder(_Z_BITS, specs)

    # pass 2: spill into contiguous z-ranges
    spill_root = os.path.join(
        os.path.dirname(ctx.index_data_path),
        "_spill_z_" + os.path.basename(ctx.index_data_path).replace("=", "_"),
    )
    os.makedirs(spill_root, exist_ok=True)
    range_parts: dict = {}
    try:
        import pyarrow as pa

        for wi, w in enumerate(waves):
            batch = scan.materialize(w)
            if batch.num_rows == 0:
                continue
            with covering_build.stage("zorder_interleave"):
                planes = encoder.planes(
                    [batch.column(c) for c in indexed_cols]
                )
            pid = (planes[0] >> np.uint32(32 - _ZORDER_SPILL_BITS)).astype(
                np.int32
            )
            table = batch.to_arrow()
            for p, idx in pio.bucket_runs(pid):
                path = os.path.join(spill_root, f"r{p:03d}-w{wi:05d}.parquet")
                pio.write_table(path, table.take(pa.array(idx)))
                range_parts.setdefault(p, []).append(path)

        # merge: per z-range ascending, local sort == global order.
        # A skewed/constant key can funnel most rows into ONE range;
        # oversized ranges split recursively on deeper z-address bits —
        # through the remaining windows of plane 0, then every deeper
        # plane — and only when EVERY bit of every plane is exhausted
        # (all rows share one complete z-address, whose relative order is
        # semantically arbitrary) is each part sorted and written
        # individually. Peak memory stays bounded either way.
        from hyperspace_tpu.indexes.covering_build import (
            estimated_materialized_bytes,
        )

        total_bits = len(indexed_cols) * encoder.bits
        n_planes = max(1, (total_bits + 31) // 32)

        def plane_floor(plane_idx):
            """Lowest MEANINGFUL bit of a plane: the last plane's tail
            below 32 - (total_bits mod 32) is zero padding — descending
            into it would read and rewrite oversized groups without
            discriminating anything."""
            if plane_idx == n_planes - 1:
                rem = total_bits - 32 * (n_planes - 1)
                return 32 - rem
            return 0

        written: List[str] = []
        state = {"file_idx": 0}

        def write_sorted(table):
            paths = _write_parts(ctx, table, target_bytes, state["file_idx"])
            written.extend(paths)
            state["file_idx"] += len(paths)

        def sort_batch(batch):
            with covering_build.stage("zorder_interleave"):
                planes = encoder.planes(
                    [batch.column(c) for c in indexed_cols]
                )
            with covering_build.stage("zorder_sort"):
                perm = lexsort_perm(planes)
            with covering_build.stage("take"):
                batch = batch.take(perm)
            with covering_build.stage("to_arrow"):
                return batch.to_arrow()

        def next_window(plane_idx, shift):
            """The split window after (plane_idx, shift): slide down the
            current plane (clamping the last window to the plane's floor
            so the lowest meaningful bits still discriminate), then
            advance to the next plane."""
            floor = plane_floor(plane_idx)
            if shift > floor:
                return plane_idx, max(shift - _ZORDER_SPILL_BITS, floor)
            nxt = plane_idx + 1
            return nxt, max(
                32 - _ZORDER_SPILL_BITS,
                plane_floor(nxt) if nxt < n_planes else 0,
            )

        def merge_parts(parts, plane_idx, shift):
            est = estimated_materialized_bytes(parts, "parquet")
            if est <= budget or plane_idx >= n_planes:
                if plane_idx >= n_planes and est > budget:
                    # every z-address bit is exhausted: rows share one
                    # complete z-address, whose relative order is
                    # arbitrary — sort parts independently
                    for part in parts:
                        write_sorted(
                            sort_batch(
                                ColumnarBatch.from_arrow(
                                    pio.read_table([part], None)
                                )
                            )
                        )
                    return
                write_sorted(
                    sort_batch(
                        ColumnarBatch.from_arrow(pio.read_table(parts, None))
                    )
                )
                return
            # split on the window's _ZORDER_SPILL_BITS bits of this plane
            sub_parts: dict = {}
            nxt = next_window(plane_idx, shift)
            for part in parts:
                b = ColumnarBatch.from_arrow(pio.read_table([part], None))
                plane = encoder.planes(
                    [b.column(c) for c in indexed_cols]
                )[plane_idx]
                sub = ((plane >> np.uint32(shift))
                       & np.uint32((1 << _ZORDER_SPILL_BITS) - 1)).astype(
                    np.int32
                )
                table = b.to_arrow()
                for sp, idx in pio.bucket_runs(sub):
                    path = part + f".s{sp:03d}"
                    pio.write_table(path, table.take(pa.array(idx)))
                    sub_parts.setdefault(sp, []).append(path)
            for sp in sorted(sub_parts):
                merge_parts(sub_parts[sp], *nxt)

        for p in sorted(range_parts):
            merge_parts(
                range_parts[p], 0, 32 - 2 * _ZORDER_SPILL_BITS
            )
        return written
    finally:
        shutil.rmtree(spill_root, ignore_errors=True)


class ZOrderCoveringIndexConfig(IndexConfigTrait):
    """name + indexedColumns + includedColumns
    (ZOrderCoveringIndexConfig.scala)."""

    def __init__(
        self,
        index_name: str,
        indexed_columns: List[str],
        included_columns: Optional[List[str]] = None,
    ):
        if not index_name:
            raise HyperspaceException("Index name cannot be empty")
        if not indexed_columns:
            raise HyperspaceException("indexed_columns cannot be empty")
        self._name = index_name
        self._indexed = list(indexed_columns)
        self._included = list(included_columns or [])

    @property
    def index_name(self) -> str:
        return self._name

    @property
    def indexed_columns(self) -> List[str]:
        return list(self._indexed)

    @property
    def included_columns(self) -> List[str]:
        return list(self._included)

    @property
    def referenced_columns(self) -> List[str]:
        return self._indexed + self._included

    def _target_bytes(self, ctx) -> int:
        return ctx.session.conf.zorder_target_source_bytes_per_partition

    def create_index(self, ctx, source_data, properties: Dict[str, str]):
        from hyperspace_tpu.indexes import covering_build

        covering, batch = covering_build.create_covering_index(
            ctx, source_data, self, properties
        )
        # a SourceScan (dataset beyond the memory budget) flows straight
        # into write(): the streamed two-pass z-order build handles it
        index = ZOrderCoveringIndex(
            covering.indexed_columns,
            covering.included_columns,
            covering.schema_json,
            self._target_bytes(ctx),
            dict(properties),
        )
        return index, batch

    def describe_index(self, ctx, source_data, properties: Dict[str, str]):
        from hyperspace_tpu.indexes import covering_build

        covering = covering_build.describe_covering_index(
            ctx, source_data, self, properties
        )
        return ZOrderCoveringIndex(
            covering.indexed_columns,
            covering.included_columns,
            covering.schema_json,
            self._target_bytes(ctx),
            dict(properties),
        )
