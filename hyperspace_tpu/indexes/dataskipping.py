"""Data-skipping index — per-source-file sketch table.

Reference: ``dataskipping/DataSkippingIndex.scala:44-336``: build
(`createIndexData:291-317`) groups rows by source file and aggregates each
sketch; query time (`translateFilterCondition:143-185`) converts the filter
predicate into a predicate over the sketch table and prunes source files.
Unlike the covering kinds, the rewritten plan still scans the SOURCE —
just fewer files (``DataSkippingFileIndex``,
``dataskipping/execution/DataSkippingFileIndex.scala:32-74``).
"""

from __future__ import annotations

import json
import time
from typing import Dict, List, Optional, Tuple

import numpy as np
import pyarrow as pa

from hyperspace_tpu.constants import DATA_FILE_NAME_ID
from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.indexes.base import Index, IndexConfigTrait, UpdateMode
from hyperspace_tpu.indexes.registry import register_index
from hyperspace_tpu.indexes.sketches import Sketch, sketch_from_dict
from hyperspace_tpu.io import parquet as pio
from hyperspace_tpu.io.columnar import ColumnarBatch
from hyperspace_tpu.obs import trace
from hyperspace_tpu.plan import expressions as E

#: the ``sketch`` span's attrs, each a sum over the source files: the
#: read (``covering_build._read_source_files``' task seconds, thread
#: seconds where its pool overlaps the files, and the ColumnarBatch on
#: the caller's thread), each sketch kind's ``aggregate`` (its
#: ``seconds_attr``; a kind the index does not hold reads 0, one not
#: named here appears where it runs), inside the bloom's the word split
#: and pad, the device leg as the host sees it and the host's bit
#: setting, and last the sketch table's assembly
_SKETCH_SECONDS = (
    "read_s",
    "minmax_s",
    "bloom_s",
    "words_s",
    "device_s",
    "set_bits_s",
    "table_s",
)


@register_index
class DataSkippingIndex(Index):
    kind = "DataSkippingIndex"
    kind_abbr = "DS"

    def __init__(
        self,
        sketches: List[Sketch],
        schema_json: str = "",
        properties: Optional[Dict[str, str]] = None,
    ):
        self.sketches = list(sketches)
        self.schema_json = schema_json
        self.properties: Dict[str, str] = dict(properties or {})

    def __eq__(self, other):
        return (
            isinstance(other, DataSkippingIndex)
            and [s.to_dict() for s in self.sketches]
            == [s.to_dict() for s in other.sketches]
        )

    def __hash__(self):
        return hash(tuple(s.kind + s.column for s in self.sketches))

    # -- schema surface -----------------------------------------------------
    @property
    def indexed_columns(self) -> List[str]:
        seen = []
        for s in self.sketches:
            for c in s.referenced_columns():
                if c not in seen:
                    seen.append(c)
        return seen

    @property
    def included_columns(self) -> List[str]:
        return []

    @property
    def can_handle_deleted_files(self) -> bool:
        # one sketch row per file: deletion = drop rows (no lineage needed)
        return True

    # -- serialization ------------------------------------------------------
    def to_dict(self) -> dict:
        return {
            "type": self.kind,
            "kindAbbr": self.kind_abbr,
            "sketches": [s.to_dict() for s in self.sketches],
            "schemaJson": self.schema_json,
            "properties": dict(self.properties),
        }

    @classmethod
    def from_dict(cls, d: dict) -> "DataSkippingIndex":
        return cls(
            [sketch_from_dict(s) for s in d["sketches"]],
            d.get("schemaJson", ""),
            d.get("properties", {}),
        )

    # -- build --------------------------------------------------------------
    def build_sketch_rows(self, ctx, plan_relation) -> pa.Table:
        """One sketch row per source file (createIndexData:291-317). File
        ids are keyed by the provider's (path,size,mtime) view so they
        match the ids recorded in the log entry's source content."""
        from hyperspace_tpu.indexes.covering_build import (
            _read_source_files,
            source_file_infos,
        )

        fields: List[Tuple[str, pa.DataType]] = [(DATA_FILE_NAME_ID, pa.int64())]
        rows: List[Dict] = []
        out_fields = None
        # what the span's seconds went to, summed over the files
        acc = dict.fromkeys(_SKETCH_SECONDS, 0.0)
        acc.update(h2d_bytes=0, d2h_bytes=0)
        n_rows = 0
        with trace.span("sketch") as sp:
            infos = sorted(source_file_infos(ctx.session, plan_relation))
            workers, reads = _read_source_files(
                [f for f, _size, _mtime in infos],
                plan_relation.fmt,
                self.indexed_columns,
            )
            for (table, read_ns), (f, size, mtime) in zip(reads, infos):
                fid = ctx.file_id_tracker.add_file(f, size, mtime)
                t0 = time.perf_counter()
                batch = ColumnarBatch.from_arrow(table)
                acc["read_s"] += read_ns / 1e9 + time.perf_counter() - t0
                row = {DATA_FILE_NAME_ID: fid}
                if out_fields is None:
                    out_fields = list(fields)
                    for s in self.sketches:
                        src_t = batch.column(s.referenced_columns()[0]).arrow_type
                        out_fields.extend(s.output_fields(src_t))
                for s in self.sketches:
                    t0 = time.perf_counter()
                    row.update(s.aggregate(batch, acc))
                    acc[s.seconds_attr] = (
                        acc.get(s.seconds_attr, 0.0) + time.perf_counter() - t0
                    )
                rows.append(row)
                n_rows += batch.num_rows
            if out_fields is None:
                raise HyperspaceException("No source files to sketch")
            t0 = time.perf_counter()
            table = pa.table(
                {
                    name: pa.array([r.get(name) for r in rows], type=t)
                    for name, t in out_fields
                }
            )
            acc["table_s"] += time.perf_counter() - t0
            trace.accumulate("h2d_bytes", acc.pop("h2d_bytes"))
            trace.accumulate("d2h_bytes", acc.pop("d2h_bytes"))
            sp.set("files", len(rows))
            sp.set("rows", n_rows)
            sp.set("workers", workers)
            for key, seconds in acc.items():
                sp.set(key, round(seconds, 6))
        return table

    def write(self, ctx, index_data: pa.Table) -> None:
        import os

        path = os.path.join(ctx.index_data_path, "part-00000-sketch.parquet")
        with trace.span("write", files=1) as sp:
            os.makedirs(ctx.index_data_path, exist_ok=True)
            pio.write_table(path, index_data)
            sp.set("bytes", os.path.getsize(path))

    def optimize(self, ctx, files_to_optimize: List[str]) -> None:
        table = pio.read_table(files_to_optimize, None)
        self.write(ctx, table)

    def refresh_incremental(
        self, ctx, appended_df, deleted_source_file_ids, previous_content
    ) -> Tuple["DataSkippingIndex", UpdateMode]:
        parts = []
        if appended_df is not None:
            rel = appended_df.logical_plan.collect_leaves()[0].relation
            parts.append(self.build_sketch_rows(ctx, rel))
        if deleted_source_file_ids:
            old = pio.read_table(list(previous_content.files), None)
            ids = np.asarray(old.column(DATA_FILE_NAME_ID))
            keep = ~np.isin(ids, np.array(deleted_source_file_ids, dtype=np.int64))
            parts.append(old.filter(pa.array(keep)))
            mode = UpdateMode.OVERWRITE
        else:
            mode = UpdateMode.MERGE
        if parts:
            self.write(ctx, pa.concat_tables(parts, promote_options="permissive"))
        return self, mode

    def refresh_full(self, ctx, df) -> "DataSkippingIndex":
        rel = df.logical_plan.collect_leaves()[0].relation
        table = self.build_sketch_rows(ctx, rel)
        self.write(ctx, table)
        return self

    # -- query-time translation (translateFilterCondition:143-185) ----------
    def translate_filter(
        self, condition: E.Expr, sketch_table: pa.Table
    ) -> Optional[np.ndarray]:
        """Keep-mask over sketch rows, or None when nothing translates."""

        def walk(expr) -> Optional[np.ndarray]:
            if isinstance(expr, E.And):
                l, r = walk(expr.left), walk(expr.right)
                if l is not None and r is not None:
                    return l & r
                return l if l is not None else r
            if isinstance(expr, E.Or):
                l, r = walk(expr.left), walk(expr.right)
                if l is not None and r is not None:
                    return l | r
                return None  # OR prunes only if BOTH sides translate
            for s in self.sketches:
                m = s.convert_predicate(expr, sketch_table)
                if m is not None:
                    return m
            return None

        return walk(condition)

    def statistics(self, extended: bool = False) -> Dict[str, str]:
        return {
            "sketches": ";".join(repr(s) for s in self.sketches),
            "indexedColumns": ",".join(self.indexed_columns),
            "schema": self.schema_json if extended else "",
        }


class DataSkippingIndexConfig(IndexConfigTrait):
    """name + sketches (DataSkippingIndexConfig.scala:39-95); a
    PartitionSketch is implicit in our build since constancy is detected
    per file (`:72-84` auto-adds it for partitioned sources)."""

    def __init__(self, index_name: str, *sketches: Sketch):
        if not index_name:
            raise HyperspaceException("Index name cannot be empty")
        if not sketches:
            raise HyperspaceException("At least one sketch is required")
        cols = [s.referenced_columns()[0].lower() + s.kind for s in sketches]
        if len(set(cols)) != len(cols):
            raise HyperspaceException("Duplicate sketches")
        self._name = index_name
        self._sketches = list(sketches)

    @property
    def index_name(self) -> str:
        return self._name

    @property
    def referenced_columns(self) -> List[str]:
        out = []
        for s in self._sketches:
            for c in s.referenced_columns():
                if c not in out:
                    out.append(c)
        return out

    def _mk_index(self, ctx, source_data, properties) -> DataSkippingIndex:
        from hyperspace_tpu.utils import resolver

        rel = source_data.logical_plan.collect_leaves()[0].relation
        schema = rel.schema
        resolved_sketches = []
        for s in self._sketches:
            rc = resolver.require_resolve(
                s.referenced_columns(), rel.column_names
            )[0]
            d = s.to_dict()
            d["column"] = rc.name
            d["sourceType"] = str(schema[rc.name])
            resolved_sketches.append(sketch_from_dict(d))
        schema_json = json.dumps(
            [[c, str(schema[c])] for c in self.referenced_columns]
        )
        return DataSkippingIndex(resolved_sketches, schema_json, dict(properties))

    def create_index(self, ctx, source_data, properties: Dict[str, str]):
        index = self._mk_index(ctx, source_data, properties)
        rel = source_data.logical_plan.collect_leaves()[0].relation
        data = index.build_sketch_rows(ctx, rel)
        return index, data

    def describe_index(self, ctx, source_data, properties: Dict[str, str]):
        return self._mk_index(ctx, source_data, properties)
