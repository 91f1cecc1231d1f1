"""Sketches for the data-skipping index.

Reference: ``dataskipping/sketches/`` — ``Sketch.scala:36-119`` (the
expressions/aggregate/convertPredicate contract), ``MinMaxSketch.scala``
(range pruning for =,<,≤,>,≥,In), ``BloomFilterSketch.scala`` (equality/In
membership pruning), ``PartitionSketch.scala`` (constant-per-file
columns). A sketch aggregates one source file into a few cells of the
sketch table and converts query conjuncts into keep-masks over its rows.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple, Type

import numpy as np
import pyarrow as pa

from hyperspace_tpu.exceptions import HyperspaceException
from hyperspace_tpu.io.columnar import Column, ColumnarBatch, column_value_range
from hyperspace_tpu.ops.bloom import bit_indices_np
from hyperspace_tpu.plan import expressions as E
from hyperspace_tpu.utils.hashing import murmur3_64_bytes

_SKETCH_REGISTRY: Dict[str, Type["Sketch"]] = {}


def register_sketch(cls):
    _SKETCH_REGISTRY[cls.kind] = cls
    return cls


def sketch_from_dict(d: dict) -> "Sketch":
    cls = _SKETCH_REGISTRY.get(d.get("type"))
    if cls is None:
        raise HyperspaceException(f"Unknown sketch kind: {d.get('type')!r}")
    return cls.from_dict(d)


# Shared NaN/null-aware range helper (io/columnar.column_value_range):
# previously a plain v.min() here let one NaN poison a file's min to NaN,
# making `min <= lit` False and wrongly skipping a file with matching rows.
_column_min_max = column_value_range


# Col-vs-Lit normalization lives in plan/expressions (shared with the
# executor's bucket pruning); keep the historical local name.
_normalize_conjunct = E.normalize_comparison


class Sketch:
    kind = "Sketch"
    #: the build's ``sketch`` span attr that sums this kind's aggregate seconds
    seconds_attr = "sketch_s"

    def __init__(self, column: str):
        self.column = column
        # arrow type string of the source column, resolved at index
        # creation; literals are coerced against it at probe time
        self.source_type: Optional[str] = None

    # -- identity / serialization ------------------------------------------
    def __eq__(self, other):
        return type(self) is type(other) and self.to_dict() == other.to_dict()

    def __repr__(self):
        return f"{self.kind}({self.column})"

    def to_dict(self) -> dict:
        d = {"type": self.kind, "column": self.column}
        if self.source_type is not None:
            d["sourceType"] = self.source_type
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "Sketch":
        s = cls(d["column"])
        s.source_type = d.get("sourceType")
        return s

    # -- contract -----------------------------------------------------------
    def referenced_columns(self) -> List[str]:
        return [self.column]

    def output_fields(self, source_type: pa.DataType) -> List[Tuple[str, pa.DataType]]:
        raise NotImplementedError

    def aggregate(
        self, batch: ColumnarBatch, acc: Optional[Dict[str, float]] = None
    ) -> Dict[str, Any]:
        """One source file's batch -> sketch cell values. ``acc``, where
        given, sums what a sketch's seconds went to (``perf_counter``
        deltas by name, the build span's attrs)."""
        raise NotImplementedError

    def convert_predicate(
        self, expr: E.Expr, table: pa.Table
    ) -> Optional[np.ndarray]:
        """Keep-mask over sketch rows for one conjunct, or None if this
        sketch cannot decide it (Sketch.convertPredicate contract)."""
        return None


@register_sketch
class MinMaxSketch(Sketch):
    kind = "MinMaxSketch"
    seconds_attr = "minmax_s"

    def output_fields(self, source_type):
        return [
            (f"MinMax_{self.column}__min", source_type),
            (f"MinMax_{self.column}__max", source_type),
        ]

    def aggregate(self, batch, acc=None):
        lo, hi = _column_min_max(batch.column(self.column))
        return {
            f"MinMax_{self.column}__min": lo,
            f"MinMax_{self.column}__max": hi,
        }

    def _arrow_type(self):
        if self.source_type is None:
            return None
        from hyperspace_tpu.rules.rule_utils import parse_arrow_type

        try:
            return parse_arrow_type(self.source_type)
        except (ValueError, HyperspaceException):
            return None

    def _cell_zones(self, table: pa.Table, t):
        """The sketch table's min/max cells as a zone-map column
        (``indexes/zonemaps.ColZones``) through the SHARED assembly
        helper, memoized per table identity — ``translate_filter`` probes
        once per conjunct against the same table, and the cell conversion
        is the dominant per-call cost (one pyarrow round trip per
        temporal cell)."""
        cached = getattr(self, "_zone_cache", None)
        if cached is not None and cached[0] is table:
            return cached[1]
        from hyperspace_tpu.indexes import zonemaps as zm

        lo_cells = table.column(f"MinMax_{self.column}__min").to_pylist()
        hi_cells = table.column(f"MinMax_{self.column}__max").to_pylist()
        cells = [
            "allnull" if lo is None and hi is None else (lo, hi)
            for lo, hi in zip(lo_cells, hi_cells)
        ]
        cz = zm.column_zones(cells, t)
        self._zone_cache = (table, cz)
        return cz

    def convert_predicate(self, expr, table):
        """Keep-mask over sketch rows, evaluated for ALL files in one
        vectorized pass through the zone-map overlap test
        (``indexes/zonemaps``) — the interval extraction and literal
        lowering are SHARED with the executor's ``_range_pruned_scan``,
        so sketch pruning and zone-map pruning can never disagree on
        what a literal means."""
        from hyperspace_tpu.indexes import zonemaps as zm

        if f"MinMax_{self.column}__min" not in table.column_names:
            return None
        t = self._arrow_type()
        if t is None:
            return None  # no recorded type: abstain (sound, and real
            # indexes always record one at creation)
        if isinstance(expr, E.In):
            if not (
                isinstance(expr.child, E.Col)
                and expr.child.name.lower() == self.column.lower()
            ):
                return None
            cz = self._cell_zones(table, t)
            masks = []
            for v in expr.values:
                if v is None:
                    continue
                iv = zm.interval_for("=", v, t)
                if iv is None:
                    return None  # incomparable literal type: abstain
                masks.append(zm.zone_keep_mask(cz, iv))
            if not masks:
                return np.zeros(len(cz.has), dtype=bool)
            return np.logical_or.reduce(masks)
        norm = _normalize_conjunct(expr)
        if norm is None:
            return None
        op, col, lit = norm
        if col.lower() != self.column.lower() or op == "!=":
            return None
        iv = zm.interval_for(op, lit, t)
        if iv is None:
            return None  # incomparable literal type: abstain
        return zm.zone_keep_mask(self._cell_zones(table, t), iv)


@register_sketch
class BloomFilterSketch(Sketch):
    kind = "BloomFilterSketch"
    seconds_attr = "bloom_s"

    def __init__(self, column: str, fpp: float = 0.01, expected_items: int = 10000):
        super().__init__(column)
        self.fpp = float(fpp)
        self.expected_items = int(expected_items)
        from hyperspace_tpu.ops.bloom import optimal_params

        self.m, self.k = optimal_params(self.expected_items, self.fpp)

    def to_dict(self):
        d = {
            "type": self.kind,
            "column": self.column,
            "fpp": self.fpp,
            "expectedItems": self.expected_items,
        }
        if self.source_type is not None:
            d["sourceType"] = self.source_type
        return d

    @classmethod
    def from_dict(cls, d):
        s = cls(d["column"], d.get("fpp", 0.01), d.get("expectedItems", 10000))
        s.source_type = d.get("sourceType")
        return s

    def output_fields(self, source_type):
        return [(f"BloomFilter_{self.column}__bits", pa.binary())]

    def aggregate(self, batch, acc=None):
        from hyperspace_tpu.ops.bloom import build_bloom

        col = batch.column(self.column)
        reps = col.key_rep()
        nulls = col.null_mask
        if nulls is not None:
            reps = reps[~nulls]
        words = build_bloom(reps, self.m, self.k, acc)
        return {f"BloomFilter_{self.column}__bits": words.tobytes()}

    def _probe(self, table: pa.Table, values) -> Optional[np.ndarray]:
        name = f"BloomFilter_{self.column}__bits"
        if name not in table.column_names:
            return None
        reps = []
        for v in values:
            rep = _value_rep(v, self.source_type)
            if rep is _ABSTAIN:
                return None  # un-coercible literal: this sketch can't decide
            if rep is not _NO_MATCH:
                reps.append(rep)
        blobs = table.column(name).to_pylist()
        if not reps:  # every literal is outside the column's value domain
            return np.zeros(len(blobs), dtype=bool)
        blooms = np.stack(
            [
                np.frombuffer(b, dtype=np.uint64)
                if b
                else np.zeros(self.m // 64, dtype=np.uint64)
                for b in blobs
            ]
        )
        idx = bit_indices_np(
            np.array(reps, dtype=np.int64), self.m, self.k
        )  # [k, n_values]
        widx, bit = idx >> 6, (idx & 63).astype(np.uint64)
        # hits[f, j] = all k bits of value j set in bloom f
        hits = (
            (blooms[:, widx] >> bit[None, :, :]) & np.uint64(1)
        ).all(axis=1)
        return hits.any(axis=1)

    def convert_predicate(self, expr, table):
        if isinstance(expr, E.In):
            if (
                isinstance(expr.child, E.Col)
                and expr.child.name.lower() == self.column.lower()
            ):
                vals = [v for v in expr.values if v is not None]
                return self._probe(table, vals)
            return None
        norm = _normalize_conjunct(expr)
        if norm is None:
            return None
        op, col, lit = norm
        if col.lower() != self.column.lower() or op != "=":
            return None
        return self._probe(table, [lit])


_ABSTAIN = object()  # literal un-coercible -> sketch cannot decide
_NO_MATCH = object()  # literal outside the column's domain -> matches nothing


def _value_rep(v, source_type: Optional[str]):
    """Literal -> the int64 key rep io/columnar assigns to the COLUMN's
    values, coercing the literal to the column's type first (an int column
    probed with 2050.0 must hash the integer 2050; a probe the executor
    would match must never be pruned away)."""
    if source_type is None:
        return _ABSTAIN
    t = source_type
    if t in ("string", "large_string"):
        if not isinstance(v, str):
            return _ABSTAIN
        return murmur3_64_bytes(v.encode("utf-8"))
    if t == "bool":
        return int(bool(v))
    if t.startswith("int") or t.startswith("uint"):
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            return _ABSTAIN
        if isinstance(v, float):
            if not v.is_integer():
                return _NO_MATCH
            v = int(v)
        if t.startswith("uint"):
            # Column key_rep for uint64 is the int64 bit-view (values >= 2^63
            # appear negative); the probe must match bit-for-bit.
            if v < 0 or v >= 1 << 64:
                return _NO_MATCH
            return int(np.uint64(v).view(np.int64))
        if v < -(1 << 63) or v >= 1 << 63:
            return _NO_MATCH
        return int(v)
    if t in ("float", "double", "halffloat"):
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            return _ABSTAIN
        f = np.float64(v)
        if f == 0.0:
            return 0
        return int(f.view(np.int64))
    return _ABSTAIN


@register_sketch
class PartitionSketch(Sketch):
    """Constant-per-file column values (the reference auto-adds this for
    hive-partitioned sources, PartitionSketch.scala:38-74; ours detects
    constancy per file at build time, which also covers partition dirs)."""

    kind = "PartitionSketch"
    seconds_attr = "partition_s"

    def output_fields(self, source_type):
        return [
            (f"Partition_{self.column}__val", source_type),
            (f"Partition_{self.column}__const", pa.bool_()),
        ]

    def aggregate(self, batch, acc=None):
        col = batch.column(self.column)
        val, const = None, False
        if batch.num_rows:
            if col.kind == "string":
                codes = np.unique(col.codes)
                const = len(codes) == 1
                if const and codes[0] >= 0:
                    val = col.dictionary[codes[0]]
            else:
                v = col.values
                if col.validity is None or col.validity.all():
                    const = bool((v == v[0]).all()) if len(v) else False
                    if const:
                        val = v[0].item()
        return {
            f"Partition_{self.column}__val": val,
            f"Partition_{self.column}__const": const,
        }

    def convert_predicate(self, expr, table):
        name = f"Partition_{self.column}__val"
        if name not in table.column_names:
            return None
        vals = table.column(name).to_pylist()
        const = np.asarray(table.column(f"Partition_{self.column}__const"))

        def eq_mask(lit):
            return np.array(
                [
                    (not c) or (v is not None and v == lit)
                    for v, c in zip(vals, const)
                ]
            )

        if isinstance(expr, E.In):
            if (
                isinstance(expr.child, E.Col)
                and expr.child.name.lower() == self.column.lower()
            ):
                masks = [eq_mask(v) for v in expr.values if v is not None]
                if not masks:
                    return np.zeros(len(vals), dtype=bool)
                return np.logical_or.reduce(masks)
            return None
        norm = _normalize_conjunct(expr)
        if norm is None:
            return None
        op, col, lit = norm
        if col.lower() != self.column.lower() or op != "=":
            return None
        return eq_mask(lit)
