"""Aggregate index plane: differential + approximation property suite.

The contract under test (docs/agg-serve.md, indexes/aggindex.py,
execution/pipeline_compiler.try_metadata_aggregate): for every supported
``Filter(→Project)→Aggregate`` over a clean index scan, the metadata
plane's answer — fully-covered row groups folded from the persisted
``_aggstate.json`` partials, boundary row groups scanned — is
BIT-IDENTICAL to the fused pass and to the interpreted chain, across the
range-prune dtype matrix; incremental refresh folds only the appended
files' partials; a stale sidecar entry falls back per file (lazy
backfill); the sampling plane's 95% confidence intervals empirically
hold; and approximate answers are NEVER silently substituted for exact
ones.
"""

import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import constants as C
from hyperspace_tpu import functions as F
from hyperspace_tpu.exceptions import ApproximationError
from hyperspace_tpu.execution import pipeline_compiler as PC
from hyperspace_tpu.hyperspace import Hyperspace
from hyperspace_tpu.indexes import aggindex, zonemaps
from hyperspace_tpu.indexes.covering import CoveringIndexConfig
from hyperspace_tpu.indexes.zorder import ZOrderCoveringIndexConfig
from hyperspace_tpu.io import parquet as pio


@pytest.fixture
def s1(session_factory):
    """Mesh-1 session: the metadata plane is a host serving substitution
    with no mesh axis."""
    return session_factory(1)


@pytest.fixture(autouse=True)
def _small_row_groups(monkeypatch):
    """Write index files with small row groups so test-sized fixtures
    exercise real FULL / boundary / EMPTY classification instead of one
    row group per file."""
    monkeypatch.setattr(pio, "INDEX_ROW_GROUP_SIZE", 512)
    aggindex.invalidate_local_cache()
    zonemaps.invalidate_local_cache()
    yield
    aggindex.invalidate_local_cache()
    zonemaps.invalidate_local_cache()


def _write_files(tmp_path, name, table, n_files=4):
    d = tmp_path / name
    d.mkdir()
    n = table.num_rows
    for i in range(n_files):
        lo, hi = i * n // n_files, (i + 1) * n // n_files
        pq.write_table(table.slice(lo, hi - lo), str(d / f"part{i}.parquet"))
    return str(d)


def _tables_bit_equal(a: pa.Table, b: pa.Table) -> None:
    assert a.schema.equals(b.schema), (a.schema, b.schema)
    assert a.num_rows == b.num_rows, (a.num_rows, b.num_rows)
    for name in a.column_names:
        ca = a.column(name).combine_chunks()
        cb = b.column(name).combine_chunks()
        assert ca.is_valid().equals(cb.is_valid()), name
        if pa.types.is_floating(ca.type):
            va = np.asarray(ca.fill_null(0.0)).view(np.int64)
            vb = np.asarray(cb.fill_null(0.0)).view(np.int64)
            np.testing.assert_array_equal(va, vb, err_msg=name)
        else:
            assert ca.equals(cb), name


def _four_way(session, q, expect_meta=True):
    """q() with (1) the metadata plane on, (2) plane off + fused on,
    (3) both off (interpreted chain), (4) unindexed. 1 ≡ 2 ≡ 3
    bit-identically; vs raw the row count must agree. Returns (metadata
    table, metadata-plane stats)."""
    session.enable_hyperspace()
    aggindex.invalidate_local_cache()
    zonemaps.invalidate_local_cache()
    PC.last_aggplane_stats = {}
    meta = q()
    stats = dict(PC.last_aggplane_stats)
    if expect_meta:
        assert stats.get("mode") == "agg_metadata", (
            f"metadata plane did not answer: {stats}"
        )
        assert stats["row_groups_metadata"] > 0, stats
    session.conf.set(C.INDEX_AGG_ENABLED, False)
    PC.last_aggplane_stats = {}
    fused = q()
    assert PC.last_aggplane_stats == {}, "metadata plane ran with flag off"
    session.conf.set(C.SERVE_FUSEDPIPELINE_ENABLED, False)
    interp = q()
    session.conf.unset(C.SERVE_FUSEDPIPELINE_ENABLED)
    session.conf.unset(C.INDEX_AGG_ENABLED)
    session.disable_hyperspace()
    raw = q()
    _tables_bit_equal(meta, fused)
    _tables_bit_equal(meta, interp)
    assert meta.num_rows == raw.num_rows, (meta.num_rows, raw.num_rows)
    return meta, stats


def _dtype_tables(rng, n=8000):
    """The range-prune dtype matrix with METADATA-MERGEABLE aggregates
    (count / count(col) / min / max / int sum / int avg / float min-max;
    float SUM stays on the fused path by contract and is covered by
    test_float_sum_declines_to_fused)."""
    base = np.datetime64("2019-01-01")
    days = np.sort(rng.integers(0, 900, n))

    def num_aggs(df):
        return (
            F.count().alias("n"),
            F.count("c").alias("nc"),
            F.min("c").alias("mn"),
            F.max("c").alias("mx"),
            F.sum("c").alias("sc"),
            F.avg("c").alias("ac"),
            F.min("v").alias("mnv"),
            F.max("v").alias("mxv"),
        )

    def temporal_aggs(df):
        return (
            F.count().alias("n"),
            F.min("c").alias("mn"),
            F.max("c").alias("mx"),
            F.min("v").alias("mnv"),
        )

    def count_only(df):
        return (F.count().alias("n"), F.count("c").alias("nc"))

    v = rng.normal(0, 5, n)
    common = {
        "p": pa.array(rng.integers(0, 10, n), type=pa.int64()),
        "v": pa.array(v),
    }
    yield "ints", {
        "c": pa.array(np.sort(rng.integers(-1000, 1000, n)), type=pa.int64()),
        **common,
    }, lambda df: (df["c"] >= -800) & (df["c"] < 800), num_aggs
    f = np.sort(rng.normal(0, 100, n))
    f[::31] = np.nan
    yield "floats_nan", {
        "c": pa.array(f),
        **common,
    }, lambda df: (df["c"] > -250.0) & (df["c"] <= 250.0), (
        lambda df: (
            F.count().alias("n"),
            F.count("c").alias("nc"),
            F.min("c").alias("mn"),
            F.max("c").alias("mx"),
            F.sum("p").alias("sp"),
        )
    )
    yield "strings", {
        "c": pa.array([f"k{int(x):06d}" for x in rng.integers(0, 5000, n)]),
        "s": pa.array(np.sort(rng.integers(0, 4000, n)), type=pa.int64()),
        **common,
    }, lambda df: (df["s"] >= 100) & (df["s"] < 3900), count_only
    yield "dates", {
        "c": pa.array((base + days).astype("datetime64[D]")),
        **common,
    }, lambda df: (
        (df["c"] >= np.datetime64("2019-02-01"))
        & (df["c"] <= np.datetime64("2021-04-01"))
    ), temporal_aggs
    yield "ts_tz", {
        "c": pa.array(
            (base + days).astype("datetime64[us]"),
            type=pa.timestamp("us", tz="UTC"),
        ),
        **common,
    }, lambda df: (df["c"] >= "2019-02-01") & (df["c"] < "2021-04-01"), (
        temporal_aggs
    )
    yield "nullable_int", {
        "c": pa.array(
            [
                None if i % 11 == 0 else int(x)
                for i, x in enumerate(np.sort(rng.integers(0, 10_000, n)))
            ],
            type=pa.int64(),
        ),
        **common,
    }, lambda df: (df["c"] > 500) & (df["c"] <= 9500), (
        lambda df: (
            F.count().alias("n"),
            F.count("c").alias("nc"),
            F.min("c").alias("mn"),
            F.max("c").alias("mx"),
            F.sum("c").alias("sc"),
        )
    )


class TestMetadataPlaneMatrix:
    """Four-way differential (metadata ≡ fused ≡ interpreted ≡ unindexed
    row count) across the dtype matrix, grouped and ungrouped, over
    z-order (range-sorted) index scans with real FULL + boundary row
    groups."""

    def test_dtype_matrix_grouped(self, s1, tmp_path):
        hs = Hyperspace(s1)
        rng = np.random.default_rng(7)
        for name, arrays, cond_fn, agg_fn in _dtype_tables(rng):
            d = _write_files(tmp_path, name, pa.table(arrays))
            df = s1.read.parquet(d)
            icols = ["s"] if name == "strings" else ["c"]
            inc = [c for c in arrays if c not in icols]
            hs.create_index(
                df, ZOrderCoveringIndexConfig(f"z_{name}", icols, inc)
            )
            q = lambda: (
                df.filter(cond_fn(df))
                .group_by("p")
                .agg(*agg_fn(df))
                .collect()
            )
            out, stats = _four_way(s1, q)
            assert 0 < out.num_rows <= 10, (name, out.num_rows)
            hs.delete_index(f"z_{name}")
            hs.vacuum_index(f"z_{name}")
            s1.index_manager.clear_cache()

    def test_ungrouped_with_boundary(self, s1, tmp_path):
        """A range cutting through the sorted key: interior row groups
        answer from metadata, boundary row groups scan — merged result
        bit-identical, and the telemetry proves both paths ran."""
        hs = Hyperspace(s1)
        rng = np.random.default_rng(11)
        n = 8000
        arrays = {
            "c": pa.array(np.sort(rng.integers(0, 100_000, n)), type=pa.int64()),
            "p": pa.array(rng.integers(0, 6, n), type=pa.int64()),
            "v": pa.array(rng.normal(10, 2, n)),
        }
        d = _write_files(tmp_path, "bnd", pa.table(arrays))
        df = s1.read.parquet(d)
        hs.create_index(df, ZOrderCoveringIndexConfig("z_b", ["c"], ["p", "v"]))
        q = lambda: (
            df.filter((df["c"] >= 7_777) & (df["c"] < 77_777))
            .agg(
                F.count().alias("n"),
                F.min("v").alias("mnv"),
                F.max("v").alias("mxv"),
                F.sum("p").alias("sp"),
                F.avg("p").alias("ap"),
            )
            .collect()
        )
        out, stats = _four_way(s1, q)
        assert stats["row_groups_metadata"] > 0, stats
        assert stats["row_groups_scanned"] > 0, stats  # real boundary
        assert stats["rows_scanned"] > 0
        assert out.num_rows == 1

    def test_fully_covered_zero_rows_read(self, s1, tmp_path):
        """The headline: a fully-covered grouped point aggregate answers
        from the sidecar with ZERO parquet row groups read."""
        hs = Hyperspace(s1)
        rng = np.random.default_rng(13)
        n = 6000
        arrays = {
            "c": pa.array(np.sort(rng.integers(0, 50_000, n)), type=pa.int64()),
            "p": pa.array(rng.integers(0, 8, n), type=pa.int64()),
            "v": pa.array(rng.normal(0, 5, n)),
        }
        d = _write_files(tmp_path, "full", pa.table(arrays))
        df = s1.read.parquet(d)
        hs.create_index(df, ZOrderCoveringIndexConfig("z_f", ["c"], ["p", "v"]))
        q = lambda: (
            df.filter(df["c"] >= 0)
            .group_by("p")
            .agg(F.count().alias("n"), F.sum("c").alias("sc"))
            .collect()
        )
        out, stats = _four_way(s1, q)
        assert stats["row_groups_scanned"] == 0, stats
        assert stats["rows_scanned"] == 0, stats
        assert stats["row_groups_metadata"] == stats["row_groups_total"]

    def test_no_filter_via_aggregate_rule(self, s1, tmp_path):
        """AggregateIndexRule: a bare Aggregate∘Scan (no Filter) rewrites
        onto the covering index and answers entirely from metadata."""
        hs = Hyperspace(s1)
        rng = np.random.default_rng(17)
        n = 5000
        arrays = {
            "k": pa.array(rng.integers(0, 40, n), type=pa.int64()),
            "p": pa.array(rng.integers(0, 5, n), type=pa.int64()),
            "v": pa.array(rng.normal(0, 5, n)),
        }
        d = _write_files(tmp_path, "rule", pa.table(arrays))
        df = s1.read.parquet(d)
        hs.create_index(df, CoveringIndexConfig("ci_r", ["k"], ["p", "v"]))
        q = lambda: (
            df.group_by("p")
            .agg(F.count().alias("n"), F.max("k").alias("mk"))
            .collect()
        )
        out, stats = _four_way(s1, q)
        assert stats["rows_scanned"] == 0, stats
        # float SUM keeps the rule OFF the plan (row order would
        # reassociate the sum vs the source scan)
        s1.enable_hyperspace()
        plan = (
            df.group_by("p").agg(F.sum("v").alias("sv")).explain()
        )
        assert "Hyperspace" not in plan, plan
        s1.disable_hyperspace()

    def test_float_sum_declines_to_fused(self, s1, tmp_path):
        """Float SUM/AVG partials don't merge bit-identically, so the
        metadata plane must decline and the fused pass must serve."""
        hs = Hyperspace(s1)
        rng = np.random.default_rng(19)
        n = 5000
        arrays = {
            "c": pa.array(np.sort(rng.integers(0, 5000, n)), type=pa.int64()),
            "p": pa.array(rng.integers(0, 8, n), type=pa.int64()),
            "v": pa.array(rng.normal(0, 5, n)),
        }
        d = _write_files(tmp_path, "fsum", pa.table(arrays))
        df = s1.read.parquet(d)
        hs.create_index(df, ZOrderCoveringIndexConfig("z_fs", ["c"], ["p", "v"]))
        s1.enable_hyperspace()
        PC.last_aggplane_stats = {}
        PC.last_fused_stats = {}
        old = PC._NATIVE_FUSED_PIPELINE_MIN_ROWS
        PC._NATIVE_FUSED_PIPELINE_MIN_ROWS = 1
        try:
            df.filter(df["c"] >= 0).group_by("p").agg(
                F.sum("v").alias("sv")
            ).collect()
        finally:
            PC._NATIVE_FUSED_PIPELINE_MIN_ROWS = old
        assert PC.last_aggplane_stats == {}, PC.last_aggplane_stats
        assert PC.last_fused_stats.get("mode") == "agg", PC.last_fused_stats
        s1.disable_hyperspace()

    def test_in_predicate_declines(self, s1, tmp_path):
        """IN-list conjuncts lower to a [min,max] HULL — sound for
        pruning, UNSOUND for full-coverage — so the strict lowering must
        decline and results must still match."""
        hs = Hyperspace(s1)
        rng = np.random.default_rng(23)
        n = 4000
        arrays = {
            "c": pa.array(np.sort(rng.integers(0, 3000, n)), type=pa.int64()),
            "p": pa.array(rng.integers(0, 5, n), type=pa.int64()),
        }
        d = _write_files(tmp_path, "inq", pa.table(arrays))
        df = s1.read.parquet(d)
        hs.create_index(df, ZOrderCoveringIndexConfig("z_in", ["c"], ["p"]))
        s1.enable_hyperspace()
        PC.last_aggplane_stats = {}
        got = (
            df.filter(df["c"].isin([5, 2900]))
            .agg(F.count().alias("n"))
            .collect()
        )
        assert PC.last_aggplane_stats == {}, PC.last_aggplane_stats
        s1.disable_hyperspace()
        raw = (
            df.filter(df["c"].isin([5, 2900]))
            .agg(F.count().alias("n"))
            .collect()
        )
        _tables_bit_equal(got, raw)


class TestPartialsTwin:
    """The PR-13 hook: kernel chunk-state snapshots and the numpy twin
    produce IDENTICAL partials, and finalize_partials(fold(chunks)) ==
    the single-pass result."""

    def _plan_and_batch(self, nulls=False):
        from hyperspace_tpu.io.columnar import ColumnarBatch
        from hyperspace_tpu.ops.filter import lower_range_terms

        rng = np.random.default_rng(29)
        n = 4000
        g = rng.integers(0, 12, n).astype(np.float64)
        g[::13] = np.nan
        g[::17] = -0.0
        v = rng.normal(0, 3, n)
        v[::23] = np.nan
        arrays = {
            "c": pa.array(rng.integers(0, 1000, n), type=pa.int64()),
            "g": pa.array(
                [None if nulls and i % 19 == 0 else float(x) for i, x in enumerate(g)]
            ),
            "v": pa.array(v),
            "w": pa.array(rng.integers(-50, 50, n), type=pa.int64()),
        }
        batch = ColumnarBatch.from_arrow(pa.table(arrays))
        schema = {k: batch.column(k).arrow_type for k in arrays}
        from hyperspace_tpu.plan.nodes import AggSpec

        aggs = [
            AggSpec("count", None, "n"),
            AggSpec("count", "v", "nv"),
            AggSpec("sum", "w", "sw"),
            AggSpec("min", "v", "mnv"),
            AggSpec("max", "v", "mxv"),
            AggSpec("min", "w", "mnw"),
            AggSpec("max", "w", "mxw"),
        ]
        import hyperspace_tpu.plan.expressions as E

        cond = E.And(
            E.Ge(E.Col("c"), E.Lit(100)),
            E.Lt(E.Col("c"), E.Lit(900)),
        )
        terms = lower_range_terms(cond, batch)
        fplan = PC._lower_from_terms(terms, ("g",), aggs, schema)
        assert fplan is not None
        return fplan, batch

    def test_kernel_vs_numpy_partials(self, s1):
        from hyperspace_tpu import native
        from hyperspace_tpu.ops.filter import range_mask_numpy

        if native.load() is None:
            pytest.skip("native kernels unavailable")
        fplan, batch = self._plan_and_batch(nulls=True)
        state = PC.AggState(fplan)
        assert state.accumulate(batch)
        kp = state.partials()
        fb = batch.filter(range_mask_numpy(batch, fplan.terms))
        tp = PC.partials_from_batch(fplan, fb, rows_scanned=batch.num_rows)
        assert tp is not None
        # same group SET and per-group accumulators (the kernel's group
        # order is insertion order, the twin's is factorize order —
        # compare through the canonical finalize)
        a = PC.finalize_partials(fplan, kp).to_arrow()
        b = PC.finalize_partials(fplan, tp).to_arrow()
        _tables_bit_equal(a, b)

    def test_fold_equals_single_pass(self, s1):
        fplan, batch = self._plan_and_batch()
        from hyperspace_tpu.ops.filter import range_mask_numpy

        fb = batch.filter(range_mask_numpy(batch, fplan.terms))
        whole = PC.partials_from_batch(fplan, fb)
        acc = PC.PartialsAccumulator(fplan)
        step = 700
        for lo in range(0, fb.num_rows, step):
            idx = np.arange(lo, min(lo + step, fb.num_rows))
            acc.fold(PC.partials_from_batch(fplan, fb.take(idx)))
        a = PC.finalize_partials(fplan, whole).to_arrow()
        b = PC.finalize_partials(fplan, acc.snapshot()).to_arrow()
        _tables_bit_equal(a, b)


# ---------------------------------------------------------------------------
# Capture sweep: the native kernel against its twin, and the pool's order
# ---------------------------------------------------------------------------

_CAP = 8  # max_groups of the sweep cases; row groups of 512 rows


def _case_nulls(rng, n):
    k = rng.integers(0, 6, n)
    return {
        "k": pa.array([None if i % 11 == 0 else int(v) for i, v in enumerate(k)],
                      type=pa.int64()),
        "v": pa.array([None if i % 7 == 0 else float(x)
                       for i, x in enumerate(rng.normal(0, 4, n))]),
        "w": pa.array([None if i % 5 == 0 else int(x)
                       for i, x in enumerate(rng.integers(-9, 9, n))],
                      type=pa.int64()),
    }


def _case_float_specials(rng, n):
    # every column is a measure too: a NaN sum — of data NaNs and of
    # inf - inf — only in the later row groups, of key and value alike
    specials = np.array([-0.0, 0.0, np.inf, 1.5, -2.5, np.nan, -np.inf])
    k = specials[rng.integers(0, 5, n)]
    k[n // 2 :] = specials[rng.integers(0, 7, n - n // 2)]
    v = rng.normal(0, 1e3, n)
    v[::13] = -0.0
    v[::17] = np.inf
    v[n // 2 :: 9] = np.nan
    v[n // 2 :: 19] = -np.inf
    return {"k": pa.array(k), "v": pa.array(v)}


def _case_int_wrap(rng, n):
    big = np.full(n, (1 << 62) + 12345, dtype=np.int64)
    big[::3] = -(1 << 61)
    return {"k": pa.array(rng.integers(0, 4, n), type=pa.int64()),
            "w": pa.array(big)}


def _case_exact_cap(rng, n):
    return {"k": pa.array(np.arange(n, dtype=np.int64) % _CAP),
            "v": pa.array(rng.normal(0, 1, n))}


def _case_cap_plus_one(rng, n):
    # a ninth value, once a row group, past the probe's 4 x cap = 32 rows
    k = np.arange(n, dtype=np.int64) % _CAP
    k[np.arange(n) % 512 == 300] = _CAP
    return {"k": pa.array(k), "v": pa.array(rng.normal(0, 1, n))}


def _case_late_overflow(rng, n):
    # few distinct keys in every row group's prefix, many behind it
    pos = np.arange(n) % 512
    k = np.where(pos < 300, pos % 3, 100 + pos).astype(np.int64)
    return {"k": pa.array(k), "v": pa.array(rng.normal(0, 1, n))}


def _case_string_column(rng, n):
    names = np.array(["ash", "birch", "cedar", None], dtype=object)
    return {"k": pa.array(rng.integers(0, 5, n), type=pa.int64()),
            "s": pa.array(list(names[rng.integers(0, 4, n)]), type=pa.string()),
            "d": pa.array(rng.integers(9000, 9040, n).astype(np.int32),
                          type=pa.date32())}


def _case_empty_file(rng, n):
    return {"k": pa.array([], type=pa.int64()), "v": pa.array([], type=pa.float64())}


def _case_group_keys(rng, n):
    return {"k": pa.array(rng.integers(0, 5, n), type=pa.int64()),
            "g": pa.array(rng.integers(0, 3, n), type=pa.int64()),
            "v": pa.array(rng.normal(0, 1, n))}


# case -> (columns, group_keys, grouped keys kept, grouped passes abandoned)
_SWEEP_CASES = {
    "nulls_in_measure_and_key": (_case_nulls, None, {"k"}, 0),
    "nan_negzero_inf": (_case_float_specials, None, {"k"}, 0),
    "int64_sum_wraps": (_case_int_wrap, None, {"k", "w"}, 0),
    "key_with_exactly_max_groups": (_case_exact_cap, None, {"k"}, 0),
    "key_with_max_groups_plus_one": (_case_cap_plus_one, None, set(), 4),
    "key_over_cap_behind_the_probe": (_case_late_overflow, None, set(), 4),
    "string_count_only_column": (_case_string_column, None, {"k"}, 0),
    "empty_file": (_case_empty_file, None, set(), 0),
    "group_keys_of_the_backfill": (_case_group_keys, ("G",), {"g"}, 0),
}


class TestCaptureSweep:
    """``file_agg_doc`` sweeps with the kernel serve runs and falls back
    to its numpy twin; the sidecar entry and the sample may not tell
    which of the two ran, nor in what order a pool finished the files."""

    @staticmethod
    def _file(tmp_path, case, n=2048):
        cols = _SWEEP_CASES[case][0](np.random.default_rng(41), n)
        path = str(tmp_path / f"{case}.parquet")
        pq.write_table(pa.table(cols), path, row_group_size=512)
        return path

    @staticmethod
    def _doc(path, group_keys=None):
        stats = dict.fromkeys(aggindex._FILE_STATS, 0)
        entry, sample = aggindex.file_agg_doc(
            path, _CAP, 16, group_keys, stats=stats
        )
        return entry, sample, stats

    @pytest.mark.parametrize("case", list(_SWEEP_CASES))
    def test_native_sweep_equals_twin(self, case, tmp_path, monkeypatch):
        from hyperspace_tpu import native

        if native.load() is None:
            pytest.skip("native kernels unavailable")
        _cols, group_keys, kept, early = _SWEEP_CASES[case]
        path = self._file(tmp_path, case)
        entry_n, sample_n, stats_n = self._doc(path, group_keys)
        monkeypatch.setattr(native, "fused_filter_agg", lambda *a, **k: None)
        entry_t, sample_t, stats_t = self._doc(path, group_keys)

        assert json.dumps(entry_n) == json.dumps(entry_t)
        if sample_n is None:
            assert sample_t is None
        else:
            _tables_bit_equal(sample_n, sample_t)
        assert set(entry_n["groups"]) == kept
        # every pass counted once, under the implementation that ran it
        passes = stats_n["sweeps_native"] + stats_n["sweeps_twin"]
        assert passes == stats_t["sweeps_native"] + stats_t["sweeps_twin"]
        assert stats_n["early_rejects"] == early
        assert stats_t["early_rejects"] == 0
        if case == "empty_file":
            return  # a row group of no rows reaches neither
        assert stats_n["sweeps_native"] > 0 and stats_t["sweeps_native"] == 0
        # the twin decides a float sum that came out NaN, and nothing else
        assert (stats_n["sweeps_twin"] > 0) == (case == "nan_negzero_inf")

    def test_cap_is_exact_and_rejects_at_the_row(self, tmp_path):
        """Eight distinct keys are kept, a ninth rejects the row group —
        through the sweep itself, not the prefix probe — and the kernel
        stops there: what it had consumed is less than the row group."""
        from hyperspace_tpu import native

        if native.load() is None:
            pytest.skip("native kernels unavailable")
        kept, _s, st = self._doc(
            self._file(tmp_path, "key_with_exactly_max_groups")
        )
        assert [len(g["kv"]) for g in kept["groups"]["k"]] == [_CAP] * 4
        assert st["early_rejects"] == 0
        for case in ("key_with_max_groups_plus_one",
                     "key_over_cap_behind_the_probe"):
            entry, _s, st = self._doc(self._file(tmp_path, case))
            assert entry["groups"] == {}
            assert st["early_rejects"] == 4, case  # one a row group
            # 4 ungrouped + 4 abandoned "k" passes ("v" fails the probe)
            assert st["sweeps_native"] == 8, case

    @staticmethod
    def _version_dir(tmp_path, n_files=12):
        rng = np.random.default_rng(43)
        d = tmp_path / "v__=0"
        d.mkdir()
        for i in range(n_files):
            n = 700 + 13 * i
            pq.write_table(
                pa.table({
                    "k": pa.array(rng.integers(0, 5, n), type=pa.int64()),
                    "v": pa.array(rng.normal(0, 2, n)),
                    "s": pa.array([f"r{j % 3}" for j in range(n)]),
                }),
                str(d / f"part-{i:05d}-bucket_{i}.parquet"),
                row_group_size=512,
            )
        return str(d)

    @pytest.mark.parametrize("how", ["reversed_completion", "short_switch_interval"])
    def test_many_files_keep_file_order(self, how, tmp_path, monkeypatch):
        """Twelve tasks forced to finish in reverse, or 24 with more
        threads than cores switching every 10 us: the sidecars are the
        parent's serial loop over the twin, byte for byte."""
        import sys
        import threading

        from hyperspace_tpu import native

        d = self._version_dir(tmp_path, 12 if how == "reversed_completion" else 24)
        files = pio.list_format_files(d, "parquet")
        with monkeypatch.context() as m:
            m.setattr(native, "fused_filter_agg", lambda *a, **k: None)
            golden = {"version": 1, "files": {}}
            golden_samples = []
            for f in files:
                entry, sample = aggindex.file_agg_doc(f)
                st = os.stat(f)
                entry["size"], entry["mtime_ns"] = st.st_size, st.st_mtime_ns
                golden["files"][os.path.basename(f)] = entry
                golden_samples.append(sample)

        # every task waits for the file after it: completion is reversed
        real, finished = aggindex.file_agg_doc, []
        done = {f: threading.Event() for f in files}

        def after_the_next(path, *a, **k):
            out = real(path, *a, **k)
            i = files.index(path)
            if i + 1 < len(files):
                # as a read would wait
                with aggindex._outside(k["turn"], k["stats"], "read_s"):
                    assert done[files[i + 1]].wait(30), "tasks did not overlap"
            finished.append(path)
            done[path].set()
            return out

        monkeypatch.setattr(native, "_cores", lambda: 16)  # a thread a file
        interval = sys.getswitchinterval()
        try:
            if how == "reversed_completion":
                monkeypatch.setattr(aggindex, "file_agg_doc", after_the_next)
            else:
                sys.setswitchinterval(1e-5)
            assert aggindex.capture_index_dir(d, _CoveringKind())
        finally:
            sys.setswitchinterval(interval)
        if how == "reversed_completion":
            assert finished == files[::-1]

        with open(os.path.join(d, aggindex.SIDECAR_NAME), encoding="utf-8") as fh:
            text = fh.read()
        assert list(json.loads(text)["files"]) == [
            os.path.basename(f) for f in files
        ]
        assert text == json.dumps(golden)
        sample = pq.read_table(os.path.join(d, aggindex.SAMPLE_NAME))
        assert sample.equals(
            pa.concat_tables(golden_samples, promote_options="permissive")
        )

    # case -> (row counts of the files, how the tasks finish); row groups
    # of 512 rows, a task's grain cut from 65,536 rows to 1,024
    _RANGE_CASES = {
        "one_file_of_many_row_groups": ([512 * 40], "as_they_come"),
        "two_files_of_unequal_row_groups": ([512 * 37, 512 * 5 + 9], "as_they_come"),
        "ranges_finish_in_reverse": ([512 * 24], "reversed"),
        "over_the_cap_in_some_ranges_only": ([512 * 30], "as_they_come"),
        "short_last_range": ([512 * 8 + 3], "as_they_come"),
        "a_file_too_small_to_cut_beside_one_cut": ([900, 512 * 12], "reversed"),
    }

    @staticmethod
    def _range_case_dir(tmp_path, case, rows_of_files):
        rng = np.random.default_rng(53)
        d = tmp_path / "v__=0"
        d.mkdir()
        for i, n in enumerate(rows_of_files):
            rg = np.arange(n) // 512
            # "k" stays under the cap of 8 everywhere; "m" is over it in
            # the row groups of the middle third alone — whole ranges of
            # None between ranges that keep it; "h" is over it in every
            # row group but the file's last, so all ranges but one would
            # prune it and the file may not; "v" is over it everywhere
            third = max(1, (int(rg[-1]) + 1) // 3)
            m = np.where((rg >= third) & (rg < 2 * third),
                         np.arange(n) % 64, np.arange(n) % 5)
            h = np.where(rg == rg[-1], np.arange(n) % 3, np.arange(n) % 97)
            if case != "over_the_cap_in_some_ranges_only":
                m = h = np.arange(n) % 4
            pq.write_table(
                pa.table({
                    "k": pa.array(rng.integers(0, 5, n), type=pa.int64()),
                    "m": pa.array(m, type=pa.int64()),
                    "h": pa.array(h, type=pa.int64()),
                    "v": pa.array(rng.normal(0, 2, n)),
                    "s": pa.array([f"r{j % 3}" for j in range(n)]),
                }),
                str(d / f"part-{i:05d}.parquet"),
                row_group_size=512,
            )
        return str(d)

    @pytest.mark.parametrize("case", list(_RANGE_CASES))
    def test_ranges_of_a_file_are_the_file(self, case, tmp_path, monkeypatch):
        """Fewer files than workers: a file with rows for more than one
        task is swept as row-group ranges, and the sidecars are still the
        serial ``file_agg_doc`` loop over the twin, byte for byte — lists
        end to end in row-group order, the prune of a grouped candidate
        decided over the file and not a range."""
        import threading
        import types

        from hyperspace_tpu import native

        rows_of_files, finish = self._RANGE_CASES[case]
        d = self._range_case_dir(tmp_path, case, rows_of_files)
        files = pio.list_format_files(d, "parquet")
        with monkeypatch.context() as m:
            m.setattr(native, "fused_filter_agg", lambda *a, **k: None)
            golden = {"version": 1, "files": {}}
            golden_samples = []
            for f in files:
                entry, sample = aggindex.file_agg_doc(f, _CAP, 16)
                st = os.stat(f)
                entry["size"], entry["mtime_ns"] = st.st_size, st.st_mtime_ns
                golden["files"][os.path.basename(f)] = entry
                golden_samples.append(sample)
        if case == "over_the_cap_in_some_ranges_only":
            (entry,) = golden["files"].values()
            assert set(entry["groups"]) == {"k", "m", "h"}
            assert entry["groups"]["m"][:10] != [None] * 10
            assert entry["groups"]["m"][10:20] == [None] * 10
            assert entry["groups"]["h"][:-1] == [None] * 29
            assert entry["groups"]["h"][-1] is not None

        monkeypatch.setattr(native, "_cores", lambda: 8)
        monkeypatch.setattr(aggindex, "_TASK_ROWS", 1024)
        tasks, split = aggindex._plan_tasks(files)
        cut = [f for f, n in zip(files, rows_of_files) if n >= 2048]
        assert split == len(cut) and len(tasks) > len(files)
        for f, n in zip(files, rows_of_files):
            ranges = [r for tf, r in tasks if tf == f]
            if f not in cut:
                assert ranges == [None]
                continue
            # contiguous, in order, all of the file, none empty, and no
            # more of them than the file has tasks' worth of rows
            assert 2 <= len(ranges) <= n // 1024
            assert [r.start for r in ranges[1:]] == [r.stop for r in ranges[:-1]]
            assert ranges[0].start == 0 and ranges[-1].stop == -(-n // 512)
            assert all(len(r) > 0 for r in ranges)
        if case == "two_files_of_unequal_row_groups":
            a, b = (sum(tf == f for tf, _r in tasks) for f in files)
            assert a > b >= 2   # 32 ranges between two files, by their rows

        # every task waits for the task after it: completion is reversed
        # (a whole file's task comes through here too, row_groups None)
        real, finished = aggindex._range_agg_doc, []
        done = [threading.Event() for _t in tasks]

        def after_the_next(path, row_groups, *a, **k):
            out = real(path, row_groups, *a, **k)
            i = tasks.index((path, row_groups))
            if i + 1 < len(tasks):
                # as a read would wait
                with aggindex._outside(k["turn"], k["stats"], "read_s"):
                    assert done[i + 1].wait(30), "tasks did not overlap"
            finished.append(i)
            done[i].set()
            return out

        conf = types.SimpleNamespace(
            index_agg_enabled=True, index_agg_max_groups=_CAP,
            index_agg_sample_rows=16)
        with monkeypatch.context() as m:
            if finish == "reversed":
                assert len(tasks) <= 16  # a thread a task: the last can start
                m.setattr(native, "_cores", lambda: 16)
                m.setattr(aggindex, "_plan_tasks", lambda _files: (tasks, split))
                m.setattr(aggindex, "_range_agg_doc", after_the_next)
            assert aggindex.capture_index_dir(d, _CoveringKind(), conf)
        if finish == "reversed":
            assert finished == list(range(len(tasks)))[::-1]

        with open(os.path.join(d, aggindex.SIDECAR_NAME), encoding="utf-8") as fh:
            text = fh.read()
        assert text == json.dumps(golden)
        sample = pq.read_table(os.path.join(d, aggindex.SAMPLE_NAME))
        assert sample.equals(
            pa.concat_tables(golden_samples, promote_options="permissive")
        )
        # and the serve path's backfill, which gets no pool, agrees
        for f in files:
            entry, _sample = aggindex.file_agg_doc(f, _CAP, 16)
            assert {**entry, "size": 0, "mtime_ns": 0} == {
                **golden["files"][os.path.basename(f)], "size": 0, "mtime_ns": 0}

    def test_one_file_failing_fails_no_build_and_publishes_nothing(
        self, s1, tmp_path, monkeypatch
    ):
        rng = np.random.default_rng(47)
        n = 6000
        d = _write_files(tmp_path, "onebad", pa.table({
            "c": pa.array(rng.integers(0, 40_000, n), type=pa.int64()),
            "p": pa.array(rng.integers(0, 6, n), type=pa.int64()),
        }))
        real, seen = aggindex.file_agg_doc, []

        def third_file_bad(path, *a, **k):
            seen.append(path)
            if len(seen) == 3:
                raise ValueError("uncapturable column set")
            return real(path, *a, **k)

        monkeypatch.setattr(aggindex, "file_agg_doc", third_file_bad)
        s1.conf.set(C.INDEX_NUM_BUCKETS, 8)
        hs = Hyperspace(s1)
        hs.create_index(
            s1.read.parquet(d), CoveringIndexConfig("ci_onebad", ["c"], ["p"])
        )
        idx_root = os.path.join(s1.conf.get(C.INDEX_SYSTEM_PATH), "ci_onebad")
        assert len(seen) >= 3
        assert _sidecar_paths(idx_root) == []
        left = [
            n_ for _r, _d, names in os.walk(idx_root) for n_ in names
            if "_agg" in n_
        ]
        assert left == [], left  # no half-written sidecar, no temp file
        # the build stands, and the serve path backfills what is missing
        monkeypatch.setattr(aggindex, "file_agg_doc", real)
        fresh = s1.read.parquet(d)
        _four_way(s1, lambda: fresh.filter(fresh["c"] >= 0).group_by("p").agg(
            F.count().alias("n")).collect())


class _CoveringKind:
    kind = "CoveringIndex"


class TestLifecycle:
    def _mk(self, s1, tmp_path, name="lc", n=6000):
        hs = Hyperspace(s1)
        rng = np.random.default_rng(31)
        arrays = {
            "c": pa.array(np.sort(rng.integers(0, 40_000, n)), type=pa.int64()),
            "p": pa.array(rng.integers(0, 6, n), type=pa.int64()),
            "w": pa.array(rng.integers(0, 4, n), type=pa.int64()),
            "v": pa.array(rng.normal(0, 5, n)),
        }
        d = _write_files(tmp_path, name, pa.table(arrays))
        df = s1.read.parquet(d)
        hs.create_index(
            df, CoveringIndexConfig(f"ci_{name}", ["c"], ["p", "w", "v"])
        )

        def q():
            # re-read per call: a refresh test appends source files, and
            # a stale DataFrame snapshot would defeat the signature match
            fresh = s1.read.parquet(d)
            return (
                fresh.filter(fresh["c"] >= 0)
                .group_by("p")
                .agg(F.count().alias("n"), F.sum("c").alias("sc"))
                .collect()
            )

        return hs, df, d, q

    def test_incremental_refresh_folds_appended(self, s1, tmp_path):
        """Incremental refresh writes a NEW version dir whose sidecar
        covers only the appended files; earlier dirs keep theirs, and
        the merged serve still answers from metadata."""
        hs, df, d, q = self._mk(s1, tmp_path, "inc")
        base_out, _ = _four_way(s1, q)
        idx_root = os.path.join(
            s1.conf.get(C.INDEX_SYSTEM_PATH), "ci_inc"
        )
        before = {
            p: os.path.getmtime(p)
            for p in _sidecar_paths(idx_root)
        }
        assert before
        extra = pa.table(
            {
                "c": pa.array([7, 39_999, 12_345], type=pa.int64()),
                "p": pa.array([1, 2, 3], type=pa.int64()),
                "w": pa.array([0, 1, 2], type=pa.int64()),
                "v": pa.array([1.0, 2.0, 3.0]),
            }
        )
        pq.write_table(extra, os.path.join(d, "part_extra.parquet"))
        hs.refresh_index("ci_inc", "incremental")
        after = _sidecar_paths(idx_root)
        assert len(after) == len(before) + 1  # one NEW dir sidecar
        for p, mt in before.items():
            assert os.path.getmtime(p) == mt  # old sidecars untouched
        out, stats = _four_way(s1, q)
        assert stats["rows_scanned"] == 0, stats
        assert out.num_rows >= base_out.num_rows

    def test_stale_sidecar_per_file_fallback(self, s1, tmp_path):
        """A sidecar whose entry no longer matches its file (size/mtime)
        must fall back PER FILE to lazy backfill — answers stay correct
        and the rest of the sidecar keeps serving."""
        hs, df, d, q = self._mk(s1, tmp_path, "stale")
        idx_root = os.path.join(
            s1.conf.get(C.INDEX_SYSTEM_PATH), "ci_stale"
        )
        side = _sidecar_paths(idx_root)[0]
        with open(side, "r", encoding="utf-8") as fh:
            doc = json.load(fh)
        victim = sorted(doc["files"])[0]
        doc["files"][victim]["mtime_ns"] = 1  # stale vs the real file
        with open(side, "w", encoding="utf-8") as fh:
            json.dump(doc, fh)
        aggindex._sidecar_cached.cache_clear()
        aggindex.invalidate_local_cache()
        out, stats = _four_way(s1, q)
        assert stats["rows_scanned"] == 0, stats  # backfill covered it
        # and the assembly really took the backfill path for that file
        s1.enable_hyperspace()
        plan = s1.optimize(
            df.filter(df["c"] >= 0)
            .group_by("p")
            .agg(F.count().alias("n"))
            ._plan
        )
        s1.disable_hyperspace()

    def test_missing_sidecar_lazy_backfill(self, s1, tmp_path):
        """Pre-existing indexes (no sidecar at all) still get metadata
        answers: the per-file state is lazily computed from the files.
        Backfill restricts its grouped sweep to the QUERIED key — a
        later query grouping by a different key must trigger a fresh
        assembly (AggData.covers_key), not a silent decline."""
        hs, df, d, q = self._mk(s1, tmp_path, "nofile")
        idx_root = os.path.join(
            s1.conf.get(C.INDEX_SYSTEM_PATH), "ci_nofile"
        )
        for p in _sidecar_paths(idx_root):
            os.unlink(p)
        aggindex.invalidate_local_cache()
        out, stats = _four_way(s1, q)
        assert stats["rows_scanned"] == 0, stats
        # different group key over the SAME backfilled file set
        s1.enable_hyperspace()
        PC.last_aggplane_stats = {}
        fresh = s1.read.parquet(d)
        fresh.filter(fresh["c"] >= 0).group_by("w").agg(
            F.count().alias("n")
        ).collect()
        st2 = dict(PC.last_aggplane_stats)
        assert st2.get("mode") == "agg_metadata", st2
        assert st2["rows_scanned"] == 0, st2
        s1.disable_hyperspace()

    def test_vacuum_outdated_keeps_latest_sidecar(self, s1, tmp_path):
        """vacuum('outdated') drops old version dirs (sidecars die with
        them) but must NOT delete the retained dir's sidecars."""
        hs, df, d, q = self._mk(s1, tmp_path, "vac")
        pq.write_table(
            pa.table(
                {
                    "c": pa.array([5], type=pa.int64()),
                    "p": pa.array([0], type=pa.int64()),
                    "w": pa.array([0], type=pa.int64()),
                    "v": pa.array([1.0]),
                }
            ),
            os.path.join(d, "part_extra.parquet"),
        )
        hs.refresh_index("ci_vac", "full")
        idx_root = os.path.join(s1.conf.get(C.INDEX_SYSTEM_PATH), "ci_vac")
        # a crash-leaked publish temp in the retained dir: vacuum is its
        # only sweeper and must delete it while keeping the sidecars
        keep_dir = os.path.dirname(_sidecar_paths(idx_root)[-1])
        leak = os.path.join(keep_dir, "._aggstate.json.tmp.999")
        with open(leak, "w", encoding="utf-8") as fh:
            fh.write("{}")
        hs.vacuum_index("ci_vac")  # ACTIVE → outdated vacuum
        assert not os.path.exists(leak), "vacuum left the crash temp"
        remaining = _sidecar_paths(idx_root)
        assert remaining, "retained version dir lost its aggstate sidecar"
        out, stats = _four_way(s1, q)
        assert stats["rows_scanned"] == 0, stats

    def test_serve_cache_aggstate_kind(self, s1, tmp_path):
        """Serve-server mode caches the assembled state under
        ("aggstate", fp) and evict_kind reclaims it."""
        hs, df, d, q = self._mk(s1, tmp_path, "sc")
        s1.enable_hyperspace()
        s1.conf.set(C.SERVE_CACHE_ENABLED, True)
        try:
            q()
            kinds = {k[0] for k in s1.serve_cache._entries}
            assert "aggstate" in kinds, kinds
            assert s1.serve_cache.evict_kind("aggstate") >= 1
        finally:
            s1.conf.set(C.SERVE_CACHE_ENABLED, False)
            s1.clear_serve_cache()
            s1.disable_hyperspace()


def _sidecar_paths(idx_root):
    out = []
    for root, _dirs, names in os.walk(idx_root):
        for n in names:
            if n == aggindex.SIDECAR_NAME:
                out.append(os.path.join(root, n))
    return sorted(out)


class TestApproxPlane:
    def _mk(self, s1, tmp_path, n=20_000):
        hs = Hyperspace(s1)
        rng = np.random.default_rng(37)
        arrays = {
            "c": pa.array(np.sort(rng.integers(0, 100_000, n)), type=pa.int64()),
            "p": pa.array(rng.integers(0, 6, n), type=pa.int64()),
            "v": pa.array(rng.gamma(4.0, 10.0, n)),  # positive: rel err sane
        }
        d = _write_files(tmp_path, "apx", pa.table(arrays))
        df = s1.read.parquet(d)
        hs.create_index(df, ZOrderCoveringIndexConfig("z_apx", ["c"], ["p", "v"]))
        return hs, df

    def test_disabled_raises_and_exact_never_substituted(self, s1, tmp_path):
        hs, df = self._mk(s1, tmp_path, n=4000)
        s1.enable_hyperspace()
        dfq = df.filter(df["c"] >= 0).agg(F.count().alias("n"))
        with pytest.raises(ApproximationError):
            dfq.collect_approx()
        # approx enabled does NOT leak into exact collect()
        s1.conf.set(C.SERVE_APPROX_ENABLED, True)
        exact = dfq.collect()
        assert exact.column("n").to_pylist() == [4000]
        assert exact.schema.field("n").type == pa.int64()
        s1.conf.unset(C.SERVE_APPROX_ENABLED)
        s1.disable_hyperspace()

    def test_unapproximable_aggregates_raise(self, s1, tmp_path):
        hs, df = self._mk(s1, tmp_path, n=4000)
        s1.enable_hyperspace()
        s1.conf.set(C.SERVE_APPROX_ENABLED, True)
        try:
            with pytest.raises(ApproximationError):
                df.filter(df["c"] >= 0).agg(F.min("v").alias("m")).collect_approx()
            with pytest.raises(ApproximationError):
                # MULTI-key grouped: not estimable (single-key is — see
                # test_grouped_estimates_with_per_group_cis)
                df.filter(df["c"] >= 0).group_by("p", "c").agg(
                    F.count().alias("n")
                ).collect_approx()
        finally:
            s1.conf.unset(C.SERVE_APPROX_ENABLED)
            s1.disable_hyperspace()

    def test_budget_violation_raises(self, s1, tmp_path):
        hs, df = self._mk(s1, tmp_path)
        s1.enable_hyperspace()
        s1.conf.set(C.SERVE_APPROX_ENABLED, True)
        try:
            with pytest.raises(ApproximationError):
                # a near-empty selection: CI half-width dwarfs the tiny
                # estimate, the budget must reject it
                df.filter(df["c"] < 3).agg(
                    F.count().alias("n")
                ).collect_approx(max_rel_error=0.01)
        finally:
            s1.conf.unset(C.SERVE_APPROX_ENABLED)
            s1.disable_hyperspace()

    def test_grouped_estimates_with_per_group_cis(self, s1, tmp_path):
        """Single-key grouped COUNT/SUM: one row per observed group,
        key-sorted, each with its own 95% interval — and the intervals
        contain the exact answers (a seeded check, not probabilistic
        hand-waving: this seed's sample is fixed)."""
        hs, df = self._mk(s1, tmp_path)
        s1.enable_hyperspace()
        s1.conf.set(C.SERVE_APPROX_ENABLED, True)
        try:
            q = df.filter(df["c"] < 60_000).group_by("p").agg(
                F.count().alias("n"), F.sum("v").alias("sv")
            )
            approx = q.collect_approx(max_rel_error=0.9)
            exact = q.collect().sort_by([("p", "ascending")])
            assert approx.column_names == ["p", "n", "n_lo", "n_hi", "sv", "sv_lo", "sv_hi"]
            assert approx.column("p").to_pylist() == exact.column("p").to_pylist()
            an = approx.to_pydict()
            en = exact.to_pydict()
            held = sum(
                1
                for i in range(len(an["p"]))
                if an["n_lo"][i] <= en["n"][i] <= an["n_hi"][i]
            )
            # 95% intervals over 6 groups: tolerate one miss, no more
            assert held >= len(an["p"]) - 1, (an, en)
            for i in range(len(an["p"])):
                assert an["n_lo"][i] <= an["n"][i] <= an["n_hi"][i]
                assert an["sv_lo"][i] <= an["sv"][i] <= an["sv_hi"][i]
            # estimates are float64 — never mistakable for exact ints
            assert approx.schema.field("n").type == pa.float64()
        finally:
            s1.conf.unset(C.SERVE_APPROX_ENABLED)
            s1.disable_hyperspace()

    def test_grouped_budget_applies_per_group(self, s1, tmp_path):
        """A budget every group must hold: a rare group's wide interval
        rejects the whole answer rather than shipping one over-trusted
        row."""
        hs, df = self._mk(s1, tmp_path)
        s1.enable_hyperspace()
        s1.conf.set(C.SERVE_APPROX_ENABLED, True)
        try:
            with pytest.raises(ApproximationError):
                df.filter(df["c"] < 60_000).group_by("p").agg(
                    F.count().alias("n")
                ).collect_approx(max_rel_error=0.01)
        finally:
            s1.conf.unset(C.SERVE_APPROX_ENABLED)
            s1.disable_hyperspace()

    def test_single_sample_stratum_refused(self, s1, tmp_path):
        """A partially-sampled stratum with ONE sample row has no
        estimable variance — the estimator must refuse, never return a
        zero-width 'interval'."""
        hs = Hyperspace(s1)
        rng = np.random.default_rng(43)
        n = 4000
        s1.conf.set(C.INDEX_AGG_SAMPLE_ROWS, 1)
        try:
            d = _write_files(tmp_path, "one", pa.table({
                "c": pa.array(np.sort(rng.integers(0, 9000, n)), type=pa.int64()),
                "v": pa.array(rng.gamma(2.0, 3.0, n)),
            }))
            df = s1.read.parquet(d)
            hs.create_index(df, ZOrderCoveringIndexConfig("z_one", ["c"], ["v"]))
            s1.enable_hyperspace()
            s1.conf.set(C.SERVE_APPROX_ENABLED, True)
            with pytest.raises(ApproximationError):
                df.filter(df["c"] >= 0).agg(
                    F.count().alias("n")
                ).collect_approx(max_rel_error=1e9)
        finally:
            s1.conf.unset(C.INDEX_AGG_SAMPLE_ROWS)
            s1.conf.unset(C.SERVE_APPROX_ENABLED)
            s1.disable_hyperspace()

    def test_rewritten_file_never_serves_stale_samples(self, s1, tmp_path):
        """A data file rewritten under the same basename must sample from
        the fresh backfill read, never the dir sidecar's old rows."""
        hs, df = self._mk(s1, tmp_path, n=4000)
        rel_files = None
        s1.enable_hyperspace()
        s1.conf.set(C.SERVE_APPROX_ENABLED, True)
        try:
            sel = df.filter(df["c"] >= 0)
            before = sel.agg(F.count().alias("n")).collect_approx(
                max_rel_error=1e9
            )
            # dirty ONE index file's identity (stat changes; content-wise
            # this simulates a rewrite) and drop assembled caches
            idx_root = os.path.join(
                s1.conf.get(C.INDEX_SYSTEM_PATH), "z_apx"
            )
            victim = None
            for root, _dirs, names in os.walk(idx_root):
                for nme in sorted(names):
                    if nme.endswith(".parquet") and not nme.startswith("_"):
                        victim = os.path.join(root, nme)
                        break
                if victim:
                    break
            os.utime(victim, ns=(1, 1))
            aggindex.invalidate_local_cache()
            # the estimate must still be produced (backfilled sample for
            # the dirtied file) and still bracket the exact answer
            est = sel.agg(F.count().alias("n")).collect_approx(
                max_rel_error=1e9
            )
            s1.conf.set(C.SERVE_APPROX_ENABLED, False)
            truth = sel.agg(F.count().alias("n")).collect()
            s1.conf.set(C.SERVE_APPROX_ENABLED, True)
            tn = truth.column("n").to_pylist()[0]
            e = est.to_pydict()
            assert e["n_lo"][0] <= tn <= e["n_hi"][0], (e, tn)
        finally:
            s1.conf.unset(C.SERVE_APPROX_ENABLED)
            s1.disable_hyperspace()

    def test_error_bounds_hold(self, s1, tmp_path):
        """95% CIs over a battery of seeded range queries: coverage of
        the true COUNT/SUM must hold well above the coin-flip line (the
        battery shares one sample, so outcomes correlate; ≥85% observed
        coverage on 40 windows is the flake-proof assertion for a
        nominal 95% interval)."""
        hs, df = self._mk(s1, tmp_path)
        s1.enable_hyperspace()
        s1.conf.set(C.SERVE_APPROX_ENABLED, True)
        rng = np.random.default_rng(41)
        hits_n = hits_s = total = 0
        try:
            for _ in range(40):
                lo = int(rng.integers(0, 60_000))
                hi = lo + int(rng.integers(20_000, 40_000))
                sel = df.filter((df["c"] >= lo) & (df["c"] < hi))
                est = sel.agg(
                    F.count().alias("n"), F.sum("v").alias("sv")
                ).collect_approx(max_rel_error=1e9)
                s1.conf.set(C.SERVE_APPROX_ENABLED, False)
                truth = sel.agg(
                    F.count().alias("n"), F.sum("v").alias("sv")
                ).collect()
                s1.conf.set(C.SERVE_APPROX_ENABLED, True)
                tn = truth.column("n").to_pylist()[0]
                ts = truth.column("sv").to_pylist()[0] or 0.0
                e = est.to_pydict()
                total += 1
                if e["n_lo"][0] <= tn <= e["n_hi"][0]:
                    hits_n += 1
                if e["sv_lo"][0] <= ts <= e["sv_hi"][0]:
                    hits_s += 1
        finally:
            s1.conf.unset(C.SERVE_APPROX_ENABLED)
            s1.disable_hyperspace()
        assert hits_n / total >= 0.85, (hits_n, total)
        assert hits_s / total >= 0.85, (hits_s, total)
