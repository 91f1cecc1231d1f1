"""Partition-first build pipeline — differential tests.

The partition-then-sort pipeline must produce output BIT-IDENTICAL to
the stable global lexsort by (bucket, keys...): same stable tie order,
same lineage values, same parquet bytes per bucket file (modulo nothing
— the encoding decision is shared), on both the in-memory and the
streaming/spill paths, with and without the native kernels. The
expected files are written here, from ``sort_permutation`` +
``pio.write_bucket_files`` (``_reference_tail``), with nothing of the
build's partition, exchange or per-bucket sorts.
"""

import contextlib
import hashlib
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import constants as C
from hyperspace_tpu.hyperspace import Hyperspace
from hyperspace_tpu.indexes import covering_build
from hyperspace_tpu.indexes.covering import CoveringIndexConfig
from hyperspace_tpu.io import parquet as pio
from hyperspace_tpu.ops.hash import bucket_ids_np
from hyperspace_tpu.ops.sort import (
    partition_by_bucket,
    partitioned_sort_permutation,
    sort_permutation,
)


@pytest.fixture
def hs(session):
    return Hyperspace(session)


@pytest.fixture
def tied_parquet(tmp_path):
    """4 files whose keys collide heavily (3 distinct values per column)
    — long tie runs across files, the stability torture case — plus a
    string column and a float payload."""
    rng = np.random.default_rng(21)
    d = tmp_path / "tied"
    d.mkdir()
    for i in range(4):
        n = 3000
        t = pa.table(
            {
                "k": pa.array(rng.integers(0, 3, n), type=pa.int64()),
                "s": pa.array(
                    [["aa", "bb", "cc"][v] for v in rng.integers(0, 3, n)]
                ),
                "v": pa.array(rng.normal(size=n)),
            }
        )
        pq.write_table(t, d / f"part-{i}.parquet")
    return str(d)


def _sha(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _reference_bucketize(ctx, batch, indexed_cols, num_buckets):
    """(bucket ids, batch) by ONE stable lexsort over (bucket, keys...):
    no exchange, no partition, whatever the session's mesh."""
    reps = batch.key_reps(indexed_cols)
    buckets = bucket_ids_np(reps, num_buckets)
    perm = sort_permutation(reps, buckets)
    return buckets[perm], batch.take(perm)


def _reference_tail(ctx, batch, indexed_cols, num_buckets, file_idx_offset, use_dict):
    """The files an in-memory build must write."""
    buckets, batch = _reference_bucketize(ctx, batch, indexed_cols, num_buckets)
    return pio.write_bucket_files(
        ctx.index_data_path, buckets, batch, num_buckets, file_idx_offset,
        use_dictionary=use_dict,
    )


@pytest.fixture
def reference_build(monkeypatch):
    """Run the block's builds with the test's own tail in the program's
    place: the in-memory tail, and the streaming waves' bucketize."""
    @contextlib.contextmanager
    def swapped():
        with monkeypatch.context() as mp:
            mp.setattr(covering_build, "_write_bucketed_pipelined", _reference_tail)
            mp.setattr(covering_build, "bucketize", _reference_bucketize)
            yield

    return swapped


def _build(session, hs, src, name, budget=0, lineage=False):
    session.conf.set(C.INDEX_BUILD_MEMORY_BUDGET, budget)
    session.conf.set(C.INDEX_LINEAGE_ENABLED, lineage)
    df = session.read.parquet(src)
    hs.create_index(df, CoveringIndexConfig(name, ["k"], ["s", "v"]))
    entry = session.index_manager.get_index_log_entry(name)
    return sorted(entry.content.files)


def _assert_identical_files(files_a, files_b):
    assert [os.path.basename(f) for f in files_a] == [
        os.path.basename(f) for f in files_b
    ]
    for fa, fb in zip(files_a, files_b):
        ta, tb = pq.read_table(fa), pq.read_table(fb)
        assert ta.equals(tb), f"row content/order differs: {fa} vs {fb}"
        assert _sha(fa) == _sha(fb), f"parquet bytes differ: {fa} vs {fb}"


class TestDifferentialBuild:
    def test_in_memory_bit_identical(
        self, session, hs, tied_parquet, reference_build
    ):
        with reference_build():
            expected = _build(session, hs, tied_parquet, "ref")
        pfirst = _build(session, hs, tied_parquet, "pf")
        _assert_identical_files(expected, pfirst)

    def test_lineage_bit_identical(
        self, session, hs, tied_parquet, reference_build
    ):
        """Lineage attaches a per-file constant column whose within-tie
        order is exactly what stability protects."""
        with reference_build():
            expected = _build(session, hs, tied_parquet, "refl", lineage=True)
        pfirst = _build(session, hs, tied_parquet, "pfl", lineage=True)
        _assert_identical_files(expected, pfirst)
        # lineage survives: every file id of the source is present
        t = pa.concat_tables([pq.read_table(f) for f in pfirst])
        assert len(set(t.column(C.DATA_FILE_NAME_ID).to_pylist())) == 4

    def test_streaming_spill_bit_identical(
        self, session, hs, tied_parquet, reference_build
    ):
        """Budget-constrained builds go through the wave/spill/merge loop;
        its per-wave bucketize must partition-first to the same layout."""
        from hyperspace_tpu.indexes.covering_build import (
            estimated_materialized_bytes,
        )

        per_file = estimated_materialized_bytes(
            [os.path.join(tied_parquet, sorted(os.listdir(tied_parquet))[0])],
            "parquet",
        )
        budget = int(per_file * 2.5)
        with reference_build():
            expected = _build(session, hs, tied_parquet, "refs", budget=budget)
        pfirst = _build(session, hs, tied_parquet, "pfs", budget=budget)
        _assert_identical_files(expected, pfirst)

    def test_numpy_leg_bit_identical(self, session, hs, tied_parquet, monkeypatch):
        """HS_NATIVE=0: the pure-numpy twins must reproduce the same
        bytes as the native kernels."""
        from hyperspace_tpu import native

        native_files = _build(session, hs, tied_parquet, "natv")
        monkeypatch.setenv("HS_NATIVE", "0")
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_load_failed", False)
        numpy_files = _build(session, hs, tied_parquet, "nump")
        _assert_identical_files(native_files, numpy_files)

    def test_refresh_incremental_bit_identical(
        self, session, hs, tied_parquet, reference_build
    ):
        """The refresh data plane (append + delete compensation) rides
        the same writers; it must land the new version the reference
        tail writes."""

        def run(name):
            files = _build(session, hs, tied_parquet, name, lineage=True)
            rng = np.random.default_rng(5)
            extra = pa.table(
                {
                    "k": pa.array(rng.integers(0, 3, 500), type=pa.int64()),
                    "s": pa.array(["dd"] * 500),
                    "v": pa.array(rng.normal(size=500)),
                }
            )
            extra_path = os.path.join(tied_parquet, f"extra-{name}.parquet")
            pq.write_table(extra, extra_path)
            session.index_manager.clear_cache()
            hs.refresh_index(name, C.REFRESH_MODE_INCREMENTAL)
            os.remove(extra_path)  # keep the source identical for the next leg
            session.index_manager.clear_cache()
            entry = session.index_manager.get_index_log_entry(name)
            return sorted(entry.content.files), files

        with reference_build():
            expected, _ = run("rref")
        pfirst, _ = run("rpf")
        # refresh MERGE appends new files next to the v0 ones; compare
        # only the refreshed version's files (same basenames both legs)
        _assert_identical_files(expected, pfirst)


class TestPartitionedSortPermutation:
    @pytest.mark.parametrize(
        "n,nb,k",
        [(0, 8, 1), (1, 1, 1), (7, 3, 2), (50_000, 8, 1), (120_001, 200, 3)],
    )
    def test_matches_global_lexsort(self, n, nb, k):
        rng = np.random.default_rng(n + nb + k)
        reps = rng.integers(-(2**60), 2**60, size=(k, n), dtype=np.int64)
        buckets = rng.integers(0, nb, n).astype(np.int32)
        np.testing.assert_array_equal(
            partitioned_sort_permutation(reps, buckets, nb),
            sort_permutation(reps, buckets),
        )

    def test_heavy_ties_stability(self):
        rng = np.random.default_rng(9)
        n = 80_000
        reps = rng.integers(0, 2, size=(2, n), dtype=np.int64)
        buckets = rng.integers(0, 4, n).astype(np.int32)
        np.testing.assert_array_equal(
            partitioned_sort_permutation(reps, buckets, 4),
            sort_permutation(reps, buckets),
        )

    def test_single_and_empty_buckets(self):
        rng = np.random.default_rng(11)
        n = 10_000
        reps = rng.integers(-5, 5, size=(1, n), dtype=np.int64)
        # all rows in one bucket of many; most buckets empty
        buckets = np.full(n, 6, dtype=np.int32)
        np.testing.assert_array_equal(
            partitioned_sort_permutation(reps, buckets, 16),
            sort_permutation(reps, buckets),
        )


class TestPartitionByBucket:
    def test_twin_parity_and_offsets(self):
        rng = np.random.default_rng(3)
        for n, nb in [(0, 4), (1, 1), (999, 7), (200_000, 200)]:
            bids = rng.integers(0, nb, n).astype(np.int32)
            order, offsets = partition_by_bucket(bids, nb)
            np.testing.assert_array_equal(
                order, np.argsort(bids, kind="stable")
            )
            counts = np.bincount(bids, minlength=nb)
            np.testing.assert_array_equal(np.diff(offsets), counts)
            assert offsets[0] == 0 and offsets[-1] == n

    def test_numpy_twin_forced(self, monkeypatch):
        """With native disabled the twin must produce the identical
        partition."""
        from hyperspace_tpu import native

        rng = np.random.default_rng(4)
        bids = rng.integers(0, 8, 100_000).astype(np.int32)
        with_native = partition_by_bucket(bids, 8)
        monkeypatch.setattr(native, "partition_by_bucket_i32", lambda *a: None)
        without = partition_by_bucket(bids, 8)
        np.testing.assert_array_equal(with_native[0], without[0])
        np.testing.assert_array_equal(with_native[1], without[1])
