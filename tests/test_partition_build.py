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


def _tied_files(d, distinct_keys):
    rng = np.random.default_rng(21)
    d.mkdir()
    for i in range(4):
        n = 3000
        t = pa.table(
            {
                "k": pa.array(
                    rng.integers(0, distinct_keys, n), type=pa.int64()
                ),
                "s": pa.array(
                    [["aa", "bb", "cc"][v] for v in rng.integers(0, 3, n)]
                ),
                "v": pa.array(rng.normal(size=n)),
            }
        )
        pq.write_table(t, d / f"part-{i}.parquet")
    return str(d)


@pytest.fixture
def tied_parquet(tmp_path):
    """4 files whose keys collide heavily (3 distinct values per column)
    — long tie runs across files, the stability torture case — plus a
    string column and a float payload."""
    return _tied_files(tmp_path / "tied", 3)


@pytest.fixture
def spread_parquet(tmp_path):
    """The same, over 60 keys: ties as long as 200 rows, and a file in
    every one of a session's buckets."""
    return _tied_files(tmp_path / "spread", 60)


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


class _Sized:
    """What ``_bucket_writers`` reads of an arrow table."""

    def __init__(self, num_rows, bytes_per_row):
        self.num_rows, self.nbytes = num_rows, num_rows * bytes_per_row


def _ctx_with_budget(budget):
    from types import SimpleNamespace as NS

    return NS(session=NS(conf=NS(build_memory_budget=budget)))


class TestBucketWriters:
    """The pipelined tail's bucket files go through a pool of writers
    sized from what the build can see — cores, non-empty buckets, the
    memory budget — and come out as from one writer: same names, same
    order, same bytes."""

    @pytest.mark.parametrize(
        "cores,rows,buckets,budget,want",
        [
            (13, 16_000_000, 200, 0, 13),   # the benchmark's one-chip host
            (30, 16_000_000, 200, 0, 16),   # the core budget's cap
            (8, 16_000_000, 3, 0, 3),       # never more than files
            (13, 16_000, 200, 0, 13),       # a refresh's few-KB files too
            (13, 0, 200, 0, 1),             # no rows: no file, one idle writer
            # 4 buckets of 4M rows x 28 B = 112 MB each
            (13, 16_000_000, 4, 250_000_000, 2),
            (13, 16_000_000, 4, 223_999_999, 1),  # two do not fit
            (13, 16_000_000, 4, 1, 1),
        ],
    )
    def test_pool_size_rule(
        self, monkeypatch, cores, rows, buckets, budget, want
    ):
        from hyperspace_tpu import native

        monkeypatch.setattr(native, "_cores", lambda: cores)
        offsets = np.linspace(0, rows, buckets + 1).astype(np.int64)
        got = covering_build._bucket_writers(
            _ctx_with_budget(budget), _Sized(rows, 28), offsets
        )
        assert got == want

    def test_empty_buckets_take_no_writer(self, monkeypatch):
        from hyperspace_tpu import native

        monkeypatch.setattr(native, "_cores", lambda: 8)
        offsets = np.array([0, 0, 500_000, 500_000, 1_000_000], np.int64)
        assert covering_build._bucket_writers(
            _ctx_with_budget(0), _Sized(1_000_000, 28), offsets
        ) == 2

    @pytest.fixture
    def spied_tail(self, monkeypatch):
        """The list ``_write_bucketed_pipelined`` returned, as returned."""
        returned = []
        real = covering_build._write_bucketed_pipelined

        def spy(*a, **k):
            out = real(*a, **k)
            returned.append(list(out))
            return out

        monkeypatch.setattr(covering_build, "_write_bucketed_pipelined", spy)
        return returned

    @pytest.mark.parametrize("writers", [1, 8])
    @pytest.mark.parametrize("source", ["tied_parquet", "spread_parquet"])
    def test_files_do_not_depend_on_the_pool(
        self, session_factory, request, reference_build, spied_tail,
        monkeypatch, source, writers,
    ):
        from hyperspace_tpu import native
        from hyperspace_tpu.obs import trace

        src = request.getfixturevalue(source)
        session = session_factory(1)
        session.conf.set(C.INDEX_NUM_BUCKETS, 24)
        hs = Hyperspace(session)
        with reference_build():
            expected = _build(session, hs, src, f"ref{writers}")
        assert spied_tail == []  # the reference took the tail's place
        monkeypatch.setattr(native, "core_budget", lambda: writers)
        got = _build(session, hs, src, f"pool{writers}")
        _assert_identical_files(expected, got)
        assert len(got) == (3 if source == "tied_parquet" else 24)
        # as returned: one file a bucket, in ascending bucket id
        (returned,) = spied_tail
        ids = [pio.bucket_id_of_file(f) for f in returned]
        assert ids == sorted(set(ids)) and sorted(returned) == got
        root = trace.finished("action.CreateAction")[-1]
        (write,) = [sp for sp in root.spans if sp.name == "write"]
        assert write.attrs["writers"] == min(writers, len(got))

    def test_memory_budget_narrows_the_pool(
        self, session_factory, tmp_path, monkeypatch
    ):
        """Few, large buckets: a writer holds one gathered bucket, so
        under a budget that two of the largest do not fit there is one
        writer; with no budget, one a non-empty bucket."""
        from hyperspace_tpu import native
        from hyperspace_tpu.obs import trace

        rng = np.random.default_rng(8)
        n = 20_000
        d = tmp_path / "skewed"
        d.mkdir()
        k = np.where(rng.random(n) < 0.7, 1, rng.integers(2, 5, n))
        pq.write_table(
            pa.table({"k": pa.array(k, pa.int64()),
                      "s": pa.array(["x"] * n),
                      "v": pa.array(rng.normal(size=n))}),
            d / "part-0.parquet",
        )
        monkeypatch.setattr(native, "core_budget", lambda: 8)
        session = session_factory(1)
        hs = Hyperspace(session)

        def writers_of(name, budget):
            files = _build(session, hs, str(d), name, budget=budget)
            root = trace.finished("action.CreateAction")[-1]
            # the whole table fit the budget: no wave, no spill
            assert "waves" not in covering_build.last_build_telemetry
            (write,) = [sp for sp in root.spans if sp.name == "write"]
            return write.attrs["writers"], len(files)

        budget = covering_build.estimated_materialized_bytes(
            [str(d / "part-0.parquet")], "parquet"
        )
        assert writers_of("tight", budget) == (1, 4)
        assert writers_of("free", 0) == (4, 4)

    def test_crash_at_the_second_file_fails_the_build(
        self, session_factory, spread_parquet, monkeypatch
    ):
        """The first writer that raises surfaces from ``create_index``;
        the file it died on is never written, and the recovery path
        rolls the stranded create back."""
        import time

        from hyperspace_tpu import native
        from hyperspace_tpu.constants import States
        from hyperspace_tpu.metadata import recovery
        from hyperspace_tpu.testing import faults
        from hyperspace_tpu.testing.faults import SimulatedCrash

        session = session_factory(1)
        session.conf.set(C.RECOVERY_LEASE_MS, 40)
        session.conf.set(C.RECOVERY_ORPHAN_GRACE_MS, 0)
        hs = Hyperspace(session)
        monkeypatch.setattr(native, "core_budget", lambda: 4)
        faults.reset()
        faults.set_crash("mid_data_write", "raise;at=2")
        try:
            with pytest.raises(SimulatedCrash):
                _build(session, hs, spread_parquet, "dies")
            assert faults.stats() == {"crash.mid_data_write": 1}
        finally:
            faults.reset()
        log_mgr, _ = session.index_manager._managers("dies")
        assert log_mgr.get_latest_log().state == States.CREATING
        written = [
            f for _d, _s, fs in os.walk(log_mgr.index_path) for f in fs
            if pio.bucket_id_of_file(f) is not None
        ]
        assert 1 <= len(written) < session.conf.num_buckets
        time.sleep(0.1)  # past the dead writer's lease
        assert hs.recover("dies")["rolled_back"]
        assert log_mgr.get_latest_log().state == States.DOESNOTEXIST
        assert recovery.find_orphans(log_mgr.index_path) == []
        # the name is free again, and the build completes
        session.index_manager.clear_cache()
        files = _build(session, hs, spread_parquet, "dies")
        assert len(files) == session.conf.num_buckets
