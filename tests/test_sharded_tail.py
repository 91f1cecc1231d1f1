"""Sharded build/serve tail — differential tests on the simulated
8-device CPU mesh.

The contract: on a mesh of more than one device each shard runs the
post-exchange build tail (partition-first sort + bucketed parquet write)
and the serve tail (prepare + merge-join) over only the buckets it owns
(``bucket % D``), concurrently with the other shards — and every output
is BIT-IDENTICAL to what a 1-device session (no exchange, the single
tail) builds and serves: same parquet bytes per bucket file, same joined
rows in the same order. A bucket lives wholly inside one shard, so the
per-bucket stable sort/merge cannot observe the sharding; these tests
make that argument mechanical.
"""

import hashlib
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import constants as C
from hyperspace_tpu.hyperspace import Hyperspace
from hyperspace_tpu.indexes.covering import CoveringIndexConfig


@pytest.fixture
def mesh8(session_factory):
    return session_factory(8)


@pytest.fixture
def mesh1(session_factory):
    """The single tail: one device, over the same index system path."""
    return session_factory(1)


@pytest.fixture
def mixed_parquet(tmp_path):
    """Heavily tied keys (stability torture) + a string column + a
    NULLABLE float payload (validity masks must survive the exchange and
    the per-shard tail)."""
    rng = np.random.default_rng(17)
    d = tmp_path / "mixed"
    d.mkdir()
    for i in range(4):
        n = 3000
        vals = rng.normal(size=n)
        t = pa.table(
            {
                "k": pa.array(rng.integers(0, 5, n), type=pa.int64()),
                "s": pa.array(
                    [["aa", "bb", "cc"][v] for v in rng.integers(0, 3, n)]
                ),
                "v": pa.array(
                    [None if j % 13 == 0 else vals[j] for j in range(n)],
                    type=pa.float64(),
                ),
            }
        )
        pq.write_table(t, d / f"part-{i}.parquet")
    return str(d)


def _sha(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _assert_identical_files(files_a, files_b):
    assert [os.path.basename(f) for f in files_a] == [
        os.path.basename(f) for f in files_b
    ]
    for fa, fb in zip(files_a, files_b):
        assert _sha(fa) == _sha(fb), f"parquet bytes differ: {fa} vs {fb}"


def _build(session, src, name, budget=0, lineage=False):
    session.conf.set(C.INDEX_BUILD_MEMORY_BUDGET, budget)
    session.conf.set(C.INDEX_LINEAGE_ENABLED, lineage)
    hs = Hyperspace(session)
    df = session.read.parquet(src)
    hs.create_index(df, CoveringIndexConfig(name, ["k"], ["s", "v"]))
    entry = session.index_manager.get_index_log_entry(name)
    return sorted(entry.content.files)


class TestShardedBuildDifferential:
    def test_in_memory_bit_identical(self, mesh8, mesh1, mixed_parquet):
        from hyperspace_tpu.indexes.covering_build import (
            last_build_breakdown,
        )

        on = _build(mesh8, mixed_parquet, "shon")
        # the sharded tail actually ran per shard
        assert last_build_breakdown.get("tail_shards", 0) > 1
        off = _build(mesh1, mixed_parquet, "shoff")
        assert "tail_shards" not in last_build_breakdown
        _assert_identical_files(on, off)
        on2 = _build(mesh8, mixed_parquet, "shon2")
        _assert_identical_files(on, on2)

    def test_streaming_waves_bit_identical(self, mesh8, mesh1, mixed_parquet):
        """Budget-capped builds wave/spill/merge; the per-wave sharded
        sort and the per-shard merge fan-out must land the same bytes."""
        from hyperspace_tpu.indexes.covering_build import (
            per_file_materialized_bytes,
        )

        first = sorted(os.listdir(mixed_parquet))[0]
        per_file = per_file_materialized_bytes(
            [os.path.join(mixed_parquet, first)], "parquet"
        )[0]
        budget = int(per_file * 2.5)
        on = _build(mesh8, mixed_parquet, "ston", budget=budget)
        off = _build(mesh1, mixed_parquet, "stoff", budget=budget)
        _assert_identical_files(on, off)

    def test_refresh_incremental_bit_identical(self, mesh8, mesh1, mixed_parquet):
        def run(session, name):
            _build(session, mixed_parquet, name, lineage=True)
            hs = Hyperspace(session)
            rng = np.random.default_rng(5)
            extra = pa.table(
                {
                    "k": pa.array(
                        rng.integers(0, 5, 500), type=pa.int64()
                    ),
                    "s": pa.array(["dd"] * 500),
                    "v": pa.array(rng.normal(size=500)),
                }
            )
            extra_path = os.path.join(
                mixed_parquet, f"extra-{name}.parquet"
            )
            pq.write_table(extra, extra_path)
            session.index_manager.clear_cache()
            hs.refresh_index(name, C.REFRESH_MODE_INCREMENTAL)
            os.remove(extra_path)  # identical source for the next leg
            session.index_manager.clear_cache()
            entry = session.index_manager.get_index_log_entry(name)
            return sorted(entry.content.files)

        on = run(mesh8, "rfon")
        off = run(mesh1, "rfoff")
        _assert_identical_files(on, off)

    def test_cross_mesh_serve(self, session_factory, mixed_parquet):
        """An index built by the sharded tail serves identically from a
        single-device session (layout is mesh-independent)."""
        _build(session_factory(8), mixed_parquet, "xms")
        server = session_factory(1)
        df = server.read.parquet(mixed_parquet)
        q = lambda d: d.filter(d["k"] == 2).select("k", "s", "v")
        server.disable_hyperspace()
        base = q(df).collect()
        server.enable_hyperspace()
        assert "Hyperspace(Type: CI" in q(df).explain()
        got = q(df).collect()
        key = lambda t: t.sort_by(
            [(c, "ascending") for c in t.column_names]
        )
        assert key(got).equals(key(base))
        assert got.num_rows > 0


@pytest.fixture
def join_data(tmp_path):
    rng = np.random.default_rng(23)
    fact = tmp_path / "fact"
    dim = tmp_path / "dim"
    fact.mkdir()
    dim.mkdir()
    for i in range(3):
        n = 4000
        t = pa.table(
            {
                "k": pa.array(rng.integers(0, 100, n), type=pa.int64()),
                "p": pa.array(rng.normal(size=n)),
            }
        )
        pq.write_table(t, fact / f"f{i}.parquet")
    pq.write_table(
        pa.table(
            {
                "j": pa.array(np.arange(100), type=pa.int64()),
                "w": pa.array(rng.normal(size=100)),
            }
        ),
        dim / "d.parquet",
    )
    return str(fact), str(dim)


class TestShardedServeDifferential:
    def _indexed(self, session, fact, dim):
        hs = Hyperspace(session)
        f = session.read.parquet(fact)
        d = session.read.parquet(dim)
        hs.create_index(f, CoveringIndexConfig("fidx", ["k"], ["p"]))
        hs.create_index(d, CoveringIndexConfig("didx", ["j"], ["w"]))
        return f, d

    @staticmethod
    def _q(f, d):
        return f.join(d, on=f["k"] == d["j"]).select("k", "p", "w")

    def _served(self, session, fact, dim):
        """The join, index-served by ``session`` from the indexes on the
        shared system path."""
        f, d = session.read.parquet(fact), session.read.parquet(dim)
        session.enable_hyperspace()
        assert self._q(f, d).explain().count("Hyperspace(Type: CI") == 2
        return self._q(f, d).collect()

    def test_join_bit_identical(self, mesh8, mesh1, join_data):
        f, d = self._indexed(mesh8, *join_data)
        on = self._served(mesh8, *join_data)
        off = self._served(mesh1, *join_data)
        # bit-identical: same rows in the same order, not just same set
        assert on.equals(off)
        mesh8.disable_hyperspace()
        base = self._q(f, d).collect()
        key = lambda t: t.sort_by(
            [(c, "ascending") for c in t.column_names]
        )
        assert key(on).equals(key(base))
        assert on.num_rows > 0

    def test_hybrid_delta_bit_identical(self, mesh8, mesh1, join_data):
        fact, dim = join_data
        f, d = self._indexed(mesh8, fact, dim)
        pq.write_table(
            pa.table(
                {
                    # one key beyond the dim range: delta-only bucket rows
                    "k": pa.array([0, 1, 2, 300], type=pa.int64()),
                    "p": pa.array([1.0, 2.0, 3.0, 4.0]),
                }
            ),
            os.path.join(fact, "extra.parquet"),
        )
        for session in (mesh8, mesh1):
            session.conf.set(C.INDEX_HYBRID_SCAN_ENABLED, True)
            session.index_manager.clear_cache()
        f2 = mesh8.read.parquet(fact)
        on = self._served(mesh8, fact, dim)
        off = self._served(mesh1, fact, dim)
        assert on.equals(off)
        mesh8.disable_hyperspace()
        base = self._q(f2, d).collect()
        key = lambda t: t.sort_by(
            [(c, "ascending") for c in t.column_names]
        )
        assert key(on).equals(key(base))


class TestTailChoice:
    @pytest.mark.parametrize(
        "devices, keys, sharded",
        [
            (1, "spread", False),  # no exchange ran: no shard offsets
            (8, "one_bucket", False),  # an exchange, ONE occupied shard
            (8, "spread", True),  # more than one shard holds rows
        ],
    )
    def test_sharded_iff_more_than_one_occupied_shard(
        self, devices, keys, sharded, session_factory, tmp_path, monkeypatch
    ):
        """``bucketize`` and ``write_bucketed`` take the sharded tail
        exactly when the exchange returned offsets with more than one
        occupied shard: nothing else is asked."""
        from hyperspace_tpu.indexes import covering_build
        from hyperspace_tpu.indexes.context import IndexerContext
        from hyperspace_tpu.io.columnar import ColumnarBatch
        from hyperspace_tpu.metadata.entry import FileIdTracker
        from hyperspace_tpu.ops import sort as sort_ops

        n = 4000
        rng = np.random.default_rng(devices + len(keys))
        k = np.full(n, 7) if keys == "one_bucket" else rng.integers(0, 500, n)
        batch = ColumnarBatch.from_arrow(
            pa.table({"k": pa.array(k, type=pa.int64()), "v": pa.array(rng.normal(size=n))})
        )
        ctx = IndexerContext(
            session_factory(devices), FileIdTracker(), str(tmp_path / "v__=0")
        )
        seen = {"offsets": [], "sharded_sort": 0, "sharded_write": 0}
        real_shuffle = covering_build._hash_shuffle

        def shuffle(*args):
            out = real_shuffle(*args)
            seen["offsets"].append(out[3])
            return out

        def counting(key, real):
            def call(*args, **kw):
                seen[key] += 1
                return real(*args, **kw)

            return call

        monkeypatch.setattr(covering_build, "_hash_shuffle", shuffle)
        monkeypatch.setattr(
            sort_ops, "sharded_sort_permutation",
            counting("sharded_sort", sort_ops.sharded_sort_permutation),
        )
        monkeypatch.setattr(
            covering_build, "_write_bucketed_sharded",
            counting("sharded_write", covering_build._write_bucketed_sharded),
        )
        buckets, out = covering_build.bucketize(ctx, batch, ["k"], 8)
        written = covering_build.write_bucketed(ctx, batch, ["k"], 8)
        assert out.num_rows == n and len(buckets) == n
        assert sum(pq.read_metadata(f).num_rows for f in written) == n
        for offs in seen["offsets"]:
            occupied = 0 if offs is None else np.count_nonzero(np.diff(offs))
            assert (offs is None) == (devices == 1)
            assert (occupied > 1) == sharded
        assert seen["sharded_sort"] == seen["sharded_write"] == int(sharded)


class TestShardedSortPermutation:
    @pytest.mark.parametrize("n,nb,k", [(0, 8, 1), (9, 3, 2), (60_000, 8, 1)])
    def test_per_bucket_equals_global(self, n, nb, k):
        """Shard-major output differs in GLOBAL order from the global
        (bucket, keys) sort by design; restricted to any bucket the two
        are identical — the only order the bucketed writers observe."""
        from hyperspace_tpu.ops.sort import (
            sharded_sort_permutation,
            sort_permutation,
        )

        rng = np.random.default_rng(n + nb + k)
        D = 4
        reps = rng.integers(-(2**60), 2**60, size=(k, n), dtype=np.int64)
        # shard-major layout with bucket % D ownership, as post-exchange
        owner = rng.integers(0, D, n)
        order = np.argsort(owner, kind="stable")
        reps = reps[:, order]
        owner = owner[order]
        buckets = np.empty(n, dtype=np.int32)
        for s in range(D):
            m = owner == s
            buckets[m] = (
                rng.integers(0, max(nb // D, 1), int(m.sum())) * D + s
            ) % nb
        shard_offs = np.concatenate(
            [[0], np.cumsum(np.bincount(owner, minlength=D))]
        ).astype(np.int64)
        perm = sharded_sort_permutation(reps, buckets, nb, shard_offs)
        ref = sort_permutation(reps, buckets)
        for b in np.unique(buckets):
            np.testing.assert_array_equal(
                perm[buckets[perm] == b], ref[buckets[ref] == b]
            )


class TestSkewTelemetry:
    def test_skew_recorded_and_warned(self, mesh8, tmp_path, caplog):
        """All rows hashing into one bucket → one hot (shard, peer) slot;
        telemetry must record the ratio and the warning must fire."""
        import logging

        d = tmp_path / "skew"
        d.mkdir()
        # enough rows that every shard's send to the one hot peer clears
        # the warn floor (BUILD_SHUFFLE_SKEW_WARN_MIN_ROWS)
        n = 20000
        t = pa.table(
            {
                "k": pa.array(np.full(n, 7), type=pa.int64()),
                "s": pa.array(["x"] * n),
                "v": pa.array(np.ones(n)),
            }
        )
        pq.write_table(t, d / "p0.parquet")
        pq.write_table(t, d / "p1.parquet")
        with caplog.at_level(logging.WARNING, "hyperspace_tpu.shuffle"):
            _build(mesh8, str(d), "skidx")
        from hyperspace_tpu.indexes.covering_build import (
            last_build_telemetry,
        )

        assert last_build_telemetry["shuffle_skew_ratio"] >= (
            C.BUILD_SHUFFLE_SKEW_WARN_RATIO
        )
        assert any("shuffle skew" in r.message for r in caplog.records)

    def test_balanced_no_warning(self, mesh8, mixed_parquet, caplog):
        import logging

        with caplog.at_level(logging.WARNING, "hyperspace_tpu.shuffle"):
            # 5 keys over 8 buckets is mildly skewed but telemetry must
            # exist either way
            _build(mesh8, mixed_parquet, "balidx")
        from hyperspace_tpu.indexes.covering_build import (
            last_build_telemetry,
        )

        assert "shuffle_skew_ratio" in last_build_telemetry
        assert last_build_telemetry["shuffle_devices"] == 8.0


class TestNativeTmpSweep:
    def test_stale_tmp_and_superseded_swept(self, tmp_path):
        """Week-old compile scratch files are reclaimed on cleanup —
        including the CURRENT revision's own orphans — while live
        artifacts and fresh tmps (possibly another process mid-compile)
        survive."""
        import time

        from hyperspace_tpu.native import _SUPERSEDED_TTL_S, _cleanup_superseded

        keep = tmp_path / "_hs_native_aaaa.so"
        stale = time.time() - _SUPERSEDED_TTL_S - 60
        files = {
            "_hs_native_aaaa.so": None,  # current revision: keep
            "_hs_native_aaaa.so.failed": None,  # current marker: keep
            "_hs_native_aaaa.so.tmp.123": stale,  # own orphan: sweep
            "_hs_native_bbbb.so.tmp.9": stale,  # foreign orphan: sweep
            "_hs_native_bbbb.so": stale,  # superseded revision: sweep
            "_hs_native_cccc.so": None,  # fresh foreign .so: keep
            "_hs_native_cccc.so.tmp.7": None,  # mid-compile tmp: keep
        }
        for name, mtime in files.items():
            p = tmp_path / name
            p.write_bytes(b"x")
            if mtime is not None:
                os.utime(p, (mtime, mtime))
        _cleanup_superseded(str(keep))
        left = sorted(os.listdir(tmp_path))
        assert left == [
            "_hs_native_aaaa.so",
            "_hs_native_aaaa.so.failed",
            "_hs_native_cccc.so",
            "_hs_native_cccc.so.tmp.7",
        ]


class TestShardMapBodyLint:
    def test_parallel_shard_map_bodies_hs3_clean(self):
        """HS3xx (hot-path purity) over the mesh/shuffle modules: the
        shard_map program bodies the sharded tail feeds must stay
        device-pure (no host numpy / syncs under trace)."""
        import hyperspace_tpu
        from hyperspace_tpu.analysis import run_analysis

        pkg = os.path.dirname(os.path.abspath(hyperspace_tpu.__file__))
        findings = [
            f
            for f in run_analysis(pkg)
            if f.rule.startswith("HS3") and not f.suppressed
        ]
        assert findings == [], findings
