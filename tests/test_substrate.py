"""Substrate tests: columnar batches, device hashing/sorting, mesh shuffle.

Distribution runs on the virtual 8-device CPU mesh from conftest — the
analogue of the reference testing Spark behavior on ``local[4]``
(``SparkInvolvedSuite.scala:31-47``).
"""

import numpy as np
import pyarrow as pa
import pytest

from hyperspace_tpu.io.columnar import Column, ColumnarBatch
from hyperspace_tpu.ops.hash import bucket_ids_np
from hyperspace_tpu.ops.sort import sort_permutation
from hyperspace_tpu.utils.hashing import murmur3_32_bytes, murmur3_64_bytes


class TestColumnar:
    def test_arrow_roundtrip_numeric_strings_nulls(self):
        t = pa.table(
            {
                "i": pa.array([1, 2, None, 4], type=pa.int64()),
                "f": pa.array([1.5, None, 3.0, 4.0], type=pa.float64()),
                "s": pa.array(["a", None, "a", "c"]),
                "b": pa.array([True, False, True, None]),
            }
        )
        rt = ColumnarBatch.from_arrow(t).to_arrow()
        assert rt.equals(t)

    def test_key_rep_stability_across_dictionaries(self):
        # Same values in different files (different dictionary orders) must
        # produce identical key reps — bucket layout depends on it.
        c1 = Column.from_arrow(pa.array(["x", "y", "z"]))
        c2 = Column.from_arrow(pa.array(["z", "x", "y", "x"]))
        r1 = {v: r for v, r in zip(["x", "y", "z"], c1.key_rep())}
        r2 = {v: r for v, r in zip(["z", "x", "y", "x"], c2.key_rep())}
        assert all(r1[k] == r2[k] for k in "xyz")

    def test_key_rep_floats_group_negzero_and_nan(self):
        c = Column.from_arrow(pa.array([0.0, -0.0, float("nan"), float("nan")]))
        r = c.key_rep()
        assert r[0] == r[1]
        assert r[2] == r[3]

    def test_concat_remaps_string_codes(self):
        a = Column.from_arrow(pa.array(["p", "q"]))
        b = Column.from_arrow(pa.array(["q", "r", None]))
        merged = Column.concat([a, b])
        assert merged.to_arrow().to_pylist() == ["p", "q", "q", "r", None]

    def test_nullable_int_key_rep_matches_non_nullable(self):
        # Nullable int columns must not decay to float64 — same value, same
        # key rep across files with and without nulls.
        a = Column.from_arrow(pa.array([1, 2, 3], type=pa.int64()))
        b = Column.from_arrow(pa.array([1, 2, None], type=pa.int64()))
        assert a.values.dtype == b.values.dtype == np.int64
        assert a.key_rep()[0] == b.key_rep()[0]

    def test_temporal_roundtrip_with_nulls(self):
        import datetime

        t = pa.table(
            {
                "d32": pa.array([datetime.date(2020, 1, 1), None], type=pa.date32()),
                "ts": pa.array(
                    [datetime.datetime(2020, 1, 1, 12), None],
                    type=pa.timestamp("us"),
                ),
            }
        )
        rt = ColumnarBatch.from_arrow(t).to_arrow()
        assert rt.equals(t)

    def test_dictionary_of_int_column(self):
        arr = pa.array([1, 2, 1, 3], type=pa.int64()).dictionary_encode()
        c = Column.from_arrow(arr)
        assert c.kind == "numeric"
        assert c.to_arrow().to_pylist() == [1, 2, 1, 3]

    def test_large_string_roundtrip(self):
        arr = pa.array(["a", "b"], type=pa.large_string())
        c = Column.from_arrow(arr)
        assert c.to_arrow().type == pa.large_string()

    def test_concat_empty_batches(self):
        t = pa.table({"k": pa.array([], type=pa.int64())})
        e = ColumnarBatch.from_arrow(t)
        out = ColumnarBatch.concat([e, e])
        assert out.num_rows == 0

    def test_take_and_filter(self):
        t = pa.table({"k": [10, 20, 30, 40], "s": ["a", "b", "c", "d"]})
        batch = ColumnarBatch.from_arrow(t)
        out = batch.filter(np.array([True, False, True, False])).to_arrow()
        assert out.column("k").to_pylist() == [10, 30]
        assert out.column("s").to_pylist() == ["a", "c"]


class TestHash:
    def test_murmur3_32_known_vectors(self):
        # Canonical murmur3-x86-32 test vectors.
        assert murmur3_32_bytes(b"", 0) == 0
        assert murmur3_32_bytes(b"", 1) == 0x514E28B7
        assert murmur3_32_bytes(b"hello", 0) == 0x248BFA47
        assert murmur3_32_bytes(b"hello, world", 0) == 0x149BBB7F

    def test_device_hash_matches_host_bytes_hash(self):
        # Device murmur3 over an int64 rep == host murmur3 over its 8 LE bytes.
        vals = np.array([0, 1, -1, 2**40 + 17, -(2**35)], dtype=np.int64)
        dev = bucket_ids_np(vals[None, :], 1 << 31, seed=7)
        host = np.array(
            [
                murmur3_32_bytes(int(v).to_bytes(8, "little", signed=True), 7)
                % (1 << 31)
                for v in vals
            ],
            dtype=np.int64,
        )
        np.testing.assert_array_equal(dev.astype(np.int64), host)

    def test_bucket_ids_deterministic_and_in_range(self):
        reps = np.random.default_rng(0).integers(-(2**62), 2**62, (2, 1000))
        b1 = bucket_ids_np(reps, 8)
        b2 = bucket_ids_np(reps, 8)
        np.testing.assert_array_equal(b1, b2)
        assert b1.min() >= 0 and b1.max() < 8
        # decently balanced
        counts = np.bincount(b1, minlength=8)
        assert counts.min() > 50

    def test_string_hash_64_stable(self):
        assert murmur3_64_bytes(b"abc") == murmur3_64_bytes(b"abc")
        assert murmur3_64_bytes(b"abc") != murmur3_64_bytes(b"abd")


class TestSort:
    def test_lexsort_primary_first(self):
        k0 = np.array([2, 1, 2, 1], dtype=np.int64)
        k1 = np.array([0, 3, 1, 2], dtype=np.int64)
        perm = sort_permutation(np.stack([k0, k1]))
        assert k0[perm].tolist() == [1, 1, 2, 2]
        assert k1[perm].tolist() == [2, 3, 0, 1]

    def test_bucket_grouping(self):
        bucket = np.array([3, 0, 3, 1], dtype=np.int32)
        keys = np.array([[9, 5, 1, 7]], dtype=np.int64)
        perm = sort_permutation(keys, bucket)
        assert bucket[perm].tolist() == [0, 1, 3, 3]
        assert keys[0][perm].tolist() == [5, 7, 1, 9]


class TestShuffle:
    def test_all_to_all_bucket_shuffle_preserves_rows(self):
        import jax

        from hyperspace_tpu.parallel import bucket_shuffle, default_mesh

        assert len(jax.devices()) == 8, "conftest must force 8 CPU devices"
        mesh = default_mesh()
        rng = np.random.default_rng(1)
        n, nb = 1003, 16  # deliberately not divisible by 8
        keys = rng.integers(0, 50, (1, n)).astype(np.int64)
        payload = rng.integers(0, 10**9, n).astype(np.int64)
        # pin the compact strategy: this test exercises the device
        # all_to_all itself (auto resolves a CPU mesh to the host-side
        # exchange; tests/test_exchange_strategies.py covers the matrix)
        buckets, (keys_out, payload_out) = bucket_shuffle(
            mesh, keys, [keys[0], payload], nb, strategy="compact"
        )
        # No rows lost or duplicated.
        assert len(buckets) == n
        np.testing.assert_array_equal(
            np.sort(payload_out), np.sort(payload)
        )
        # Bucket assignment matches the device hash.
        expected = bucket_ids_np(keys_out[None, :], nb)
        np.testing.assert_array_equal(buckets, expected)
        # Same key ⇒ same bucket (layout is a pure function of key values).
        same_key_same_bucket = {}
        for k, b in zip(keys_out, buckets):
            assert same_key_same_bucket.setdefault(int(k), int(b)) == int(b)

    def test_shuffle_key_payload_alignment(self):
        from hyperspace_tpu.parallel import bucket_shuffle, default_mesh

        mesh = default_mesh()
        n = 64
        keys = np.arange(n, dtype=np.int64)[None, :]
        payload = np.arange(n, dtype=np.int64) * 1000
        _, (k_out, p_out) = bucket_shuffle(
            mesh, keys, [keys[0], payload], 4, strategy="compact"
        )
        np.testing.assert_array_equal(k_out * 1000, p_out)


def test_bucket_ids_host_device_bit_exact():
    """The small-input host hash and the device kernel must agree
    bit-for-bit (build uses device at scale, pruning uses host)."""
    import numpy as np

    from hyperspace_tpu.ops import hash as H

    rng = np.random.default_rng(0)
    reps = rng.integers(-(2**62), 2**62, size=(2, 3000), dtype=np.int64)
    host = H.bucket_ids_np(reps, 16)
    assert len(host) == 3000
    # force the device path by lowering the threshold
    old = H._HOST_HASH_MAX_ROWS
    try:
        H._HOST_HASH_MAX_ROWS = 0
        dev = H.bucket_ids_np(reps, 16)
    finally:
        H._HOST_HASH_MAX_ROWS = old
    assert np.array_equal(host, dev)


