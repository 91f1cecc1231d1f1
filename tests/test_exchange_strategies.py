"""Exchange-strategy plane (``hyperspace.build.exchange.strategy``) —
the differential matrix.

The contract: every strategy (``host`` pure-RAM reorder, ``compact``
host-packed exact-extent all_to_all, ``twostage`` DCN/ICI decomposition
with per-peer round caps) produces BIT-IDENTICAL output — the rows in
the numpy lexsort by (owner, bucket, row) that ``_reference`` computes
with nothing of the exchange: same bucket ids, same payload rows in the
same order, same ``with_shard_offsets`` extents — across mesh sizes,
payload types (ints, strings via dictionary codes, validity masks,
floats with NaNs), skews (uniform and one hot bucket) and the
empty-shard edge (a peer that owns zero rows). Session-level legs check
the parquet bytes of whole builds, including streaming waves.
"""

import hashlib
import logging
import os
import types

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import jax

from hyperspace_tpu import constants as C
from hyperspace_tpu.hyperspace import Hyperspace
from hyperspace_tpu.indexes.covering import CoveringIndexConfig
from hyperspace_tpu.parallel import shuffle as sh


def _mesh(n_devices):
    return jax.sharding.Mesh(
        np.array(jax.devices()[:n_devices]), (sh.SHARD_AXIS,)
    )


def _payload_matrix(rng, n):
    """One array per payload kind the build decomposes batches into:
    int64 key reps/values, float64 with NaNs, int32 dictionary codes
    (strings), bool validity masks."""
    f = rng.normal(size=n)
    f[rng.integers(0, 2, n).astype(bool)] = np.nan
    return [
        rng.integers(-(2**60), 2**60, n).astype(np.int64),
        f,
        rng.integers(0, 3, n).astype(np.int32),
        rng.integers(0, 2, n).astype(bool),
    ]


def _keys(rng, n, skew):
    if skew == "hot":  # every row hashes into ONE bucket
        return np.full((1, n), 7, dtype=np.int64)
    return rng.integers(0, 97, (2, n)).astype(np.int64)


def _strategies_for(D):
    out = [sh.STRATEGY_HOST, sh.STRATEGY_COMPACT]
    if D > 1:
        out.append(sh.STRATEGY_TWOSTAGE)
    return out


def _reference(keys, payloads, nb, D):
    """What ``bucket_shuffle(..., with_shard_offsets=True)`` must return,
    by a numpy lexsort: rows by (owner = bucket % D, bucket, row)."""
    from hyperspace_tpu.ops.hash import bucket_ids_host

    ids = bucket_ids_host(keys, nb, 42)
    perm = np.lexsort((np.arange(len(ids)), ids, ids % D))
    offsets = np.concatenate([[0], np.cumsum(np.bincount(ids % D, minlength=D))])
    return ids[perm], [p[perm] for p in payloads], offsets


def _fake_mesh(platform):
    """As much of a mesh as ``resolve_strategy`` reads."""
    dev = types.SimpleNamespace(platform=platform)
    return types.SimpleNamespace(devices=np.array([dev, dev], dtype=object))


class TestStrategyDifferential:
    @pytest.mark.parametrize("D", [1, 2, 8])
    @pytest.mark.parametrize("skew", ["uniform", "hot"])
    def test_bit_identical_to_reference(self, D, skew):
        mesh = _mesh(D)
        rng = np.random.default_rng(D * 31 + len(skew))
        n, nb = 3001, 16
        keys = _keys(rng, n, skew)
        payloads = _payload_matrix(rng, n)
        ref = _reference(keys, payloads, nb, D)
        for strat in _strategies_for(D):
            got = sh.bucket_shuffle(
                mesh, keys, payloads, nb, with_shard_offsets=True,
                strategy=strat, twostage_hosts=2,
            )
            np.testing.assert_array_equal(got[0], ref[0], err_msg=strat)
            np.testing.assert_array_equal(got[2], ref[2], err_msg=strat)
            assert len(got[1]) == len(ref[1])
            for a, b in zip(got[1], ref[1]):
                assert a.dtype == b.dtype, strat
                np.testing.assert_array_equal(a, b, err_msg=strat)
            assert sh.last_shuffle_stats["strategy"] == strat

    def test_empty_peer_extents(self):
        """num_buckets < D: some shards own no buckets and must report
        empty ``with_shard_offsets`` extents in every strategy."""
        mesh = _mesh(8)
        rng = np.random.default_rng(3)
        n, nb = 999, 3  # owners only 0..2 of 8 shards
        keys = rng.integers(0, 50, (1, n)).astype(np.int64)
        payloads = [np.arange(n, dtype=np.int64)]
        ref = _reference(keys, payloads, nb, 8)
        assert (np.diff(ref[2])[nb:] == 0).all()
        for strat in _strategies_for(8):
            got = sh.bucket_shuffle(
                mesh, keys, payloads, nb, with_shard_offsets=True,
                strategy=strat, twostage_hosts=4,
            )
            np.testing.assert_array_equal(got[0], ref[0], err_msg=strat)
            np.testing.assert_array_equal(got[2], ref[2], err_msg=strat)
            np.testing.assert_array_equal(got[1][0], ref[1][0], err_msg=strat)

    @pytest.mark.parametrize("hosts", [2, 4, 8])
    def test_twostage_host_factorizations(self, hosts):
        """Every (H, L) carve of the 8-device mesh lands the same rows."""
        mesh = _mesh(8)
        rng = np.random.default_rng(hosts)
        n, nb = 2048, 16
        keys = rng.integers(0, 200, (1, n)).astype(np.int64)
        payloads = [keys[0], rng.normal(size=n)]
        ref = _reference(keys, payloads, nb, 8)
        got = sh.bucket_shuffle(
            mesh, keys, payloads, nb, with_shard_offsets=True,
            strategy=sh.STRATEGY_TWOSTAGE, twostage_hosts=hosts,
        )
        np.testing.assert_array_equal(got[0], ref[0])
        np.testing.assert_array_equal(got[2], ref[2])
        for a, b in zip(got[1], ref[1]):
            np.testing.assert_array_equal(a, b)
        assert sh.last_shuffle_stats["hosts"] == float(hosts)

    @pytest.mark.parametrize(
        "strategy", [sh.STRATEGY_COMPACT, sh.STRATEGY_TWOSTAGE]
    )
    def test_floats_cross_the_device_as_integers(self, strategy, monkeypatch):
        """No float dtype reaches a device exchange program, and float
        payloads come back bit for bit — including the values a TPU
        mangles when it is handed them as float64 (it holds no IEEE
        double: 1e300 -> inf, -0.0 -> 0.0, a NaN's payload bits lost)."""
        seen = []
        for prog in ("_compact_program", "_twostage_program"):
            real = getattr(sh, prog)

            def spy(*args, _real=real, **kw):
                seen.extend(
                    np.dtype(leaf.dtype)
                    for leaf in jax.tree_util.tree_leaves(args)
                    if hasattr(leaf, "dtype")
                )
                return _real(*args, **kw)

            monkeypatch.setattr(sh, prog, spy)
        rng = np.random.default_rng(5)
        n = 4096
        f64 = rng.normal(size=n)
        f64[:6] = [1e300, -1e300, 5e-324, -0.0, 3.0000000000000004, np.inf]
        f64[6:8] = np.array(
            [0x7FF8000000000123, 0xFFF0000000000001], dtype=np.uint64
        ).view(np.float64)  # NaNs with payload bits
        f32 = rng.normal(size=n).astype(np.float32)
        keys = rng.integers(0, 97, (1, n)).astype(np.int64)
        ids, (got64, got32) = sh.bucket_shuffle(
            _mesh(8), keys, [f64, f32], 16, strategy=strategy, twostage_hosts=2
        )
        assert seen and not [d for d in seen if d.kind == "f"], seen
        assert got64.dtype == np.float64 and got32.dtype == np.float32
        _ids, (ref64, ref32) = sh.bucket_shuffle(
            _mesh(8), keys, [f64, f32], 16, strategy=sh.STRATEGY_HOST
        )
        np.testing.assert_array_equal(got64.view(np.uint64), ref64.view(np.uint64))
        np.testing.assert_array_equal(got32.view(np.uint32), ref32.view(np.uint32))
        assert sorted(got64.view(np.uint64)) == sorted(f64.view(np.uint64))

    def test_canonical_order_is_flat_order(self):
        """The host-side permutation equals the naive (owner, bucket,
        row) lexsort — the invariant every strategy rides."""
        rng = np.random.default_rng(11)
        n, nb, D = 5000, 13, 8
        ids = rng.integers(0, nb, n).astype(np.int32)
        perm, offs = sh.canonical_order(ids, nb, D)
        ref = np.lexsort((np.arange(n), ids, ids % D))
        np.testing.assert_array_equal(perm, ref)
        np.testing.assert_array_equal(
            np.diff(offs), np.bincount(ids % D, minlength=D)
        )

    @pytest.mark.parametrize(
        "configured, platform, processes, expect",
        [
            # auto: a function of the process count and the platform only
            ("auto", "cpu", 1, sh.STRATEGY_HOST),
            ("auto", "tpu", 1, sh.STRATEGY_COMPACT),
            ("auto", "cpu", 2, sh.STRATEGY_TWOSTAGE),
            ("auto", "tpu", 2, sh.STRATEGY_TWOSTAGE),
            # forced: taken as given, but a multi-process job has one way
            ("TwoStage", "cpu", 1, sh.STRATEGY_TWOSTAGE),
            ("host", "tpu", 1, sh.STRATEGY_HOST),
            ("compact", "cpu", 2, sh.STRATEGY_TWOSTAGE),
            ("bogus", "cpu", 1, ValueError),
            ("flat", "tpu", 1, ValueError),
        ],
    )
    def test_resolve(self, configured, platform, processes, expect, monkeypatch):
        monkeypatch.setattr(jax, "process_count", lambda: processes)
        mesh = _fake_mesh(platform)
        if expect is ValueError:
            with pytest.raises(ValueError, match="unknown exchange strategy"):
                sh.resolve_strategy(configured, mesh)
        else:
            assert sh.resolve_strategy(configured, mesh) == expect

    def test_resolve_auto_on_the_cpu_mesh(self):
        assert sh.resolve_strategy("auto", _mesh(8)) == sh.STRATEGY_HOST


# ---------------------------------------------------------------------------
# compact: one (source, owner, bucket) counting partition drives the host
# ---------------------------------------------------------------------------

_LAYOUT_BUCKETS = 12
_LAYOUT_SHAPES = [
    "uniform", "hot", "empty_owner", "ragged", "tiny",
    "one_row_a_shard", "under_a_row_a_slot",
]


def _layout_keys(shape, D, rng):
    """[1, n] key reps for one pack-layout case (12 buckets)."""
    from hyperspace_tpu.ops.hash import bucket_ids_host

    if shape == "hot":  # every row in ONE bucket
        return np.full((1, 2048), 7, dtype=np.int64)
    if shape == "tiny":  # n < D * buckets: most runs are empty
        return rng.integers(0, 10**6, (1, D * _LAYOUT_BUCKETS - 5)).astype(np.int64)
    if shape == "one_row_a_shard":  # n = D: the least ``_hash_shuffle`` sends
        return rng.integers(0, 10**6, (1, D)).astype(np.int64)
    if shape == "under_a_row_a_slot":  # n < D * D: some slots stay empty
        return rng.integers(0, 10**6, (1, D * D - 1)).astype(np.int64)
    n = 2003 if shape == "ragged" else 2048  # 2003: no multiple of D
    keys = rng.integers(0, 10**6, (1, 4 * n)).astype(np.int64)
    if shape == "empty_owner":  # owner 1 receives no row
        ids = bucket_ids_host(keys, _LAYOUT_BUCKETS, 42)
        keys = keys[:, ids % D != 1]
    return np.ascontiguousarray(keys[:, :n])


class TestCompactOnePartition:
    @pytest.mark.parametrize("D", [2, 4, 8])
    @pytest.mark.parametrize("shape", _LAYOUT_SHAPES)
    def test_pack_layout(self, D, shape, monkeypatch):
        """Every send slot holds exactly the rows of its (source, owner)
        pair, grouped by ascending bucket, in original order inside a
        bucket, zeros behind — read off the buffers the forced
        ``compact`` exchange hands to the devices."""
        from hyperspace_tpu.ops.hash import bucket_ids_host

        rng = np.random.default_rng(D * 7 + len(shape))
        keys = _layout_keys(shape, D, rng)
        n, nb = keys.shape[1], _LAYOUT_BUCKETS
        row_id = np.arange(1, n + 1, dtype=np.int64)  # never 0: padding is
        codes = (row_id % 251 + 1).astype(np.int32)
        sent = []
        put = sh.put_sharded
        monkeypatch.setattr(
            sh, "put_sharded", lambda m, a, *r: sent.append(a) or put(m, a, *r)
        )
        got = sh.bucket_shuffle(
            _mesh(D), keys, [row_id, codes], nb, with_shard_offsets=True,
            strategy=sh.STRATEGY_COMPACT,
        )
        assert len(sent) == 2
        ids = bucket_ids_host(keys, nb, 42)
        n_local = -(-n // D)
        src = np.arange(n) // n_local
        owner = ids % D
        if shape == "empty_owner":
            assert not (owner == 1).any()
        cap = sent[0].shape[1]
        most = max(
            int(((src == s) & (owner == o)).sum())
            for s in range(D) for o in range(D)
        )
        assert cap == sh._shape_cap(most)
        for buf, payload in zip(sent, (row_id, codes)):
            assert buf.shape == (D * D, cap) and buf.dtype == payload.dtype
            for s in range(D):
                for o in range(D):
                    rows = np.nonzero((src == s) & (owner == o))[0]
                    rows = rows[np.argsort(ids[rows], kind="stable")]
                    slot = buf[s * D + o]
                    np.testing.assert_array_equal(slot[: len(rows)], payload[rows])
                    assert not slot[len(rows):].any()
        ref = sh.bucket_shuffle(
            _mesh(D), keys, [row_id, codes], nb, with_shard_offsets=True,
            strategy=sh.STRATEGY_HOST,
        )
        np.testing.assert_array_equal(got[0], ref[0])
        np.testing.assert_array_equal(got[2], ref[2])
        for a, b in zip(got[1], ref[1]):
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("D", [2, 4, 8])
    @pytest.mark.parametrize("gather", ["native", "numpy"])
    def test_mixed_widths_equal_host_and_reference(self, D, gather, monkeypatch):
        """int64, float64 (crossing as int64), int32 codes and uint8
        validity under two key columns: ``compact`` equals ``host`` and
        the numpy lexsort element for element whichever gather packs it,
        and the account says which did."""
        from hyperspace_tpu import native
        from hyperspace_tpu.io import columnar

        monkeypatch.setattr(
            columnar, "_NATIVE_GATHER_MIN_ROWS", 1 if gather == "native" else 1 << 40
        )
        rng = np.random.default_rng(D + len(gather))
        n, nb = 3001, 16
        keys = rng.integers(0, 97, (2, n)).astype(np.int64)
        f = rng.normal(size=n)
        f[::7] = np.nan
        payloads = [
            rng.integers(-(2**60), 2**60, n).astype(np.int64),
            f,
            rng.integers(0, 3, n).astype(np.int32),
            rng.integers(0, 2, n).astype(np.uint8),
        ]
        mesh = _mesh(D)
        plan = sh._compact_plan(keys, nb, 42, D)
        _, gathers_native = sh._compact_pack(
            plan, [p.view("i8") if p.dtype.kind == "f" else p for p in payloads]
        )
        lib = native.load(wait=True)
        assert gathers_native == (2 if gather == "native" and lib else 0)
        got = sh.bucket_shuffle(
            mesh, keys, payloads, nb, with_shard_offsets=True,
            strategy=sh.STRATEGY_COMPACT,
        )
        refs = {
            sh.STRATEGY_HOST: sh.bucket_shuffle(
                mesh, keys, payloads, nb, with_shard_offsets=True,
                strategy=sh.STRATEGY_HOST,
            ),
            "lexsort": _reference(keys, payloads, nb, D),
        }
        for other, ref in refs.items():
            np.testing.assert_array_equal(got[0], ref[0], err_msg=other)
            np.testing.assert_array_equal(got[2], ref[2], err_msg=other)
            for a, b in zip(got[1], ref[1]):
                assert a.dtype == b.dtype, other
                np.testing.assert_array_equal(
                    a.view(f"u{a.dtype.itemsize}"), b.view(f"u{b.dtype.itemsize}"),
                    err_msg=other,
                )

    @pytest.mark.parametrize("D", [2, 4, 8])
    def test_span_attrs(self, D):
        """``pack`` counts its gathers, ``unpack`` its runs, and the
        three host spans stay children of ``hash_shuffle``."""
        from hyperspace_tpu.obs import trace

        rng = np.random.default_rng(D)
        n, nb = 3001, 16
        keys = rng.integers(0, 97, (1, n)).astype(np.int64)
        payloads = [keys[0], rng.integers(0, 3, n).astype(np.int32),
                    rng.integers(0, 2, n).astype(bool)]
        root = trace.root("action.Test", always=True)
        with trace.activate(root):
            with trace.span("hash_shuffle") as parent:
                sh.bucket_shuffle(
                    _mesh(D), keys, payloads, nb, strategy=sh.STRATEGY_COMPACT
                )
        root.finish()
        by_name = {s.name: s for s in root.spans}
        for name in ("exchange_plan", "pack", "exchange", "unpack"):
            assert by_name[name].parent_id == parent.span_id, name
        pack, unpack = by_name["pack"].attrs, by_name["unpack"].attrs
        assert pack["gathers_native"] + pack["gathers_numpy"] == len(payloads)
        assert pack["gathers_numpy"] >= 2  # the codes and the validity
        assert 0 < unpack["runs"] <= D * nb
        assert by_name["exchange_plan"].attrs["strategy"] == "compact"


# ---------------------------------------------------------------------------
# the fetch: every copy started before any read, shards read in place
# ---------------------------------------------------------------------------

class _ShardData:
    """Stands where a shard's single-device array stands: ``np.asarray``
    of it hands out the buffer itself, and says so in the log."""

    def __init__(self, log, tag, buf):
        self._log, self._tag, self.buf = log, tag, buf

    def __array__(self, dtype=None, copy=None):
        self._log.append(("read",) + self._tag)
        return self.buf


class _Output:
    """As much of a sharded ``jax.Array`` as the fetch touches: rows
    split over ``D`` shards, handed out in no particular order."""

    def __init__(self, log, k, full, D):
        self._log, self._k, self.shape = log, k, full.shape
        rows = full.shape[0] // D
        self.addressable_shards = [
            types.SimpleNamespace(
                index=(slice(d * rows, (d + 1) * rows), slice(None)),
                data=_ShardData(log, (k, d), full[d * rows : (d + 1) * rows]),
            )
            for d in reversed(range(D))
        ]

    def copy_to_host_async(self):
        self._log.append(("start", self._k))

    def __array__(self, dtype=None, copy=None):
        raise AssertionError("a whole sharded output was converted")


class TestFetchShards:
    @pytest.mark.parametrize("D", [1, 2, 4])
    @pytest.mark.parametrize("dtype", [np.int64, np.int32, np.uint8, bool])
    def test_every_copy_starts_before_any_read_and_nothing_is_assembled(
        self, D, dtype
    ):
        rng = np.random.default_rng(D)
        log = []
        fulls = [
            rng.integers(0, 2, (D * D, 24)).astype(dtype),
            rng.integers(-(2**60), 2**60, (D * D, 24)).astype(np.int64),
            rng.integers(0, 100, (D * D, 24)).astype(dtype),
        ]
        out = [_Output(log, k, full, D) for k, full in enumerate(fulls)]
        host, account = sh._fetch_shards(out)
        # every output's copy is started, then every shard is read: in
        # output order, in index order
        assert log[: len(out)] == [("start", k) for k in range(len(out))]
        assert log[len(out):] == [
            ("read", k, d) for k in range(len(out)) for d in range(D)
        ]
        assert account == {
            "shards": len(out) * D, "started": len(out) * D, "assembled_bytes": 0,
        }
        # the arrays handed on ARE the shards' buffers, flat: nothing of
        # an output's full shape was allocated, no byte copied again
        for full, shards in zip(fulls, host):
            assert len(shards) == D
            for d, got in enumerate(shards):
                assert got.dtype == full.dtype and got.shape == (D * 24,)
                assert np.shares_memory(got, full[d * D : (d + 1) * D])
            np.testing.assert_array_equal(np.concatenate(shards), full.reshape(-1))

    @pytest.mark.parametrize("D", [1, 2, 8])
    def test_the_outputs_of_a_device_program(self, D):
        """Real sharded outputs of mixed widths: per output ``D`` flat
        shards that concatenate to the whole array."""
        mesh = _mesh(D)
        rng = np.random.default_rng(D)
        sends = tuple(
            rng.integers(0, 2**31, (D * D, 16)).astype(dt)
            for dt in (np.int64, np.int32, np.uint8)
        )
        out = sh._compact_program(
            mesh, tuple(sh.put_sharded(mesh, s) for s in sends)
        )
        host, account = sh._fetch_shards(out)
        assert account == {"shards": 3 * D, "started": 3 * D, "assembled_bytes": 0}
        for s, shards in zip(sends, host):
            want = s.reshape(D, D, 16).transpose(1, 0, 2).reshape(-1)
            assert [x.shape for x in shards] == [(D * 16,)] * D
            assert all(x.dtype == s.dtype for x in shards)
            np.testing.assert_array_equal(np.concatenate(shards), want)


def _received(sends, D):
    """What the ``all_to_all`` delivers, per payload: owner ``o``'s flat
    ``[D*cap]`` shard — slot ``(s, o)`` of every source, source-major —
    each its own read-only buffer, as the runtime hands them over."""
    out = []
    for buf in sends:
        slots = buf.reshape(D, D, -1)
        shards = [np.ascontiguousarray(slots[:, o]).reshape(-1) for o in range(D)]
        for shard in shards:
            shard.flags.writeable = False
        out.append(shards)
    return out


def _unpacked_equals_the_lexsort(plan, sends, keys, payloads, nb, D):
    """``_compact_unpack`` of the shards the sends arrive as, held to
    ``_reference`` element for element; returns the runs it copied."""
    ids, cols, offsets, runs = sh._compact_unpack(plan, _received(sends, D))
    ref = _reference(keys, payloads, nb, D)
    np.testing.assert_array_equal(ids, ref[0])
    np.testing.assert_array_equal(offsets, ref[2])
    for a, b in zip(cols, ref[1]):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    return runs


class TestCompactUnpackFromShards:
    @pytest.mark.parametrize("D", [2, 4, 8])
    @pytest.mark.parametrize("shape", _LAYOUT_SHAPES)
    def test_equals_the_lexsort(self, D, shape):
        rng = np.random.default_rng(D * 7 + len(shape))
        keys = _layout_keys(shape, D, rng)
        n, nb = keys.shape[1], _LAYOUT_BUCKETS
        payloads = [
            np.arange(1, n + 1, dtype=np.int64),
            (np.arange(n) % 251 + 1).astype(np.int32),
        ]
        plan = sh._compact_plan(keys, nb, 42, D)
        sends, _ = sh._compact_pack(plan, payloads)
        runs = _unpacked_equals_the_lexsort(plan, sends, keys, payloads, nb, D)
        assert runs <= D * nb

    @pytest.mark.parametrize("D", [2, 4, 8])
    def test_mixed_widths_equal_the_lexsort(self, D):
        """8-byte values, 4-byte string codes and a validity plane under
        two key columns, each unpacked from its own shards."""
        rng = np.random.default_rng(D)
        n, nb = 3001, 16
        keys = rng.integers(0, 97, (2, n)).astype(np.int64)
        payloads = [
            rng.integers(-(2**60), 2**60, n).astype(np.int64),
            rng.integers(0, 3, n).astype(np.int32),
            rng.integers(0, 2, n).astype(bool),
        ]
        plan = sh._compact_plan(keys, nb, 42, D)
        sends, _ = sh._compact_pack(plan, payloads)
        _unpacked_equals_the_lexsort(plan, sends, keys, payloads, nb, D)


# ---------------------------------------------------------------------------
# Session-level: whole builds, parquet bytes
# ---------------------------------------------------------------------------


@pytest.fixture
def mesh8(session_factory):
    return session_factory(8)


@pytest.fixture
def mixed_parquet(tmp_path):
    rng = np.random.default_rng(17)
    d = tmp_path / "mixed"
    d.mkdir()
    for i in range(4):
        n = 2500
        vals = rng.normal(size=n)
        t = pa.table(
            {
                "k": pa.array(rng.integers(0, 40, n), type=pa.int64()),
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


def _build(session, src, name, strategy, budget=0, hosts=0):
    session.conf.set(C.BUILD_EXCHANGE_STRATEGY, strategy)
    session.conf.set(C.BUILD_EXCHANGE_TWOSTAGE_HOSTS, hosts)
    session.conf.set(C.INDEX_BUILD_MEMORY_BUDGET, budget)
    hs = Hyperspace(session)
    df = session.read.parquet(src)
    hs.create_index(df, CoveringIndexConfig(name, ["k"], ["s", "v"]))
    entry = session.index_manager.get_index_log_entry(name)
    return sorted(entry.content.files)


def _assert_identical_files(files_a, files_b, tag):
    assert [os.path.basename(f) for f in files_a] == [
        os.path.basename(f) for f in files_b
    ], tag
    for fa, fb in zip(files_a, files_b):
        assert _sha(fa) == _sha(fb), f"{tag}: parquet bytes differ: {fa}"


class TestBuildDifferential:
    def test_in_memory_builds_bit_identical(self, mesh8, mixed_parquet):
        ref = _build(mesh8, mixed_parquet, "exhost", "host")
        from hyperspace_tpu.indexes.covering_build import last_build_telemetry

        for strat in ("auto", "compact", "twostage"):
            files = _build(
                mesh8, mixed_parquet, f"ex{strat}", strat, hosts=2
            )
            _assert_identical_files(files, ref, strat)
            expect = "host" if strat == "auto" else strat
            assert last_build_telemetry["shuffle_strategy"] == expect

    def test_streaming_waves_bit_identical(self, mesh8, mixed_parquet):
        from hyperspace_tpu.indexes.covering_build import (
            per_file_materialized_bytes,
        )

        first = sorted(os.listdir(mixed_parquet))[0]
        per_file = per_file_materialized_bytes(
            [os.path.join(mixed_parquet, first)], "parquet"
        )[0]
        budget = int(per_file * 1.5)  # several waves
        ref = _build(mesh8, mixed_parquet, "sthost", "host", budget=budget)
        from hyperspace_tpu.indexes.covering_build import last_build_telemetry

        for strat in ("compact", "twostage"):
            files = _build(
                mesh8, mixed_parquet, f"st{strat}", strat,
                budget=budget, hosts=2,
            )
            _assert_identical_files(files, ref, strat)
            assert last_build_telemetry["shuffle_waves"] > 1
            assert "shuffle_skew_ratio_max" in last_build_telemetry
            assert "shuffle_skew_ratio_mean" in last_build_telemetry

    def test_stage_seconds_and_strategy_in_telemetry(self, mesh8, mixed_parquet):
        from hyperspace_tpu.indexes.covering_build import last_build_telemetry

        _build(mesh8, mixed_parquet, "tele", "auto")
        t = last_build_telemetry
        assert t["shuffle_strategy"] == "host"
        for key in ("shuffle_pack_s", "shuffle_exchange_s", "shuffle_unpack_s"):
            assert key in t, t
        assert t["shuffle_devices"] == 8.0


class TestSkewWarnRateLimit:
    def test_streaming_build_warns_once(self, mesh8, tmp_path, caplog):
        """A skewed streaming build runs one exchange per wave; the skew
        warning must fire ONCE per build while telemetry records every
        wave as a max/mean pair."""
        d = tmp_path / "skew"
        d.mkdir()
        # per wave (one file), every shard sends all its rows to ONE
        # peer: n/8 per (shard, peer) slot must clear the warn floor
        n = 40000
        t = pa.table(
            {
                "k": pa.array(np.full(n, 7), type=pa.int64()),
                "s": pa.array(["x"] * n),
                "v": pa.array(np.ones(n)),
            }
        )
        for i in range(4):
            pq.write_table(t, d / f"p{i}.parquet")
        from hyperspace_tpu.indexes.covering_build import (
            last_build_telemetry,
            per_file_materialized_bytes,
        )

        per_file = per_file_materialized_bytes(
            [str(d / "p0.parquet")], "parquet"
        )[0]
        with caplog.at_level(logging.WARNING, "hyperspace_tpu.shuffle"):
            _build(
                mesh8, str(d), "skew1x", "auto", budget=int(per_file * 1.5)
            )
        warns = [r for r in caplog.records if "shuffle skew" in r.message]
        assert len(warns) == 1, warns
        tele = last_build_telemetry
        assert tele["shuffle_waves"] > 1
        assert tele["shuffle_skew_ratio_max"] >= C.BUILD_SHUFFLE_SKEW_WARN_RATIO
        assert tele["shuffle_skew_ratio_mean"] > 1.0
        # a second build warns again (fresh latch per data op)
        caplog.clear()
        with caplog.at_level(logging.WARNING, "hyperspace_tpu.shuffle"):
            _build(
                mesh8, str(d), "skew2x", "auto", budget=int(per_file * 1.5)
            )
        warns = [r for r in caplog.records if "shuffle skew" in r.message]
        assert len(warns) == 1, warns
