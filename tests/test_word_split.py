"""The int64 -> uint32 word split as one native pass, held to its twins.

A covering build turns its int64 key reps into uint32 words twice: the
hash's word block (``ops/hash``: low word, high word a key column, a
zero tail up to the padded length the device program is compiled for)
and the sort's order words (``ops/sort``: high word with the sign bit
flipped, then low word). Both are ``native.split_words_i64`` writing
straight into the array its consumer uses; ``split_words_np`` and
``_order_words_numpy`` are the bit-exact numpy twins the pass falls back
to. Here: the pass against the twins bit for bit, what it does with
input it cannot read in place, the dispatch, ``ColumnarBatch.key_reps``
(one int64 key is a read-only view, several are written once each), and
a whole covering build with the pass against the same build with the
twins forced, file bytes for file bytes. The bloom's ``[1, n]`` block
(``ops/bloom.bit_indices_np``) is the hash's, from either arm.
"""

import glob
import os
import pathlib

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import jax

from hyperspace_tpu import constants as C
from hyperspace_tpu import native
from hyperspace_tpu.hyperspace import Hyperspace
from hyperspace_tpu.indexes import covering_build
from hyperspace_tpu.indexes.covering import CoveringIndexConfig
from hyperspace_tpu.io.columnar import NULL_KEY_REP, Column, ColumnarBatch
from hyperspace_tpu.obs import trace
from hyperspace_tpu.ops import bloom
from hyperspace_tpu.ops import hash as hash_ops
from hyperspace_tpu.ops import pad_len
from hyperspace_tpu.ops import sort as sort_ops
from hyperspace_tpu.session import HyperspaceSession

I64 = np.iinfo(np.int64)
EDGES = np.array([I64.min, I64.max, -1, 0, 1, NULL_KEY_REP, 1 << 32, -(1 << 32), (1 << 31) - 1, 1 << 31],
                 dtype=np.int64)
# not a multiple of a thread count, of a cache line or of a power of two
SIZES = [0, 1, 7, 65_537, 1_048_577]
_HUGE = 1 << 62

needs_native = pytest.mark.skipif(native.load() is None, reason="the native kernels did not build here")


def _reps(k, n, seed=39):
    """``[k, n]`` int64 key reps: the edge values first, then keys that
    fill the high word, small keys and negative keys."""
    rng = np.random.default_rng([seed, k, n])
    reps = np.where(rng.integers(0, 3, (k, n)) == 0,
                    rng.integers(I64.min, I64.max, (k, n), dtype=np.int64, endpoint=True),
                    rng.integers(-50, 50, (k, n)))
    m = min(n, len(EDGES))
    reps[:, :m] = EDGES[:m]
    if k > 1 and m:
        reps[1, :m] = EDGES[:m][::-1]
    return np.ascontiguousarray(reps, dtype=np.int64)


def _guarded(rows, row_len, guard=64, fill=0xDEADBEEF):
    """A C-contiguous ``uint32[rows, row_len]`` block with ``guard``
    words after it in the same buffer -> (block, the words after)."""
    flat = np.full(rows * row_len + guard, fill, dtype=np.uint32)
    return flat[: rows * row_len].reshape(rows, row_len), flat[rows * row_len:]


# -- the native pass against its twins ------------------------------------------------

@needs_native
@pytest.mark.parametrize("tail", [0, 5, 1000], ids=["exact", "tail5", "tail1000"])
@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("k", [1, 2, 3], ids=["1key", "2keys", "3keys"])
def test_the_hash_words_equal_split_words_np_and_its_zero_tail(k, n, tail):
    reps = _reps(k, n)
    out, after = _guarded(2 * k, n + tail)
    assert native.split_words_i64(reps, out) is True
    want = hash_ops.split_words_np(reps) if n else np.zeros((2 * k, 0), dtype=np.uint32)
    assert out.dtype == np.uint32 and np.array_equal(out[:, :n], want)
    assert not out[:, n:].any()                 # the tail is the pad word, 0
    assert (after == 0xDEADBEEF).all()          # and nothing is written past the block
    if n >= len(EDGES):     # INT64_MIN's words, as the twin has them
        assert (out[0, 0], out[1, 0]) == (0, 0x80000000) and (out[0, 2], out[1, 2]) == (0xFFFFFFFF,) * 2


@needs_native
@pytest.mark.parametrize("tail", [0, 9], ids=["exact", "tail9"])
@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("k", [1, 2, 3], ids=["1key", "2keys", "3keys"])
def test_the_order_words_equal_order_words_numpy(k, n, tail):
    reps = _reps(k, n)
    out, after = _guarded(2 * k, n + tail)
    assert native.split_words_i64(reps, out, hi_xor=0x80000000, hi_first=True, pad=0xFFFFFFFF) is True
    if n:
        assert np.array_equal(out[:, :n], sort_ops._order_words_numpy(reps))
    assert (out[:, n:] == 0xFFFFFFFF).all()     # the pad word, not XORed
    assert (after == 0xDEADBEEF).all()
    if n >= len(EDGES):     # the planes' unsigned order is the keys' signed order
        order = np.lexsort(out[:2, :n][::-1])
        assert np.array_equal(reps[0][order], np.sort(reps[0], kind="stable"))


@needs_native
def test_what_the_pass_cannot_read_in_place_it_leaves_to_the_twin():
    wide = _reps(2, 4001)
    every_other = wide[:, ::2]                  # 16 bytes from key to key
    out, _after = _guarded(4, every_other.shape[1])
    assert native.split_words_i64(every_other, out) is False
    assert native.split_words_i64(wide[:, :2001].astype(np.int32), out) is False
    unsigned = wide[:, :2001].view(np.uint64)   # the same 8 bytes a key: read as they lie
    assert native.split_words_i64(unsigned, out) is True
    assert np.array_equal(out, hash_ops.split_words_np(wide[:, :2001]))
    # rows that are each contiguous but far apart (a view of one column's
    # values, two rows of a wider block) are read row by row
    block = _reps(5, 3000)
    rows = block[1::3]
    assert not rows.flags.c_contiguous
    out, _after = _guarded(4, 3000)
    assert native.split_words_i64(rows, out) is True
    assert np.array_equal(out, hash_ops.split_words_np(np.ascontiguousarray(rows)))


@needs_native
@pytest.mark.parametrize("bad", ["dtype", "strided", "short", "rows", "readonly"])
def test_an_output_the_pass_may_not_write_through_raises(bad):
    reps = _reps(2, 100)
    out = {
        "dtype": np.zeros((4, 100), dtype=np.int32),
        "strided": np.zeros((4, 200), dtype=np.uint32)[:, ::2],
        "short": np.zeros((4, 99), dtype=np.uint32),
        "rows": np.zeros((2, 100), dtype=np.uint32),
        "readonly": np.zeros((4, 100), dtype=np.uint32),
    }[bad]
    if bad == "readonly":
        out.flags.writeable = False
    with pytest.raises(ValueError):
        native.split_words_i64(reps, out)


# -- the dispatch: the library, the input, the row count ---------------------------------

def _arms(monkeypatch, native_rows):
    monkeypatch.setattr(hash_ops, "_NATIVE_HASH_MIN_ROWS", native_rows)
    monkeypatch.setattr(sort_ops, "_NATIVE_PARTITION_MIN_ROWS", native_rows)


def _both(reps):
    """(padded hash words, ran natively, order words, the ``native``
    attr the order words left on the span they ran under)."""
    words, ran = hash_ops._padded_words(reps, pad_len(reps.shape[1]))
    root = trace.root("action.CreateAction", always=True)
    with trace.activate(root), trace.span("partition"):
        planes = sort_ops._order_words_np(reps)
    root.finish()
    (part,) = [s for s in root.spans if s.name == "partition"]
    return words, ran, planes, part.attrs["native"]


def _want(reps):
    n = reps.shape[1]
    words = np.zeros((2 * reps.shape[0], pad_len(n)), dtype=np.uint32)
    words[:, :n] = hash_ops.split_words_np(np.ascontiguousarray(reps))
    return words, sort_ops._order_words_numpy(np.ascontiguousarray(reps))


@needs_native
@pytest.mark.parametrize("k", [1, 2], ids=["1key", "2keys"])
def test_at_the_threshold_the_pass_runs_and_below_it_the_twin(monkeypatch, k):
    reps = _reps(k, 5000)
    want_words, want_planes = _want(reps)
    for threshold, native_ran in ((5000, 1), (5001, 0)):
        _arms(monkeypatch, threshold)
        words, ran, planes, attr = _both(reps)
        assert (int(ran), attr) == (native_ran, native_ran)
        assert words.dtype == planes.dtype == np.uint32
        assert words.flags.c_contiguous and words.shape == (2 * k, pad_len(5000))
        assert np.array_equal(words, want_words) and np.array_equal(planes, want_planes)


def test_with_the_library_unloaded_the_twins_run_and_native_reads_0(monkeypatch):
    _arms(monkeypatch, 1)
    monkeypatch.setattr(native, "load", lambda wait=True: None)
    reps = _reps(2, 70_001)
    words, ran, planes, attr = _both(reps)
    want_words, want_planes = _want(reps)
    assert (ran, attr) == (False, 0)
    assert np.array_equal(words, want_words) and np.array_equal(planes, want_planes)


@pytest.mark.parametrize("shape", ["strided", "uint64", "one_column_view"])
def test_the_dispatch_never_reads_wrong_bytes(monkeypatch, shape):
    _arms(monkeypatch, 1)
    wide = _reps(2, 6000)
    reps = {"strided": wide[:, ::3], "uint64": wide.view(np.uint64), "one_column_view": wide[1][None, :]}[shape]
    words, _ran, planes, _attr = _both(reps)
    want_words, want_planes = _want(reps)
    assert np.array_equal(words, want_words) and np.array_equal(planes, want_planes)
    assert np.array_equal(hash_ops.bucket_ids_host(np.ascontiguousarray(reps).view(np.int64), 200),
                          hash_ops.bucket_ids_numpy(np.ascontiguousarray(reps).view(np.int64), 200))


@pytest.mark.parametrize("arm", ["native", "numpy"])
@pytest.mark.parametrize("n", [1, 4097, 70_001])
def test_the_device_hash_takes_the_same_block_from_either(monkeypatch, n, arm):
    """``bucket_ids_np``'s device arm with the pass and with the twin:
    the same padded block, so the same ids as the plain host murmur."""
    if arm == "native" and native.load() is None:
        pytest.skip("the native kernels did not build here")
    monkeypatch.setattr(hash_ops, "_HOST_HASH_MAX_ROWS", 0)
    _arms(monkeypatch, 1 if arm == "native" else _HUGE)
    reps = _reps(2, n)
    root = trace.root("action.CreateAction", always=True)
    with trace.activate(root):
        got = hash_ops.bucket_ids_np(reps, 200)
    root.finish()
    assert np.array_equal(got, hash_ops.bucket_ids_numpy(reps, 200))
    (split,) = [s for s in root.spans if s.name == "split_words"]
    assert split.attrs == {"words": 4, "native": int(arm == "native")}
    (h2d,) = [s for s in root.spans if s.name == "h2d"]
    assert h2d.attrs["bytes"] == root.attrs["h2d_bytes"] == 4 * 4 * pad_len(n)


@pytest.mark.parametrize("arm", ["native", "numpy"])
@pytest.mark.parametrize("n", [1, 4097, 80_000])
def test_the_bloom_takes_the_same_block_from_either(monkeypatch, n, arm):
    """``ops/bloom.bit_indices_np`` pads its ``[1, n]`` block with the
    hash's ``_padded_words``: with the pass and with the twin the same
    block (a benchmark file's 80,000 rows pad to 131,072), so the same
    bit indices as the split and zero tail written out here."""
    if arm == "native" and native.load() is None:
        pytest.skip("the native kernels did not build here")
    _arms(monkeypatch, 1 if arm == "native" else _HUGE)
    reps = _reps(1, n)[0]
    m, k = bloom.optimal_params(n, 0.01)
    want_words = np.zeros((2, pad_len(n)), dtype=np.uint32)
    want_words[:, :n] = hash_ops.split_words_np(reps[None, :])
    words, ran = hash_ops._padded_words(reps[None, :], pad_len(n))
    assert ran == (arm == "native") and np.array_equal(words, want_words)
    acc = dict.fromkeys(("words_s", "device_s"), 0.0) | dict.fromkeys(("h2d_bytes", "d2h_bytes"), 0)
    got = bloom.bit_indices_np(reps, m, k, acc)
    want = np.asarray(bloom._bit_indices(jax.numpy.asarray(want_words), m, k))[:, :n]
    assert got.shape == (k, n) and np.array_equal(got, want)
    assert acc["h2d_bytes"] == 2 * 4 * pad_len(n) and acc["d2h_bytes"] == k * 4 * pad_len(n)
    assert acc["words_s"] > 0 and acc["device_s"] > 0
    if n == 80_000:
        assert pad_len(n) == 131_072


# -- ColumnarBatch.key_reps: a view where one serves, one pass a column where not ---------

def _batch(n=1000):
    rng = np.random.default_rng(7)
    valid = rng.integers(0, 5, n) > 0
    return ColumnarBatch({
        "a": Column("numeric", pa.int64(), values=rng.integers(-9, 9, n).astype(np.int64)),
        "b": Column("numeric", pa.int64(), values=rng.integers(I64.min, I64.max, n, dtype=np.int64)),
        "nullable": Column("numeric", pa.int64(), values=rng.integers(0, 9, n).astype(np.int64), validity=valid),
        "f": Column("numeric", pa.float64(), values=np.where(valid, rng.normal(size=n), -0.0)),
        "u": Column("numeric", pa.uint64(), values=rng.integers(0, 2**64 - 1, n, dtype=np.uint64)),
        "i32": Column("numeric", pa.int32(), values=rng.integers(-9, 9, n).astype(np.int32)),
        "flag": Column("numeric", pa.bool_(), values=valid.copy()),
        "s": Column.from_arrow(pa.array([None if i % 7 == 0 else f"s{i % 13}" for i in range(n)])),
    })


def test_one_int64_key_is_a_read_only_view_of_the_column():
    batch = _batch()
    before = batch.column("a").values.copy()
    reps = batch.key_reps(["a"])
    assert reps.shape == (1, 1000) and reps.dtype == np.int64
    assert np.shares_memory(reps, batch.column("a").values) and not reps.flags.owndata
    assert not reps.flags.writeable and batch.column("a").values.flags.writeable
    with pytest.raises(ValueError):
        reps[0, 0] = 5
    assert np.array_equal(reps[0], batch.column("a").key_rep())
    assert np.array_equal(batch.column("a").values, before)
    # key_rep() itself still hands out a fresh array (the serve path writes into some)
    fresh = batch.column("a").key_rep()
    assert fresh.flags.writeable and not np.shares_memory(fresh, batch.column("a").values)


@pytest.mark.parametrize("names", [["a", "b"], ["b", "a", "nullable"], ["nullable"], ["f"], ["u"], ["i32"],
                                   ["flag"], ["s"], ["s", "a", "f"]], ids="-".join)
def test_key_reps_equal_the_stack_of_key_rep(names):
    batch = _batch()
    reps = batch.key_reps(names)
    want = np.stack([batch.column(n).key_rep() for n in names])
    assert reps.dtype == np.int64 and reps.flags.c_contiguous and np.array_equal(reps, want)
    # several keys, or one that is not int64 bytes already, own their bytes
    assert reps.flags.owndata and reps.flags.writeable
    for n in names:
        col = batch.column(n)
        held = col.codes if col.kind == "string" else col.values
        assert not np.shares_memory(reps, held)


def test_a_strided_int64_column_is_copied_not_viewed():
    values = np.arange(2000, dtype=np.int64)[::2]
    batch = ColumnarBatch({"a": Column("numeric", pa.int64(), values=values)})
    reps = batch.key_reps(["a"])
    assert reps.flags.c_contiguous and reps.flags.owndata and np.array_equal(reps[0], values)


# -- the build is the same build ------------------------------------------------------------

ROWS = 30_011


@pytest.fixture(scope="module")
def source(tmp_path_factory):
    rng = np.random.default_rng(3939)
    first = np.where(rng.integers(0, 4, ROWS) == 0, rng.integers(-(1 << 62), 1 << 62, ROWS), rng.integers(-300, 300, ROWS))
    table = pa.table({"k1": first.astype(np.int64), "k2": rng.integers(-3, 4, ROWS).astype(np.int64),
                      "v": rng.normal(size=ROWS), "w": rng.integers(0, 1000, ROWS).astype(np.int64)})
    out = tmp_path_factory.mktemp("word_split_src")
    for i in range(3):
        lo, hi = i * ROWS // 3, (i + 1) * ROWS // 3
        pq.write_table(table.slice(lo, hi - lo), str(out / f"p{i}.parquet"))
    return str(out), table


def _create(root, src, keys, monkeypatch, use_native):
    """One ``create_index`` with the device hash forced and the word
    split sent down one arm -> ({file name: bytes}, what
    ``_hash_shuffle`` returned, the action's root, the session)."""
    monkeypatch.setattr(hash_ops, "_HOST_HASH_MAX_ROWS", 1)
    _arms(monkeypatch, 1)
    if not use_native:      # the twins forced: the pass declines
        monkeypatch.setattr(native, "split_words_i64", lambda *a, **k: False)
    seen, real = [], covering_build._hash_shuffle

    def watched(ctx, batch, indexed_cols, num_buckets):
        out = real(ctx, batch, indexed_cols, num_buckets)
        source_values = [batch.column(c).values for c in indexed_cols]
        seen.append((out, source_values, [v.copy() for v in source_values]))
        return out

    monkeypatch.setattr(covering_build, "_hash_shuffle", watched)
    session = HyperspaceSession(devices=jax.devices()[:1])
    session.conf.set(C.INDEX_SYSTEM_PATH, root)
    session.conf.set(C.INDEX_NUM_BUCKETS, 16)
    items = session.read.parquet(src)
    included = [c for c in ("k1", "k2", "v", "w") if c not in keys]
    Hyperspace(session).create_index(items, CoveringIndexConfig("ws_idx", keys, included))
    files = {os.path.basename(f): pathlib.Path(f).read_bytes()
             for f in glob.glob(os.path.join(root, "ws_idx", "v__=*", "*.parquet"))
             if not os.path.basename(f).startswith(("_", "."))}      # the bucket files, not the sidecars
    return files, seen, trace.finished("action.CreateAction")[-1], session


@needs_native
@pytest.mark.parametrize("keys", [["k1"], ["k1", "k2"]], ids=["1key", "2keys"])
def test_a_build_with_the_pass_equals_the_build_with_the_twins(monkeypatch, source, tmp_path, keys):
    src, table = source
    k = len(keys)
    with monkeypatch.context() as m:
        files, seen, root, session = _create(str(tmp_path / "native"), src, keys, m, use_native=True)
    with monkeypatch.context() as m:
        twin_files, _seen, twin_root, _session = _create(str(tmp_path / "twin"), src, keys, m, use_native=False)
    assert len(files) == 16 and files == twin_files     # byte for byte, names included

    def attrs(of):
        return {s.name: s.attrs for s in of.spans if s.name in ("key_reps", "split_words", "partition")}

    got, twin = attrs(root), attrs(twin_root)
    assert got["split_words"]["native"] == got["partition"]["native"] == 1
    assert twin["split_words"]["native"] == twin["partition"]["native"] == 0
    assert got["split_words"]["words"] == twin["split_words"]["words"] == 2 * k
    assert got["key_reps"]["copied"] == twin["key_reps"]["copied"] == (0 if k == 1 else 2)
    assert got["key_reps"]["key_columns"] == k
    assert root.attrs["h2d_bytes"] == twin_root.attrs["h2d_bytes"] == 8 * k * pad_len(ROWS)

    # _hash_shuffle's contract, and the source batch as it was
    ((buckets, reps, batch, shard_offs), source_values, copies), = seen
    assert reps.shape == (k, ROWS) and reps.dtype == np.int64 and shard_offs is None
    assert buckets.shape == (ROWS,) and buckets.dtype == np.int32 and batch.num_rows == ROWS
    assert reps.flags.writeable == (k > 1)
    for now, then in zip(source_values, copies):
        assert np.array_equal(now, then)
    for i, name in enumerate(keys):
        assert np.array_equal(np.sort(reps[i]), np.sort(table.column(name).to_numpy()))

    # and the index answers as the table does
    session.enable_hyperspace()
    items = session.read.parquet(src)
    probe = int(table.column("k1")[17].as_py())
    got_rows = items.filter(items["k1"] == probe).select("k1", "k2", "v", "w").collect()
    assert "ws_idx" in items.filter(items["k1"] == probe).select("k1", "k2", "v", "w").explain()
    want_rows = table.filter(pa.compute.equal(table.column("k1"), probe))
    assert sorted(got_rows.column("v").to_pylist()) == sorted(want_rows.column("v").to_pylist())
