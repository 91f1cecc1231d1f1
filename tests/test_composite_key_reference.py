"""A covering build on a composite key held to the plain reference.

The benchmark's configuration ``tpch-q9-1chip`` builds a covering index
on TPC-H Q9's two-column join key (``l_partkey``, ``l_suppkey``) over a
LINEITEM whose rows come in ``l_orderkey`` order: in no order of the
key, with ties. Here, at a small size on one host device, the program's
bucket ids, sorts and the same ``create_index`` are compared with what
``benchmarks/reference_composite.py`` expects (numpy over the generated
columns, nothing of the program), for one key column and for two and on
each arm of ``ops/hash`` and ``ops/sort`` (device, native, numpy): every
row in the file of the bucket ALL its keys hash to, every file in
non-decreasing lexicographic order with ties in source order, the
read-back and pair lookups served as ``CI``, and the attrs and the
counter the build records for what a second key column adds.
"""

import glob
import os
import re
import sys

import numpy as np
import pyarrow.parquet as pq
import pytest

import jax

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import datagen  # noqa: E402
import reference  # noqa: E402
import reference_composite as rc  # noqa: E402

from hyperspace_tpu import constants as C  # noqa: E402
from hyperspace_tpu import native  # noqa: E402
from hyperspace_tpu.hyperspace import Hyperspace  # noqa: E402
from hyperspace_tpu.indexes import covering_build  # noqa: E402
from hyperspace_tpu.indexes.covering import CoveringIndexConfig  # noqa: E402
from hyperspace_tpu.obs import trace  # noqa: E402
from hyperspace_tpu.ops import hash as hash_ops  # noqa: E402
from hyperspace_tpu.ops import pad_len  # noqa: E402
from hyperspace_tpu.ops import sort as sort_ops  # noqa: E402
from hyperspace_tpu.session import HyperspaceSession  # noqa: E402

N_ORDERS = 6000                     # 24,000 rows
KEYS = ["l_partkey", "l_suppkey"]   # Q9's join key: rows in no order of it, ~7.5 rows a pair
# source order is (l_orderkey, l_linenumber) ascending: what a tie has to keep
INCLUDED = ["l_orderkey", "l_linenumber", "l_quantity", "l_extendedprice", "l_discount"]
COLS = KEYS + INCLUDED
SEED = 2**31 + 38
ARMS = ("device", "native", "numpy")
_BUCKET_FILE = re.compile(r"bucket_(\d+)\.parquet$")
_HUGE = 1 << 62


def _arm(monkeypatch, arm):
    """Send ``ops/hash`` and ``ops/sort`` down one arm whatever the size
    (a module attribute that differs from its default wins over the
    probe)."""
    if arm == "native" and native.load() is None:
        pytest.skip("the native kernels did not build here")
    device, use_native = arm == "device", arm == "native"
    monkeypatch.setattr(hash_ops, "_HOST_HASH_MAX_ROWS", 1 if device else _HUGE)
    monkeypatch.setattr(sort_ops, "_HOST_SORT_MAX_ROWS", 1 if device else _HUGE)
    for module, name in ((hash_ops, "_NATIVE_HASH_MIN_ROWS"), (sort_ops, "_NATIVE_SORT_MIN_ROWS"),
                         (sort_ops, "_NATIVE_PARTITION_MIN_ROWS")):
        monkeypatch.setattr(module, name, 1 if use_native else _HUGE)


def _keys(k, n=5000, seed=11):
    """``k`` int64 key columns with ties, negative keys and keys that
    fill the high word."""
    rng = np.random.default_rng([seed, k])
    small = rng.integers(-40, 40, n)
    wide = rng.integers(-(1 << 62), 1 << 62, n)
    first = np.where(rng.integers(0, 4, n) == 0, wide, small)
    return [first, rng.integers(0, 7, n) - 3][:k]


# -- the arms of ops/hash and ops/sort ------------------------------------------

@pytest.mark.parametrize("arm", ARMS)
@pytest.mark.parametrize("k", [1, 2], ids=["1key", "2keys"])
def test_bucket_ids_equal_the_plain_murmur_over_8k_bytes(monkeypatch, k, arm):
    _arm(monkeypatch, arm)
    keys = _keys(k)
    reps = np.stack(keys)
    got = hash_ops.bucket_ids_np(reps, 200)
    assert got.dtype == np.int32 and np.array_equal(got, rc.bucket_of_keys(keys, 200))
    if k == 1:
        assert np.array_equal(got, reference.bucket_of(keys[0], 200))
    else:       # and the second key is in the hash: alone it gives other buckets
        assert not np.array_equal(got, rc.bucket_of_keys(keys[:1], 200))
    if arm == "native":     # the kernel itself, not the twin it falls back to
        assert np.array_equal(native.bucket_ids_i64(reps, 200, 42), got)


@pytest.mark.parametrize("arm", ARMS)
@pytest.mark.parametrize("k", [1, 2], ids=["1key", "2keys"])
def test_the_key_sort_is_lexicographic_and_stable(monkeypatch, k, arm):
    _arm(monkeypatch, arm)
    keys = _keys(k)
    planes = sort_ops._order_words_np(np.stack(keys))
    assert planes.shape == (2 * k, len(keys[0])) and planes.dtype == np.uint32
    perm = sort_ops.lexsort_perm(planes)
    want = np.lexsort(keys[::-1])       # numpy's is stable; its LAST key is the major one
    assert np.array_equal(perm, want)
    first, second = keys[0][perm], (keys + [np.zeros_like(keys[0])])[1][perm]
    assert rc.lex_unsorted(first, second) == 0
    ties = (first[1:] == first[:-1]) & (second[1:] == second[:-1])
    assert ties.any() and np.all(perm[1:][ties] > perm[:-1][ties])
    if k == 2:      # the first key alone leaves its ties in source order: not the pair's
        assert rc.lex_unsorted(keys[0][np.argsort(keys[0], kind="stable")],
                               keys[1][np.argsort(keys[0], kind="stable")]) > 0


@pytest.mark.parametrize("arm", ARMS)
def test_the_partitioned_sort_is_the_global_sort_by_bucket_then_keys(monkeypatch, arm):
    _arm(monkeypatch, arm)
    keys = _keys(2)
    reps = np.stack(keys)
    buckets = hash_ops.bucket_ids_np(reps, 16)
    perm = sort_ops.partitioned_sort_permutation(reps, buckets, 16)
    assert np.array_equal(perm, np.lexsort((keys[1], keys[0], buckets)))


# -- the build --------------------------------------------------------------------

@pytest.fixture(scope="module")
def table(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("q9_items"))
    items_dir, cols = datagen.gen_lineitem(tmp, N_ORDERS, 8, SEED, cols=COLS)
    return items_dir, cols


def _session(root, **conf):
    session = HyperspaceSession(devices=jax.devices()[:1])
    session.conf.set(C.INDEX_SYSTEM_PATH, root)
    for key, value in conf.items():
        session.conf.set(key, value)
    return session


def _build(root, items_dir, k, **conf):
    """One ``create_index`` on the first ``k`` key columns under the
    program's defaults (and ``conf``) -> (session, {bucket: data file},
    the action's root)."""
    session = _session(root, **conf)
    items = session.read.parquet(items_dir)
    Hyperspace(session).create_index(items, CoveringIndexConfig("q9_idx", KEYS[:k], KEYS[k:] + INCLUDED))
    files = sorted(f for f in glob.glob(os.path.join(root, "q9_idx", "v__=*", "*.parquet"))
                   if not os.path.basename(f).startswith(("_", ".")))
    by_bucket = {int(_BUCKET_FILE.search(f).group(1)): f for f in files}
    assert len(by_bucket) == len(files)         # each bucket once
    return session, by_bucket, trace.finished("action.CreateAction")[-1]


def _structure(by_bucket, k, num_buckets):
    """The bucket files against the reference -> (rows, rows in another
    bucket than ALL their keys hash to, files whose keys decrease
    anywhere, files in which a tie is not in source order)."""
    rows = stray = unsorted = unstable = 0
    for bucket, path in by_bucket.items():
        t = reference.table_cols(pq.read_table(path, columns=COLS))
        keys = [t[c] for c in KEYS[:k]]
        rows += len(keys[0])
        stray += int(np.count_nonzero(rc.bucket_of_keys(keys, num_buckets) != bucket))
        second = keys[1] if k == 2 else np.zeros_like(keys[0])
        unsorted += int(rc.lex_unsorted(keys[0], second) > 0)
        tie = (keys[0][1:] == keys[0][:-1]) & (second[1:] == second[:-1])
        source = t["l_orderkey"] * 8 + t["l_linenumber"]
        unstable += int(np.any(source[1:][tie] <= source[:-1][tie]))
    return rows, stray, unsorted, unstable


@pytest.fixture(scope="module", params=[1, 2], ids=["1key", "2keys"])
def built(request, table, tmp_path_factory):
    root = str(tmp_path_factory.mktemp("q9_index"))
    session, by_bucket, action = _build(root, table[0], request.param)
    return request.param, table[0], table[1], session, by_bucket, action


def test_every_row_lies_in_the_bucket_all_its_keys_hash_to(built):
    k, _dir, cols, _session_, by_bucket, _action = built
    want = np.unique(rc.bucket_of_keys([cols[c] for c in KEYS[:k]], 200))
    assert sorted(by_bucket) == want.tolist()
    if k == 2:
        assert len(by_bucket) == 200        # 3,200 pairs fill every bucket; 800 parts need not
    rows, stray, _unsorted, _unstable = _structure(by_bucket, k, 200)
    assert rows == len(cols["l_partkey"]) and stray == 0


def test_every_file_is_lexicographically_sorted_with_ties_in_source_order(built):
    k, _dir, cols, _session_, by_bucket, _action = built
    _rows, _stray, unsorted, unstable = _structure(by_bucket, k, 200)
    assert unsorted == 0 and unstable == 0
    # the source is in no order of the key, and the key has ties: the sort did the work
    assert rc.lex_unsorted(cols["l_partkey"], cols["l_suppkey"]) > len(cols["l_partkey"]) // 3
    pairs = np.unique(np.stack([cols[c] for c in KEYS[:k]]), axis=1).shape[1]
    assert pairs < len(cols["l_partkey"]) // 2


def test_the_read_back_equals_the_references_rows(built):
    k, items_dir, cols, session, by_bucket, _action = built
    session.enable_hyperspace()
    items = session.read.parquet(items_dir)
    every = items.filter(items["l_partkey"] >= 0).select(*COLS)
    plan = every.explain()
    assert "Hyperspace(Type: CI," in plan and "Name: q9_idx" in plan
    assert reference.digest(reference.table_cols(every.collect())) == reference.digest(cols)
    # and the files themselves hold every source row once, payloads bit for bit
    written = [reference.table_cols(pq.read_table(f, columns=COLS)) for f in by_bucket.values()]
    assert reference.digest({c: np.concatenate([w[c] for w in written]) for c in COLS}) == reference.digest(cols)


@pytest.mark.parametrize("row", [17, 4242, 23999, None], ids=["row17", "row4242", "row23999", "absent"])
def test_a_pair_lookup_equals_the_references(built, row):
    _k, items_dir, cols, session, _files, _action = built
    index = rc.PairIndex(cols, *KEYS)
    if row is None:     # a part that exists, with a supplier that is none of its own
        a = int(cols["l_partkey"][0])
        b = next(s for s in range(1, 50) if s not in set(index.seconds_of(a).tolist()))
        assert len(index.rows_of(a, b)) == 0
    else:
        a, b = int(cols["l_partkey"][row]), int(cols["l_suppkey"][row])
        assert row in index.rows_of(a, b)
    session.enable_hyperspace()
    items = session.read.parquet(items_dir)
    query = items.filter((items["l_partkey"] == a) & (items["l_suppkey"] == b)).select(*COLS)
    plan = query.explain()
    assert "Hyperspace(Type: CI," in plan and "Name: q9_idx" in plan
    assert reference.digest(reference.table_cols(query.collect())) == reference.digest(index.answer(a, b, COLS))


@pytest.mark.parametrize("arm", ARMS)
@pytest.mark.parametrize("k", [1, 2], ids=["1key", "2keys"])
def test_a_build_on_each_arm_writes_the_references_layout(monkeypatch, table, tmp_path, k, arm):
    _arm(monkeypatch, arm)
    items_dir, cols = table
    _session_, by_bucket, action = _build(str(tmp_path / "ix"), items_dir, k, **{C.INDEX_NUM_BUCKETS: 8})
    assert _structure(by_bucket, k, 8) == (len(cols["l_partkey"]), 0, 0, 0)
    names = {s.name for s in action.spans}
    assert ("kernel" in names) == (arm == "device") and ("host_hash" in names) == (arm != "device")


# -- a build that drops the second key is seen ----------------------------------------

def test_a_build_that_leaves_the_second_key_out_of_the_hash_is_misbucketed(monkeypatch, table, tmp_path):
    real = covering_build.bucket_ids_np
    monkeypatch.setattr(covering_build, "bucket_ids_np", lambda reps, n: real(reps[:1], n))
    _session_, by_bucket, _action = _build(str(tmp_path / "ix"), table[0], 2)
    rows, stray, unsorted, unstable = _structure(by_bucket, 2, 200)
    # three of a part's four suppliers hash elsewhere as a pair, 199 times in 200
    assert stray > rows * 0.9 and (unsorted, unstable) == (0, 0)


def test_a_build_that_leaves_the_second_key_out_of_the_sort_is_unsorted(monkeypatch, table, tmp_path):
    real = sort_ops._order_words_np
    monkeypatch.setattr(sort_ops, "_order_words_np", lambda reps: real(reps[:1]))
    _session_, by_bucket, _action = _build(str(tmp_path / "ix"), table[0], 2)
    rows, stray, unsorted, _unstable = _structure(by_bucket, 2, 200)
    # sorted on the part key, a part's suppliers in source order: a look
    # at the first key alone passes these files
    assert stray == 0 and unsorted > 0
    for path in by_bucket.values():
        parts = pq.read_table(path, columns=["l_partkey"]).column(0).to_numpy()
        assert np.all(parts[1:] >= parts[:-1])


# -- the build's account of what a second key column adds -----------------------------

def _one(root, name):
    found = [s for s in root.spans if s.name == name]
    assert len(found) == 1, (name, [s.name for s in root.spans])
    return found[0]


def test_the_root_and_the_spans_say_how_many_key_columns(built):
    k, _dir, cols, _session_, by_bucket, root = built
    assert root.attrs["key_columns"] == k == _one(root, "key_reps").attrs["key_columns"]
    sorts = _one(root, "bucket_sorts").attrs
    assert sorts["planes"] == 2 * k and sorts["buckets"] == len(by_bucket)
    counts = np.bincount(rc.bucket_of_keys([cols[c] for c in KEYS[:k]], 200))
    assert sorts["max_rows"] == counts.max()
    write = _one(root, "write").attrs
    assert write["columns"] == len(COLS) and write["files"] == len(by_bucket)
    # the gather and the parquet write are parts of a file's seconds on its writer
    assert write["take_s"] > 0 and write["encode_s"] > 0
    assert write["take_s"] + write["encode_s"] <= write["sum_s"] + 1e-6


@pytest.mark.parametrize("k", [1, 2], ids=["1key", "2keys"])
def test_the_device_hash_moves_two_words_a_key_column(monkeypatch, table, tmp_path, k):
    monkeypatch.setattr(hash_ops, "_HOST_HASH_MAX_ROWS", 1)
    _session_, _files, root = _build(str(tmp_path / "ix"), table[0], k, **{C.INDEX_NUM_BUCKETS: 8})
    rows = len(table[1]["l_partkey"])
    assert _one(root, "split_words").attrs["words"] == 2 * k
    assert _one(root, "h2d").attrs["bytes"] == root.attrs["h2d_bytes"] == 4 * 2 * k * pad_len(rows)
    assert _one(root, "d2h").attrs["bytes"] == root.attrs["d2h_bytes"] == 4 * pad_len(rows)
    assert root.attrs["key_columns"] == k
