"""The plain reference against brute force, the templates' answers, and
the roofline's bytes."""

import numpy as np
import pytest

import datagen
import queries
import reference
import roofline


N_ORDERS = 1000


@pytest.fixture(scope="module")
def tables(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("data"))
    items_dir, items = datagen.gen_lineitem(tmp, N_ORDERS, 4, 2**31 + 7)
    return items_dir, items


def test_same_seed_same_bytes_and_every_seed_the_same_shapes(tables, tmp_path):
    import pyarrow.parquet as pq

    items_dir, items = tables
    _d, again = datagen.gen_lineitem(str(tmp_path / "a"), N_ORDERS, 4, 2**31 + 7)
    assert all(np.array_equal(items[k], again[k]) for k in items)
    on_disk = pq.read_table(items_dir)
    assert tuple(on_disk.column_names) == datagen.LINEITEM_COLS
    four = reference.table_cols(on_disk.select(list(datagen.ITEM_COLS)))
    assert reference.digest(four) == reference.digest(items)
    # asked for by name, each numeric or date column is the one the file holds
    _d, eleven = datagen.gen_lineitem(str(tmp_path / "c"), N_ORDERS, 4, 2**31 + 7, cols=datagen.NUMPY_COLS)
    assert tuple(eleven) == datagen.NUMPY_COLS
    for name in datagen.NUMPY_COLS:
        assert np.array_equal(reference.table_cols(on_disk.select([name]))[name], eleven[name]), name
    # a string column comes back as an object array equal to the file's column
    strings = ("l_returnflag", "l_linestatus", "l_shipinstruct", "l_shipmode", "l_comment")
    _d, named = datagen.gen_lineitem(str(tmp_path / "d"), N_ORDERS, 4, 2**31 + 7, cols=strings)
    for name in strings:
        assert named[name].dtype == object and named[name].tolist() == on_disk.column(name).to_pylist(), name
    with pytest.raises(ValueError, match="l_no_such_column"):
        datagen.gen_lineitem(str(tmp_path / "e"), N_ORDERS, 4, 1, cols=["l_comment", "l_no_such_column"])
    _d, other = datagen.gen_lineitem(str(tmp_path / "b"), N_ORDERS, 4, 12345)
    assert len(other["l_orderkey"]) == len(items["l_orderkey"]) == 4 * N_ORDERS
    assert not np.array_equal(other["l_orderkey"], items["l_orderkey"])
    per_file = [pq.read_metadata(f"{items_dir}/part{i}.parquet").num_rows for i in range(4)]
    assert per_file == [N_ORDERS] * 4


# sha256 of each file the generator wrote before ORDERS could be made:
# (orders, files, seed) -> one digest a file
PARENT_BYTES = {
    (1000, 4, 2**31 + 7): (
        "912679f1c5a3fad4308390cd7e20532ce3bc0c7c1c60695b9eb6277c3e1ea8b1",
        "c15258248fe5811fc46ffbb4c491ba9b2edd51be2bda02829ab9567e1d4b26b5",
        "7cd64a5b88d2e7317fa6f51eae7a481f7bcc915d8eb6644b2053dba8066f76d7",
        "ce2fc17a6fe24742033828301c12f65c2ec05b6b15ed5d3ffb91b1f683539017"),
    (7001, 3, 5): (
        "b3189d9807c2fa113f17f1aae8f8bf09597e50420ccef258bd34b9bfc654b791",
        "c2a02481504747467e0f3f757beb28b0f29a857d2095274808e202ab4a84778d",
        "d86805d6bc03332a89784559b51e362bd9e6f8741f11e6838ceb787c1141f449"),
}


@pytest.mark.parametrize("orders,files,seed", sorted(PARENT_BYTES))
def test_lineitem_bytes_are_what_they_were(tmp_path, orders, files, seed):
    import hashlib

    items_dir, _ = datagen.gen_lineitem(str(tmp_path), orders, files, seed, cols=datagen.LINEITEM_COLS)
    got = tuple(hashlib.sha256((tmp_path / "lineitem" / f"part{i}.parquet").read_bytes()).hexdigest()
                for i in range(files))
    assert got == PARENT_BYTES[orders, files, seed]


def test_lineitem_is_populated_as_the_specification_says(tables):
    import pyarrow.parquet as pq

    items_dir, _ = tables
    t = pq.read_table(items_dir)
    c = {n: t.column(n).combine_chunks() for n in t.column_names}
    key = c["l_orderkey"].to_numpy()
    assert np.all(np.diff(key) >= 0)                       # dbgen's row order
    assert np.all((key - 1) % 32 < 8) and key.min() == 1    # sparse keys
    keys, counts = np.unique(key, return_counts=True)
    assert len(keys) == N_ORDERS and counts.min() == 1 and counts.max() == 7
    line = c["l_linenumber"].to_numpy()
    assert np.array_equal(line[np.r_[True, np.diff(key) > 0]], np.ones(N_ORDERS))
    assert line.max() == 7
    part, supp = c["l_partkey"].to_numpy(), c["l_suppkey"].to_numpy()
    n_parts, n_supps = N_ORDERS * 2 // 15, N_ORDERS // 150
    assert part.min() >= 1 and part.max() <= n_parts
    assert supp.min() >= 1 and supp.max() <= n_supps
    qty = c["l_quantity"].to_numpy()
    assert qty.min() >= 1 and qty.max() <= 50
    retail = (90000 + (part // 10) % 20001 + 100 * (part % 1000)) / 100.0
    assert np.allclose(c["l_extendedprice"].to_numpy(), qty * retail, rtol=1e-12)
    disc, tax = c["l_discount"].to_numpy(), c["l_tax"].to_numpy()
    assert 0.0 <= disc.min() and disc.max() <= 0.10 and 0.0 <= tax.min() and tax.max() <= 0.08
    days = {n: c[n].cast("int32").to_numpy() for n in ("l_shipdate", "l_commitdate", "l_receiptdate")}
    order_first_ship = days["l_shipdate"].min()
    assert order_first_ship > datagen.STARTDATE
    assert days["l_shipdate"].max() <= datagen.ENDDATE - 151 + 121
    late = days["l_receiptdate"] - days["l_shipdate"]
    assert late.min() >= 1 and late.max() <= 30
    assert np.abs(days["l_commitdate"] - days["l_shipdate"]).max() <= 120
    flag = np.array(c["l_returnflag"].to_pylist())
    assert set(flag) <= {"R", "A", "N"}
    assert np.all((flag == "N") == (days["l_receiptdate"] > datagen.CURRENTDATE))
    status = np.array(c["l_linestatus"].to_pylist())
    assert np.all((status == "O") == (days["l_shipdate"] > datagen.CURRENTDATE))
    assert set(c["l_shipinstruct"].to_pylist()) <= set(datagen.INSTRUCTIONS)
    assert set(c["l_shipmode"].to_pylist()) <= set(datagen.MODES)
    lens = np.array([len(x) for x in c["l_comment"].to_pylist()])
    assert lens.min() >= 10 and lens.max() <= 43
    assert str(t.schema.field("l_comment").type) == "string"


def test_digest_sees_one_bit_and_ignores_order(tables):
    _, items = tables
    perm = np.random.default_rng(0).permutation(len(items["l_orderkey"]))
    assert reference.digest({k: v[perm] for k, v in items.items()}) == reference.digest(items)
    bumped = dict(items, l_extendedprice=items["l_extendedprice"].copy())
    bumped["l_extendedprice"][17] = np.nextafter(bumped["l_extendedprice"][17], np.inf)
    assert reference.digest(bumped) != reference.digest(items)
    assert reference.digest(reference.lossy(items)) != reference.digest(items)


def test_point_answers_against_brute_force(tables):
    _, items = tables
    index = reference.KeyIndex(items, "l_orderkey")
    keys = [1, 4, int(items["l_orderkey"].max()), 33, 4]
    cols, counts = reference.ref_point(index, keys, datagen.ITEM_COLS)
    got = reference.segment_digests(cols, counts)
    for i, k in enumerate(keys):
        m = items["l_orderkey"] == k
        assert m.any()
        want = reference.digest({c: items[c][m] for c in datagen.ITEM_COLS})
        assert tuple(int(x) for x in got[i]) == want
    # a key the sparse key space leaves out: an empty answer, digest 0
    cols, counts = reference.ref_point(index, [9, 1], datagen.ITEM_COLS)
    assert counts[0] == 0 and not reference.segment_digests(cols, counts)[0].any()


def test_point_answers_wrong_counts_altered_answers(tables):
    import pyarrow as pa

    _, items = tables
    index = reference.KeyIndex(items, "l_orderkey")
    keys = [1, 2, 3, 4]
    answers = []
    for k in keys:
        m = items["l_orderkey"] == k
        answers.append(pa.table({c: items[c][m] for c in datagen.ITEM_COLS}))
    assert queries.point_answers_wrong(index, keys, answers) == 0
    answers[2] = answers[2].slice(1)
    assert queries.point_answers_wrong(index, keys, answers) == 1
    assert queries.point_answers_wrong(index, keys, None, transform=reference.lossy) == 4


def _murmur3_32(data: bytes, seed: int) -> int:
    """MurmurHash3_x86_32 one byte string at a time, as published."""
    import struct

    c1, c2, m32 = 0xCC9E2D51, 0x1B873593, 0xFFFFFFFF
    h, n = seed, len(data) // 4
    for i in range(n + 1):
        if i < n:
            k = struct.unpack_from("<I", data, 4 * i)[0]
        else:
            k = int.from_bytes(data[4 * n:], "little")
            if not data[4 * n:]:
                break
        k = (k * c1) & m32
        k = ((k << 15) | (k >> 17)) & m32
        k = (k * c2) & m32
        h ^= k
        if i < n:
            h = ((h << 13) | (h >> 19)) & m32
            h = (h * 5 + 0xE6546B64) & m32
    h ^= len(data)
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & m32
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & m32
    return h ^ (h >> 16)


def test_bucket_of_is_the_published_murmur3():
    import struct

    # the algorithm's well-known vectors hold the scalar; the scalar holds the array
    for data, seed, want in ((b"", 0, 0), (b"", 1, 0x514E28B7), (b"\0\0\0\0", 0, 0x2362F9DE),
                             (b"\xff\xff\xff\xff", 0, 0x76293B50), (b"\x21\x43\x65\x87", 0, 0xF55B516B),
                             (b"Hello, world!", 0x9747B28C, 0x24884CBA)):
        assert _murmur3_32(data, seed) == want
    keys = np.random.default_rng(3).integers(-2**63, 2**63 - 1, 500)
    keys[:3] = (0, 1, -1)
    got = reference.murmur3_32_int64(keys, 42)
    assert [int(g) for g in got] == [_murmur3_32(struct.pack("<q", int(k)), 42) for k in keys]
    buckets = reference.bucket_of(keys, 200)
    assert buckets.min() >= 0 and buckets.max() < 200
    assert np.array_equal(buckets, got.astype(np.int64) % 200)


def test_structure_of_bucket_files(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    keys = np.sort(datagen.order_key(np.arange(4000)))
    home = reference.bucket_of(keys, 5)
    version = tmp_path / "l_idx" / "v__=0"
    newer = tmp_path / "l_idx" / "v__=10"
    version.mkdir(parents=True)
    newer.mkdir()
    for b in range(5):
        pq.write_table(pa.table({"l_orderkey": keys[home == b]}),
                       str(newer / f"part-0000{b}-bucket_0000{b}.parquet"))
    pq.write_table(pa.table({"l_orderkey": keys}), str(newer / "_aggsample.parquet"))

    class Ctx:
        config = {"index": {"name": "l_idx", "num_buckets": 5, "indexed": ["l_orderkey"]}}
        index_root = str(tmp_path)
        rows = 4000

    assert queries.newest_version_dir(str(tmp_path / "l_idx")) == str(newer)
    assert all(c["value"] == 0 for c in queries.structure(Ctx).values())
    # one row moved to its neighbour's file, at the place that keeps it sorted
    mine, theirs = keys[home == 0], keys[home == 1]
    pq.write_table(pa.table({"l_orderkey": mine[1:]}), str(newer / "part-00000-bucket_00000.parquet"))
    pq.write_table(pa.table({"l_orderkey": np.sort(np.append(theirs, mine[0]))}),
                   str(newer / "part-00001-bucket_00001.parquet"))
    got = {k: c["value"] for k, c in queries.structure(Ctx).items()}
    assert got == {"bucket_files_gap": 0, "bucket_rows_gap": 0, "misbucketed_rows": 1,
                   "unsorted_bucket_files": 0}
    # a file in reverse order; a bucket twice and another missing
    pq.write_table(pa.table({"l_orderkey": keys[home == 2][::-1]}),
                   str(newer / "part-00002-bucket_00002.parquet"))
    (newer / "part-00004-bucket_00004.parquet").rename(newer / "part-00004-bucket_00003.parquet")
    got = {k: c["value"] for k, c in queries.structure(Ctx).items()}
    assert got["unsorted_bucket_files"] == 1 and got["bucket_files_gap"] == 2
    assert got["misbucketed_rows"] == 1 + int((home == 4).sum())


def test_hash_roofline_bytes_and_peaks():
    assert roofline.bucket_hash_bytes(16_000_000, {}) == 192_000_000
    least = roofline.least_seconds(roofline.bucket_hash_bytes(16_000_000, {}), "TPU v5 lite")
    assert least == pytest.approx(192e6 / 819e9)
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9")
