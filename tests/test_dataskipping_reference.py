"""A data-skipping build held to the plain reference.

The benchmark's configuration ``tpch-skip-1chip`` keeps a data-skipping
index over LINEITEM as many files in ``l_orderkey`` order: one sketch row
a file, the min and max of ``l_orderkey`` and a bloom filter of
``l_partkey``. Here, at 12,000 rows in 20 files on one host device, the
same ``create_index`` is compared with what
``benchmarks/reference_skipping.py`` expects (numpy over the generated
columns, nothing of the program): every min, max and bloom byte of the
sketch table, the files kept by point lookups and ranges (the bloom's
false positives as the filter has them, and no file with a row of the
predicate pruned), their answers, and the build's account — one
``sketch`` span a build whatever the file count, with what its seconds
went to as attrs, and a sketch file that neither the tracing nor
reading the files on a pool touches.
"""

import json
import os
import sys

import numpy as np
import pyarrow.parquet as pq
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import datagen  # noqa: E402
import reference  # noqa: E402
import reference_skipping as rs  # noqa: E402

from hyperspace_tpu import constants as C  # noqa: E402
from hyperspace_tpu.hyperspace import Hyperspace  # noqa: E402
from hyperspace_tpu.indexes.dataskipping import DataSkippingIndexConfig  # noqa: E402
from hyperspace_tpu.indexes.sketches import sketch_from_dict  # noqa: E402
from hyperspace_tpu.obs import trace  # noqa: E402
from hyperspace_tpu.ops import pad_len  # noqa: E402
from hyperspace_tpu.session import HyperspaceSession  # noqa: E402

N_ORDERS = 3000                     # 12,000 rows
N_FILES = 20                        # 600 rows a file, each a range of l_orderkey
SEED = 2**31 + 42
SKETCH_FILE = "part-00000-sketch.parquet"

with open(os.path.join(BENCH, "configs", "tpch-skip-1chip.json")) as _f:
    CONFIG = dict(json.load(_f), files_per_table=N_FILES)
IX = CONFIG["index"]
BLOOM, MINMAX = rs.bloom_of(CONFIG), rs.minmax_of(CONFIG)


def _config(name=IX["name"]):
    return DataSkippingIndexConfig(name, *map(sketch_from_dict, IX["sketches"]))


@pytest.fixture(scope="module")
def lake(tmp_path_factory):
    """-> {files: (directory, the reference's columns)} at 20 files and 5."""
    out = {}
    for n_files in (N_FILES, 5):
        tmp = str(tmp_path_factory.mktemp(f"lake{n_files}"))
        out[n_files] = datagen.gen_lineitem(tmp, N_ORDERS, n_files, SEED, cols=IX["indexed"])
    return out


def _session(tmp_path, name="s"):
    s = HyperspaceSession()
    s.conf.set(C.INDEX_SYSTEM_PATH, str(tmp_path / name))
    return s


def _build(tmp_path, lake_dir, name="s"):
    """-> (session, the source DataFrame, the sketch file's path)."""
    s = _session(tmp_path, name)
    items = s.read.parquet(lake_dir)
    Hyperspace(s).create_index(items, _config())
    entry = s.index_manager.get_index_log_entry(IX["name"])
    (path,) = entry.content.files
    assert os.path.basename(path) == SKETCH_FILE
    return s, items, path


def _file_of(path: str) -> int:
    return int(os.path.basename(path)[len("part"):-len(".parquet")])


@pytest.fixture(scope="module")
def built(lake, tmp_path_factory):
    directory, cols = lake[N_FILES]
    s, items, path = _build(tmp_path_factory.mktemp("built"), directory)
    return s, items, path, cols, rs.file_sketches(cols, CONFIG)


def test_the_sketch_table_is_the_references(built):
    s, _items, path, _cols, want = built
    table = pq.read_table(path)
    assert table.num_rows == N_FILES
    entry = s.index_manager.get_index_log_entry(IX["name"])
    file_of = {info.id: _file_of(p) for p, info in entry.relation.content.file_infos}
    files = [file_of[i] for i in table.column(C.DATA_FILE_NAME_ID).to_pylist()]
    assert sorted(files) == list(range(N_FILES))
    col = MINMAX["column"]
    lo = table.column(f"MinMax_{col}__min").to_pylist()
    hi = table.column(f"MinMax_{col}__max").to_pylist()
    bits = table.column(f"BloomFilter_{BLOOM['column']}__bits").to_pylist()
    assert [lo[files.index(i)] for i in range(N_FILES)] == want["min"]
    assert [hi[files.index(i)] for i in range(N_FILES)] == want["max"]
    for i in range(N_FILES):
        assert len(bits[files.index(i)]) == BLOOM["m"] // 8 == len(want["bloom"][i])
        assert bits[files.index(i)] == want["bloom"][i], f"file {i}"


def _lookups(cols):
    col = cols[BLOOM["column"]]
    drawn = col[np.random.default_rng([SEED, 1]).integers(0, len(col), 7)]
    return [int(p) for p in drawn] + [int(col.max()) + 1]


def _ranges(cols):
    col = cols[MINMAX["column"]]
    lo, hi = int(col.min()), int(col.max())
    width = (hi - lo) // 100
    drawn = np.random.default_rng([SEED, 2]).integers(lo, hi - width, 2)
    return [(a, a + width) for a in [lo, *map(int, drawn), hi - width]]


QUERIES = [("lookup", i) for i in range(8)] + [("range", i) for i in range(4)]


@pytest.mark.parametrize("kind,i", QUERIES, ids=[f"{k}{i}" for k, i in QUERIES])
def test_a_query_keeps_the_files_the_reference_keeps(built, kind, i):
    s, items, _path, cols, want = built
    if kind == "lookup":
        a = b = _lookups(cols)[i]
        col = BLOOM["column"]
        query = items.filter(items[col] == a)
        keep = rs.kept_by_lookup(want, a, BLOOM["m"], BLOOM["k"])
    else:
        a, b = _ranges(cols)[i]
        col = MINMAX["column"]
        query = items.filter((items[col] >= a) & (items[col] <= b))
        keep = rs.kept_by_range(want, a, b)
    query = query.select(*IX["indexed"])
    s.enable_hyperspace()
    try:
        plan = query.explain()
        assert f"Hyperspace(Type: DS, Name: {IX['name']}" in plan
        scanned = s.optimize(query.logical_plan).collect_leaves()[0].relation.files
        got = query.collect()
    finally:
        s.disable_hyperspace()
    values = cols[col]
    holding = rs.files_with_rows(values, N_FILES, lambda v: (v >= a) & (v <= b))
    assert {_file_of(p) for p in scanned} == keep
    assert holding <= keep, "a pruned file holds a row of the predicate"
    if i == 7:      # one past the largest part key: no row, no file but false positives
        assert holding == set() and got.num_rows == 0
    mask = (values >= a) & (values <= b)
    assert reference.digest(reference.table_cols(got)) == reference.digest(
        {c: cols[c][mask] for c in IX["indexed"]})


def _root():
    return trace.finished("action.CreateAction")[-1]


#: the read's threads in each mode: ``native._cores`` and a byte per
#: worker set as the scan's own tests set them (tests/test_scan_assembly.py)
WORKERS = {"inline": 1, "pool": 4}


def _read(monkeypatch, mode):
    """Send the build's source reads inline (one thread) or to a pool."""
    from hyperspace_tpu import native
    from hyperspace_tpu.indexes import covering_build

    monkeypatch.setattr(native, "_cores", lambda: WORKERS[mode])
    monkeypatch.setattr(covering_build, "_SCAN_BYTES_PER_WORKER", 1)


@pytest.mark.parametrize("read", sorted(WORKERS))
def test_one_sketch_span_a_build_whatever_the_file_count(lake, tmp_path, monkeypatch, read):
    _read(monkeypatch, read)
    names = {}
    for n_files in (5, N_FILES):
        directory, cols = lake[n_files]
        _build(tmp_path, directory, name=f"n{n_files}")
        root = _root()
        names[n_files] = sorted(sp.name for sp in root.spans)
        (sketch,) = [sp for sp in root.spans if sp.name == "sketch"]
        (write,) = [sp for sp in root.spans if sp.name == "write"]
        a = sketch.attrs
        assert a["files"] == n_files and a["rows"] == len(cols[IX["indexed"][0]])
        assert a["bloom_s"] >= a["device_s"] + a["set_bits_s"] > 0
        assert a["bloom_s"] >= a["words_s"] + a["device_s"] + a["set_bits_s"]
        assert a["workers"] == WORKERS[read]
        if a["workers"] == 1:   # one thread: the parts add up to the span
            assert sketch.duration_s >= a["read_s"] + a["minmax_s"] + a["bloom_s"] + a["table_s"]
        else:                   # the reads are thread seconds beside the sketches
            assert a["read_s"] <= a["workers"] * sketch.duration_s
            assert sketch.duration_s >= a["minmax_s"] + a["bloom_s"] + a["table_s"]
        assert write.attrs["files"] == 1 and write.attrs["bytes"] > 0
        # the bloom's device leg, reckoned from shapes: two 32-bit words a
        # padded row up, k 32-bit indices a padded row down
        rows = [hi - lo for lo, hi in rs.file_bounds(a["rows"], n_files)]
        assert root.attrs["h2d_bytes"] == sum(2 * pad_len(n) * 4 for n in rows)
        assert root.attrs["d2h_bytes"] == sum(BLOOM["k"] * pad_len(n) * 4 for n in rows)
        assert root.children_union_s() >= 0.5 * root.duration_s
    assert names[5] == names[N_FILES]
    assert names[5].count("sketch") == 1


def test_the_sketch_file_is_the_same_with_the_tracing_off(lake, tmp_path, monkeypatch):
    directory, _cols = lake[5]
    _s, _items, traced = _build(tmp_path, directory, name="on")
    with open(traced, "rb") as f:
        on = f.read()
    trace.reset()
    monkeypatch.setattr(trace, "root", lambda *_a, **_k: trace.NOOP)
    _s, _items, untraced = _build(tmp_path, directory, name="off")
    assert trace.finished("action.CreateAction") == []
    with open(untraced, "rb") as f:
        assert f.read() == on


def _file_ids(session):
    entry = session.index_manager.get_index_log_entry(IX["name"])
    return {p: info.id for p, info in entry.relation.content.file_infos}


@pytest.mark.parametrize("read", sorted(WORKERS))
def test_the_sketch_file_is_the_same_read_inline_or_pooled(lake, tmp_path, monkeypatch, read):
    directory, _cols = lake[N_FILES]
    with monkeypatch.context() as m:
        _read(m, "inline")
        inline_session, _items, inline = _build(tmp_path, directory, name="inline")
    _read(monkeypatch, read)
    session, _items, path = _build(tmp_path, directory, name=f"read_{read}")
    (sketch,) = [sp for sp in _root().spans if sp.name == "sketch"]
    assert sketch.attrs["workers"] == WORKERS[read] and sketch.attrs["files"] == N_FILES
    with open(inline, "rb") as f:
        want = f.read()
    with open(path, "rb") as f:
        assert f.read() == want
    assert _file_ids(session) == _file_ids(inline_session)
    assert sorted(_file_ids(session).values()) == list(range(N_FILES))
