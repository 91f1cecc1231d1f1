"""The z-span capture's two sources: the write that sorted the rows, or a
second read of the files.

An in-memory z-order write hands the zone-map capture each row group's
first and last address and its encoder spec; the capture uses them when
what it can observe says they stand for the files in front of it, and
reads the indexed columns back otherwise. Held here: both give the same
sidecar, key for key; each fallback takes the re-read and says so in the
capture's counters; the hand-off is of one write and one capture only;
every action that writes a fresh version directory in memory uses it.
"""

import glob
import os
import shutil

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import constants as C
from hyperspace_tpu.hyperspace import Hyperspace
from hyperspace_tpu.indexes import aggindex, zonemaps
from hyperspace_tpu.indexes.zorder import (
    WrittenZSpans,
    ZOrderCoveringIndex,
    ZOrderCoveringIndexConfig,
)
from hyperspace_tpu.io import parquet as pio
from hyperspace_tpu.obs import trace

from test_zorder_reference import _children, _zonemap_capture, _zspans, recaptured
from test_zorder_reference import _session as _session_at

GROUP = pio.INDEX_ROW_GROUP_SIZE


def _session(root, **conf):
    return _session_at(str(root), **conf)


def _source(directory, table, n_files=2):
    os.makedirs(directory)
    n = table.num_rows
    for i in range(n_files):
        lo, hi = i * n // n_files, (i + 1) * n // n_files
        pq.write_table(table.slice(lo, hi - lo), os.path.join(directory, f"part{i}.parquet"))
    return str(directory)


def _version_dirs(root, name):
    return sorted(glob.glob(os.path.join(str(root), name, "v__=*")), key=lambda d: int(d.rsplit("=", 1)[1]))


def _data_files(version_dir):
    return sorted(glob.glob(os.path.join(version_dir, "part-*.parquet")))


def _numbers(n, seed):
    rng = np.random.default_rng(seed)
    return {"a": pa.array(rng.integers(-1000, 1000, n), type=pa.int64()),
            "b": pa.array(rng.integers(0, 50_000, n), type=pa.int64()),
            "p": pa.array(rng.random(n), type=pa.float64())}


def _tables():
    rng = np.random.default_rng(11)
    n = 20_000
    words = ["", "a", "ab", "b"] + [f"w{i:03d}" for i in range(40)]
    strings = dict(_numbers(n, 1), s=pa.array(rng.choice(words, n).tolist(), type=pa.string()))
    holes = rng.random(n) < 0.1
    nulls = dict(_numbers(n, 2), x=pa.array(np.where(holes, np.nan, rng.normal(size=n)), mask=holes,
                                            type=pa.float64()))
    return {
        # name: (table, indexed columns, conf)
        "a_string_column": (pa.table(strings), ["s", "a"], {}),
        "a_column_with_nulls": (pa.table(nulls), ["x", "b"], {}),
        # 150,000 rows x 24 B a row: two files of a full row group and a short one each
        "several_files_short_last_groups": (
            pa.table(_numbers(150_000, 3)), ["a", "b"],
            {C.ZORDER_TARGET_SOURCE_BYTES_PER_PARTITION: 2_000_000}),
    }


@pytest.mark.parametrize("case", sorted(_tables()))
def test_the_sidecar_from_the_write_equals_the_re_reads(case, tmp_path):
    table, indexed, conf = _tables()[case]
    session = _session(tmp_path / "idx", **conf)
    items = session.read.parquet(_source(tmp_path / "src", table))
    Hyperspace(session).create_index(items, ZOrderCoveringIndexConfig("z", indexed, ["p"]))
    root = trace.finished("action.CreateAction")[-1]
    (version_dir,) = _version_dirs(tmp_path / "idx", "z")
    files = _data_files(version_dir)
    rg_rows = [[g.num_rows for g in map(pq.read_metadata(f).row_group, range(pq.read_metadata(f).num_row_groups))]
               for f in files]
    if case == "several_files_short_last_groups":
        assert len(files) >= 2 and all(rows[0] == GROUP and 0 < rows[-1] < GROUP for rows in rg_rows)
    attrs = _zonemap_capture(root).attrs
    assert attrs["zspans_from_write"] == attrs["row_groups"] == sum(map(len, rg_rows))
    assert attrs["zspans_reread"] == 0
    spans, block = _zspans(version_dir)
    assert (spans, block) == recaptured(version_dir, indexed, tmp_path)
    assert [len(spans[os.path.basename(f)]) for f in files] == list(map(len, rg_rows))
    assert all(int(lo, 16) <= int(hi, 16) for file_spans in spans.values() for lo, hi in file_spans)
    assert [s[0] for s in block["specs"]] == ["dict" if c == "s" else "range" for c in indexed]
    # the spans serve: a range over both indexed columns, with and without the index
    first, second = indexed
    low = "b" if first == "s" else -0.5
    query = items.filter((items[first] >= low) & (items[second] < 100)).select(first, second, "p")
    session.enable_hyperspace()
    assert "Type: ZOCI" in query.explain()
    served = query.collect()
    session.disable_hyperspace()
    plain = query.collect()
    order = [(c, "ascending") for c in ("p", second)]
    assert served.num_rows > 0 and served.sort_by(order).equals(plain.sort_by(order))


# -- each fallback takes the re-read, and says so ------------------------------

def _add_a_file(monkeypatch):
    """A parquet file the write knows nothing of appears in the version
    directory before the capture looks at it."""
    real = ZOrderCoveringIndex.write

    def write(self, ctx, data):
        real(self, ctx, data)
        first = _data_files(ctx.index_data_path)[0]
        shutil.copy(first, os.path.join(ctx.index_data_path, "part-00099-zorder.parquet"))

    monkeypatch.setattr(ZOrderCoveringIndex, "write", write)


def _cut_other_row_groups(monkeypatch):
    """The files' writer cuts row groups of 5,000 rows, not the 65,536 the
    hand-off assumed."""
    monkeypatch.setattr(pio, "write_table",
                        lambda path, table: pq.write_table(table, path, row_group_size=5000))


def _few_addresses(n, seed):
    """Eight distinct (a, b): the streamed build's spill makes at most
    eight z-ranges of them, so its trace keeps every span."""
    rng = np.random.default_rng(seed)
    return dict(_numbers(n, seed),
                a=pa.array(rng.choice([-1000, -300, 400, 999], n), type=pa.int64()),
                b=pa.array(rng.choice([0, 40_000], n), type=pa.int64()))


FALLBACKS = {
    # name: (conf, the source's columns, what happens between the sort and the capture, data files, row groups)
    "quantile_encoder": ({C.ZORDER_QUANTILE_ENABLED: True}, _numbers, None, 1, 1),
    "streamed_build": ({C.INDEX_BUILD_MEMORY_BUDGET: 200_000}, _few_addresses, None, None, None),
    "a_file_the_write_does_not_report": ({}, _numbers, _add_a_file, 2, 2),
    "row_groups_cut_otherwise": ({}, _numbers, _cut_other_row_groups, 1, 4),
}


@pytest.mark.parametrize("case", sorted(FALLBACKS))
def test_a_fallback_takes_the_re_read_and_says_so(case, tmp_path, monkeypatch):
    conf, columns, between, n_files, n_groups = FALLBACKS[case]
    session = _session(tmp_path / "idx", **conf)
    items = session.read.parquet(_source(tmp_path / "src", pa.table(columns(20_000, 5))))
    if between is not None:
        between(monkeypatch)
    Hyperspace(session).create_index(items, ZOrderCoveringIndexConfig("z", ["a", "b"], ["p"]))
    monkeypatch.undo()
    root = trace.finished("action.CreateAction")[-1]
    (version_dir,) = _version_dirs(tmp_path / "idx", "z")
    files = _data_files(version_dir)
    groups = sum(pq.read_metadata(f).num_row_groups for f in files)
    assert (n_files or len(files), n_groups or groups) == (len(files), groups)
    zonemap = _zonemap_capture(root)
    attrs = zonemap.attrs
    assert attrs["zspans_reread"] == attrs["row_groups"] == groups and attrs["zspans_from_write"] == 0
    assert all(attrs[key] >= 0.0 for key in ("zspan_fit_s", "zspan_planes_s", "zspan_minmax_s"))
    # the re-read interleaves each file's rows again, under the capture's span
    assert [s.name for s in _children(root, zonemap)] == ["words", "h2d", "kernel", "d2h"] * len(files)
    spans, block = _zspans(version_dir)
    assert sorted(spans) == [os.path.basename(f) for f in files]
    assert sum(map(len, spans.values())) == groups and None not in sum(spans.values(), [])
    assert [s[0] for s in block["specs"]] == ["range", "range"]     # never a quantile spec
    if case == "a_file_the_write_does_not_report":
        return      # the index holds its first file twice: no answer to compare
    _served_equals_plain(session, items)


# -- one write, one capture ---------------------------------------------------

def test_the_hand_off_is_of_one_write_and_is_taken_once(tmp_path, monkeypatch):
    seen = []
    real = zonemaps.capture_safely

    def capture(dir_path, index):
        held = index._written_zspans
        # it is not the index: not in its serialised form, its equality or its hash
        twin = ZOrderCoveringIndex.from_dict(index.to_dict())
        assert isinstance(held, WrittenZSpans) and twin._written_zspans is None
        assert twin == index and hash(twin) == hash(index)
        assert "written" not in str(index.to_dict()).lower()
        real(dir_path, index)
        seen.append((dir_path, index, held))

    monkeypatch.setattr(zonemaps, "capture_safely", capture)
    session = _session(tmp_path / "idx")
    items = session.read.parquet(_source(tmp_path / "src", pa.table(_numbers(20_000, 7))))
    Hyperspace(session).create_index(items, ZOrderCoveringIndexConfig("z", ["a", "b"], ["p"]))
    ((dir_path, index, held),) = seen
    files = _data_files(dir_path)
    assert sorted(held.files) == [os.path.abspath(f) for f in files]
    assert (held.bits, held.nplanes, [s[0] for s in held.specs]) == (16, 1, ["range", "range"])
    assert [rows for rows, _spans in held.files.values()] == [[20_000]]
    # the capture took it: the object hands nothing over a second time
    assert index.take_written_zspans() is None and index._written_zspans is None
    # ... so a later capture by the same object reads the files back, to the same sidecar
    before = _zspans(dir_path)
    assert zonemaps.capture_index_dir(dir_path, index) and _zspans(dir_path) == before
    # a hand-off that names another directory's files is not used for this one
    elsewhere = str(tmp_path / "elsewhere" / os.path.basename(dir_path))
    shutil.copytree(dir_path, elsewhere)
    index._written_zspans = WrittenZSpans(held.bits, held.nplanes, held.specs, {
        path: (rows, [(0, 0)] * len(spans)) for path, (rows, spans) in held.files.items()})
    assert zonemaps.capture_index_dir(elsewhere, index) and _zspans(elsewhere) == before
    assert index._written_zspans is None


# -- every action that sorts a fresh version directory in memory --------------

def _served_equals_plain(session, items):
    query = items.filter((items["a"] >= 0) & (items["b"] < 5000)).select("a", "b", "p")
    session.enable_hyperspace()
    assert "Type: ZOCI" in query.explain()
    served = query.collect()
    session.disable_hyperspace()
    order = [("p", "ascending")]
    assert served.num_rows > 0 and served.sort_by(order).equals(query.collect().sort_by(order))


def test_both_refreshes_hand_their_spans_over(tmp_path):
    session = _session(tmp_path / "idx")
    hs = Hyperspace(session)
    src = _source(tmp_path / "src", pa.table(_numbers(20_000, 9)))
    hs.create_index(session.read.parquet(src), ZOrderCoveringIndexConfig("z", ["a", "b"], ["p"]))
    steps = [("action.RefreshIncrementalAction", C.REFRESH_MODE_INCREMENTAL, 8_000, 1),
             # 98,000 rows after it: a row group and a short one
             ("action.RefreshAction", C.REFRESH_MODE_FULL, 70_000, 2)]
    for i, (action, mode, appended, groups) in enumerate(steps):
        pq.write_table(pa.table(_numbers(appended, 20 + i)), os.path.join(src, f"more{i}.parquet"))
        session.index_manager.clear_cache()
        hs.refresh_index("z", mode)
        root = trace.finished(action)[-1]
        assert root.attrs["status"] == "ok" and root.attrs["index"] == "z"
        zonemap = _zonemap_capture(root)
        version_dir = _version_dirs(tmp_path / "idx", "z")[-1]
        assert sum(pq.read_metadata(f).num_row_groups for f in _data_files(version_dir)) == groups
        assert zonemap.attrs["zspans_from_write"] == zonemap.attrs["row_groups"] == groups, action
        assert zonemap.attrs["zspans_reread"] == 0 and _children(root, zonemap) == [], action
        assert _zspans(version_dir) == recaptured(version_dir, ["a", "b"], tmp_path / f"step{i}"), action
    assert len(_version_dirs(tmp_path / "idx", "z")) == 3
    _served_equals_plain(session, session.read.parquet(src))


def test_an_optimize_hands_its_spans_over(tmp_path):
    """``OptimizeAction`` picks files by bucket id, which a z-ordered
    file's name has none of, so the index's ``optimize`` is driven as the
    action's ``op`` would drive it: rewrite, then capture, under one root."""
    from hyperspace_tpu.indexes.context import IndexerContext
    from hyperspace_tpu.metadata.entry import FileIdTracker

    session = _session(tmp_path / "idx", **{C.ZORDER_TARGET_SOURCE_BYTES_PER_PARTITION: 100_000})
    items = session.read.parquet(_source(tmp_path / "src", pa.table(_numbers(20_000, 13))))
    Hyperspace(session).create_index(items, ZOrderCoveringIndexConfig("z", ["a", "b"], ["p"]))
    (version_dir,) = _version_dirs(tmp_path / "idx", "z")
    small = _data_files(version_dir)
    assert len(small) >= 4
    index = session.index_manager.get_index_log_entry("z").derived_dataset
    assert index.take_written_zspans() is None      # read from the log: it wrote nothing
    index.target_bytes_per_partition = 1 << 30
    compacted = str(tmp_path / "compacted" / "v__=2")
    root = trace.root("action.Optimize", always=True)
    with trace.activate(root):
        index.optimize(IndexerContext(session, FileIdTracker(), compacted), small)
        zonemaps.capture_safely(compacted, index)
    root.finish()
    (only,) = _data_files(compacted)
    assert pq.read_metadata(only).num_rows == 20_000
    zonemap = _zonemap_capture(root)
    assert (zonemap.attrs["zspans_from_write"], zonemap.attrs["zspans_reread"]) == (1, 0)
    assert _children(root, zonemap) == [] and index.take_written_zspans() is None
    assert _zspans(compacted) == recaptured(compacted, ["a", "b"], tmp_path)
    # one sorted run again: the least address of the small files, the greatest of them
    before = sum(_zspans(version_dir)[0].values(), [])
    assert _zspans(compacted)[0] == {os.path.basename(only): [[
        min((lo for lo, _hi in before), key=lambda z: int(z, 16)),
        max((hi for _lo, hi in before), key=lambda z: int(z, 16))]]}


# -- the aggregate capture beside it: the one file's row groups on a pool ------

def _agg_capture(root):
    (span,) = [sp for sp in root.spans
               if sp.name == "sidecar_capture" and sp.attrs["sidecar"] == "aggstate"]
    return span


def _sidecar_bytes(version_dir):
    out = []
    for name in (aggindex.SIDECAR_NAME, aggindex.SAMPLE_NAME):
        with open(os.path.join(version_dir, name), "rb") as fh:
            out.append(fh.read())
    return out


def test_one_file_swept_by_one_worker_or_eight_is_the_same_sidecars(tmp_path, monkeypatch):
    """The z-order build's one file, captured by a task a file (one core)
    and by row-group ranges on a pool (eight): the same bytes of both
    sidecars, and the same metadata answer served from each."""
    from hyperspace_tpu import functions as F
    from hyperspace_tpu import native
    from hyperspace_tpu.execution import pipeline_compiler as PC

    few = pa.array(np.random.default_rng(19).integers(0, 6, 400_000), type=pa.int64())
    src = _source(tmp_path / "src", pa.table(dict(_numbers(400_000, 17), g=few)))
    built = {}
    for cores in (1, 8):
        monkeypatch.setattr(native, "_cores", lambda cores=cores: cores)
        session = _session(tmp_path / f"idx{cores}")
        items = session.read.parquet(src)
        Hyperspace(session).create_index(items, ZOrderCoveringIndexConfig("z", ["a", "b"], ["p", "g"]))
        root = trace.finished("action.CreateAction")[-1]
        (version_dir,) = _version_dirs(tmp_path / f"idx{cores}", "z")
        (only,) = _data_files(version_dir)
        assert pq.read_metadata(only).num_row_groups == 7
        span = _agg_capture(root)
        assert _children(root, span) == []
        want = {"files": 1, "tasks": 1, "split_files": 0, "workers": 1}
        if cores == 8:    # six tasks' worth of rows in seven row groups
            want = {"files": 1, "tasks": 6, "split_files": 1, "workers": 6}
        assert {k: span.attrs[k] for k in want} == want
        assert (span.attrs["turn_wait_s"] == 0) == (cores == 1)
        session.enable_hyperspace()
        aggindex.invalidate_local_cache()
        PC.last_aggplane_stats = {}
        answer = (items.filter(items["a"] >= -1000).group_by("g")
                  .agg(F.count().alias("n"), F.sum("b").alias("sb"), F.max("p").alias("mp")).collect())
        assert PC.last_aggplane_stats.get("mode") == "agg_metadata", PC.last_aggplane_stats
        assert PC.last_aggplane_stats["rows_scanned"] == 0
        built[cores] = (version_dir, only, answer.sort_by("g"))
    assert built[1][2].equals(built[8][2]) and built[1][2].num_rows == 6
    assert built[8][2].column("n").to_pylist() == np.bincount(few.to_numpy()).tolist()
    # the two builds' data files are the same bytes under the same name, so
    # their sidecars can differ in the files' mtime alone: a copy of the
    # pool's directory that keeps the times, swept again on one core
    (dir1, file1, _), (dir8, file8, _) = built[1], built[8]
    assert os.path.basename(file1) == os.path.basename(file8)
    with open(file1, "rb") as one, open(file8, "rb") as eight:
        assert one.read() == eight.read()
    state1, sample1 = _sidecar_bytes(dir1)
    state8, sample8 = _sidecar_bytes(dir8)
    assert sample1 == sample8
    mtime = lambda f: f'"mtime_ns": {os.stat(f).st_mtime_ns}'.encode()
    assert state1.count(mtime(file1)) == 1
    assert state1.replace(mtime(file1), mtime(file8)) == state8
    copy = str(tmp_path / "again" / os.path.basename(dir8))
    shutil.copytree(dir8, copy)
    for name in (aggindex.SIDECAR_NAME, aggindex.SAMPLE_NAME):
        os.remove(os.path.join(copy, name))
    monkeypatch.setattr(native, "_cores", lambda: 1)
    assert aggindex.capture_index_dir(copy, ZOrderCoveringIndex(["a", "b"], [], "", 1 << 30))
    assert _sidecar_bytes(copy) == [state8, sample8]
