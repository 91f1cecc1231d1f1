"""The z-order covering build held to the plain reference of its layout.

The benchmark's configuration ``tpch-zorder-1chip`` builds a z-order
covering index on TPC-H Q6's three range columns. Here, at a small size
on one host device, the same ``create_index`` is compared with what
``benchmarks/reference_zorder.py`` expects (numpy over the generated
columns, nothing of the program): the program's 16-bit words and planes,
the written files' order within and across files, a Q6-shaped range
answer served as ``ZOCI``, and the spans and counters the build records.
"""

import datetime
import glob
import json
import os
import shutil
import sys

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import jax

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import datagen  # noqa: E402
import reference  # noqa: E402
import reference_zorder as rz  # noqa: E402

from hyperspace_tpu import constants as C  # noqa: E402
from hyperspace_tpu.hyperspace import Hyperspace  # noqa: E402
from hyperspace_tpu.indexes import covering_build, zonemaps  # noqa: E402
from hyperspace_tpu.indexes.covering import CoveringIndexConfig  # noqa: E402
from hyperspace_tpu.indexes.zorder import ZOrderCoveringIndex, ZOrderCoveringIndexConfig  # noqa: E402
from hyperspace_tpu.io.columnar import Column  # noqa: E402
from hyperspace_tpu.obs import trace  # noqa: E402
from hyperspace_tpu.ops import sort as sort_ops  # noqa: E402
from hyperspace_tpu.ops.zorder import ZOrderEncoder  # noqa: E402
from hyperspace_tpu.session import HyperspaceSession  # noqa: E402

N_ORDERS = 6000                     # 24,000 rows
Q6_COLS = ["l_shipdate", "l_discount", "l_quantity"]     # date32, float64 with 0.0, int64
COLS = Q6_COLS + ["l_extendedprice"]
SEEDS = [2**31 + 41, 7, 3400000129]
_EPOCH = datetime.date(1970, 1, 1)
Z_STAGES = ("zorder_encode", "zorder_interleave", "zorder_sort", "take", "to_arrow", "write")


def _session(root, **conf):
    session = HyperspaceSession(devices=jax.devices()[:1])
    session.conf.set(C.INDEX_SYSTEM_PATH, root)
    for key, value in conf.items():
        session.conf.set(key, value)
    return session


def _data_files(root, name):
    return sorted(f for f in glob.glob(os.path.join(root, name, "v__=*", "*.parquet"))
                  if not os.path.basename(f).startswith(("_", ".")))


def _build(root, items_dir, indexed, **conf):
    """One z-order ``create_index`` under the program's defaults (and
    ``conf``) -> (session, data files in name order, the action's root)."""
    session = _session(root, **conf)
    items = session.read.parquet(items_dir)
    included = [c for c in COLS if c not in indexed]
    Hyperspace(session).create_index(items, ZOrderCoveringIndexConfig("z_idx", indexed, included))
    return session, _data_files(root, "z_idx"), trace.finished("action.CreateAction")[-1]


@pytest.fixture(scope="module", params=SEEDS, ids=lambda s: f"seed{s}")
def table(request, tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("z_items"))
    items_dir, cols = datagen.gen_lineitem(tmp, N_ORDERS, 8, request.param, cols=COLS)
    assert 0.0 in cols["l_discount"]
    return items_dir, cols


@pytest.fixture(scope="module", params=[1, 2, 3], ids=lambda k: f"{k}col")
def built(request, table, tmp_path_factory):
    indexed = Q6_COLS[: request.param]
    root = str(tmp_path_factory.mktemp("z_index"))
    session, files, action = _build(root, table[0], indexed)
    return indexed, table[0], table[1], session, files, action


def _inversions_across(files, indexed, cols):
    """(rows in all, adjacent pairs whose reference address decreases,
    inside a file and across each file boundary)."""
    mins, maxs = rz.min_max(cols, indexed)
    return rz.inversions_across(
        (reference.table_cols(pq.read_table(f, columns=indexed)) for f in files), indexed, mins, maxs)


def test_the_files_rows_lie_in_the_references_z_order(built):
    indexed, _dir, cols, _session_, files, _action = built
    assert len(files) == 1          # 1 GiB of index bytes a file: the default
    rows, inversions = _inversions_across(files, indexed, cols)
    assert rows == len(cols["l_shipdate"]) and inversions == 0
    # every source row is in the index once, payload bit for bit
    written = reference.table_cols(pq.read_table(files[0], columns=COLS))
    assert reference.digest(written) == reference.digest(cols)


def _columns(cols, indexed):
    arrow = {"l_shipdate": lambda v: pa.array(v, type=pa.date32())}
    return [Column.from_arrow(arrow.get(c, pa.array)(cols[c])) for c in indexed]


def test_the_programs_words_and_planes_equal_the_references(built):
    indexed, _dir, cols, *_ = built
    encoder, encs = ZOrderEncoder.fit(_columns(cols, indexed), rz.BITS, False, 0.01)
    mins, maxs = rz.min_max(cols, indexed)
    for j, c in enumerate(indexed):
        ours = encoder._words(encs[j], encoder.specs[j])
        theirs = rz.words(cols[c], mins[j], maxs[j])
        assert np.array_equal(ours.astype(np.uint64), theirs), c
        # the column's least and greatest value, and a tie, among them
        assert theirs.min() == 0 and theirs.max() >= 65534
        assert len(np.unique(theirs)) < len(theirs)
    planes = encoder.planes_from_encodings(encs)
    total = len(indexed) * rz.BITS
    n_planes = -(-total // 32)
    assert planes.shape == (n_planes, len(cols[indexed[0]]))
    packed = rz.z_address(cols, indexed, mins, maxs) << np.uint64(32 * n_planes - total)
    for i in range(n_planes):
        want = (packed >> np.uint64(32 * (n_planes - 1 - i))) & np.uint64(0xFFFFFFFF)
        assert np.array_equal(planes[i].astype(np.uint64), want), i


def _q6(session, items_dir, year, lo, hi, quantity):
    items = session.read.parquet(items_dir)
    d0, d1 = datetime.date(year, 1, 1), datetime.date(year + 1, 1, 1)
    query = items.filter(
        (items["l_shipdate"] >= d0) & (items["l_shipdate"] < d1)
        & (items["l_discount"] >= lo) & (items["l_discount"] <= hi)
        & (items["l_quantity"] < quantity)).select(*COLS)
    return query, ((d0 - _EPOCH).days, (d1 - _EPOCH).days, lo, hi, quantity)


@pytest.mark.parametrize("year,hundredths,quantity", [(1994, 6, 24), (1997, 2, 25)])
def test_a_q6_shaped_range_answer_equals_the_references(built, year, hundredths, quantity):
    _indexed, items_dir, cols, session, _files, _action = built
    session.enable_hyperspace()
    query, params = _q6(session, items_dir, year, (hundredths - 1) / 100.0,
                        (hundredths + 1) / 100.0, quantity)
    plan = query.explain()
    assert "Hyperspace(Type: ZOCI," in plan and "Name: z_idx" in plan
    mask = rz.range_rows(cols, *params)
    assert 0 < mask.sum() < len(mask)
    got = reference.table_cols(query.collect())
    assert reference.digest(got) == reference.digest({c: v[mask] for c, v in cols.items()})


@pytest.fixture(scope="module")
def many_files(table, tmp_path_factory):
    """The index cut into files of 200,000 B of its in-memory bytes."""
    root = str(tmp_path_factory.mktemp("z_many"))
    session, files, action = _build(
        root, table[0], Q6_COLS, **{C.ZORDER_TARGET_SOURCE_BYTES_PER_PARTITION: 200_000})
    return table[0], table[1], session, files, action


def test_the_order_holds_from_file_to_file(many_files):
    _dir, cols, _session_, files, action = many_files
    assert len(files) >= 3 and action.attrs["index_files"] == len(files)
    rows, inversions = _inversions_across(files, Q6_COLS, cols)
    assert rows == len(cols["l_shipdate"]) and inversions == 0
    # files in name order are z-ranges one after another: each file's
    # least address is no less than the greatest of the file before it
    mins, maxs = rz.min_max(cols, Q6_COLS)
    spans = [rz.z_address(reference.table_cols(pq.read_table(f, columns=Q6_COLS)), Q6_COLS, mins, maxs)
             for f in files]
    assert all(a.max() <= b.min() for a, b in zip(spans, spans[1:]))


def test_a_range_answer_over_many_files_equals_the_references(many_files):
    items_dir, cols, session, _files, _action = many_files
    session.enable_hyperspace()
    query, params = _q6(session, items_dir, 1995, 0.04, 0.06, 24)
    assert "Hyperspace(Type: ZOCI," in query.explain()
    mask = rz.range_rows(cols, *params)
    got = reference.table_cols(query.collect())
    assert mask.sum() > 0
    assert reference.digest(got) == reference.digest({c: v[mask] for c, v in cols.items()})


# -- the build's account ------------------------------------------------------

def _children(root, span):
    return sorted((s for s in root.spans if s.parent_id == span.span_id), key=lambda s: s.start_ns)


def _one(spans, name):
    found = [s for s in spans if s.name == name]
    assert len(found) == 1, (name, [s.name for s in spans])
    return found[0]


def _inside(child, parent):
    return parent.start_ns <= child.start_ns <= child.end_ns <= parent.end_ns


def test_a_zorder_create_names_its_stages_once_each(built, tmp_path):
    indexed, items_dir, cols, _session_, files, root = built
    top = _children(root, root)
    stages = [_one(top, name) for name in Z_STAGES]
    # one after another, each with an interval inside the root's
    assert all(_inside(s, root) and s.end_ns > s.start_ns for s in stages)
    assert all(a.end_ns <= b.start_ns for a, b in zip(stages, stages[1:]))
    assert not {"hash_shuffle", "sort", "dict_probe"} & {s.name for s in root.spans}
    interleave = _one(top, "zorder_interleave")
    kids = _children(root, interleave)
    assert [s.name for s in kids] == ["words", "h2d", "kernel", "d2h"]
    assert all(_inside(s, interleave) for s in kids)
    assert sum(s.duration_s for s in kids) <= interleave.duration_s
    rows = len(cols["l_shipdate"])
    padded = 1 << (rows - 1).bit_length()
    assert kids[1].attrs["bytes"] == 4 * len(indexed) * padded
    assert kids[3].attrs["bytes"] == 4 * -(-len(indexed) * 16 // 32) * padded
    # 24,000 rows sort on the host: the host arms open nothing
    assert _children(root, _one(top, "zorder_sort")) == []
    write = _one(top, "write")
    assert write.attrs["files"] == len(files) == root.attrs["index_files"]
    assert write.attrs["rows"] == rows == root.attrs["rows"]
    assert write.attrs["bytes"] == root.attrs["index_bytes"] == sum(map(os.path.getsize, files))
    assert root.attrs["h2d_bytes"] > 0 and root.attrs["d2h_bytes"] > 0
    # the named stages cover the build. The limit is a share of the wall
    # clock of a 50 ms build, and a thread that loses its core between
    # two stages for 5 ms is unnamed time too; a stage that has no name
    # is unnamed in every build. So under load the best of three builds
    # is held to the limit
    covered = [root.children_union_s() / root.duration_s]
    while covered[-1] < 0.9 and len(covered) < 3:
        again = _build(str(tmp_path / f"again{len(covered)}"), items_dir, indexed)[2]
        covered.append(again.children_union_s() / again.duration_s)
    assert max(covered) >= 0.9, covered


def _zonemap_capture(root):
    return _one([s for s in root.spans if s.attrs.get("sidecar") == "zonemap"], "sidecar_capture")


def test_the_zspan_capture_says_where_its_spans_came_from(built):
    """A plain create sorts every row it writes in one piece, so the
    capture takes each row group's span from the write and reads nothing
    back: no second interleave, no seconds of a re-read."""
    _indexed, _dir, cols, _session_, _files, root = built
    zonemap = _zonemap_capture(root)
    attrs = zonemap.attrs
    assert attrs["row_groups"] == -(-len(cols["l_shipdate"]) // 65536)
    assert attrs["zspans_from_write"] == attrs["row_groups"] and attrs["zspans_reread"] == 0
    assert not {"zspan_fit_s", "zspan_planes_s", "zspan_minmax_s"} & set(attrs)
    assert _children(root, zonemap) == []
    # jit__interleave is dispatched once a build: under zorder_interleave
    for name in ("words", "h2d", "kernel", "d2h"):
        assert _one(root.spans, name).parent_id == _one(root.spans, "zorder_interleave").span_id


def _zspans(sidecar_dir):
    """What the z-span capture wrote into a version directory's sidecar:
    ({file: its row groups' spans}, the ``zorder`` block)."""
    with open(os.path.join(sidecar_dir, zonemaps.SIDECAR_NAME), encoding="utf-8") as f:
        doc = json.load(f)
    return {name: entry["rg_zspans"] for name, entry in doc["files"].items()}, doc["zorder"]


def recaptured(version_dir, indexed, tmp_path):
    """The z-spans of a copy of ``version_dir`` captured by an index
    object that wrote nothing: the two-pass re-read."""
    copy = str(tmp_path / "recaptured" / os.path.basename(version_dir))
    shutil.copytree(version_dir, copy)
    os.remove(os.path.join(copy, zonemaps.SIDECAR_NAME))
    assert zonemaps.capture_index_dir(copy, ZOrderCoveringIndex(indexed, [], "", 1 << 30))
    return _zspans(copy)


def test_the_sidecar_from_the_write_equals_the_re_reads(built, tmp_path):
    indexed, _dir, cols, _session_, files, _root = built
    version_dir = os.path.dirname(files[0])
    spans, block = _zspans(version_dir)
    assert (spans, block) == recaptured(version_dir, indexed, tmp_path)
    assert block["columns"] == indexed and block["bits"] == 16
    assert block["nplanes"] == -(-len(indexed) * 16 // 32)
    assert [s[0] for s in block["specs"]] == ["range"] * len(indexed)
    # and they are the reference's: the least and greatest address of the rows
    mins, maxs = rz.min_max(cols, indexed)
    z = rz.z_address(cols, indexed, mins, maxs) << np.uint64(32 * block["nplanes"] - 16 * len(indexed))
    assert spans == {os.path.basename(files[0]): [[format(int(z.min()), "x"), format(int(z.max()), "x")]]}


@pytest.fixture(scope="module")
def several_row_groups(tmp_path_factory):
    """400,000 rows: one file of seven row groups, the last one short."""
    tmp = str(tmp_path_factory.mktemp("z_groups"))
    items_dir, cols = datagen.gen_lineitem(tmp, 100_000, 4, 3500000011, cols=COLS)
    session, files, root = _build(tmp + "/index", items_dir, Q6_COLS)
    return items_dir, cols, session, files, root


@pytest.mark.parametrize("year,hundredths,quantity", [(1993, 3, 24), (1995, 8, 25), (1997, 5, 24)])
def test_a_range_answer_pruned_by_the_writes_spans_equals_the_references(
        several_row_groups, year, hundredths, quantity):
    items_dir, cols, session, files, root = several_row_groups
    groups = pq.read_metadata(files[0]).num_row_groups
    attrs = _zonemap_capture(root).attrs
    assert len(files) == 1 and groups == 7
    assert attrs["zspans_from_write"] == groups and attrs["zspans_reread"] == 0
    session.enable_hyperspace()
    query, params = _q6(session, items_dir, year, (hundredths - 1) / 100.0,
                        (hundredths + 1) / 100.0, quantity)
    assert "Hyperspace(Type: ZOCI," in query.explain()
    mask = rz.range_rows(cols, *params)
    assert mask.sum() > 0
    got = reference.table_cols(query.collect())
    assert reference.digest(got) == reference.digest({c: v[mask] for c, v in cols.items()})
    read = zonemaps.last_prune_stats
    assert read["zonemap_files_sidecar"] == 1 and read["row_groups_total"] == groups
    assert 0 < read["row_groups_kept"] < groups, read


def test_each_row_groups_span_is_the_references_least_and_greatest_address(several_row_groups):
    _dir, cols, _session_, files, _root = several_row_groups
    spans, block = _zspans(os.path.dirname(files[0]))
    mins, maxs = rz.min_max(cols, Q6_COLS)
    written = reference.table_cols(pq.read_table(files[0], columns=Q6_COLS))
    z = rz.z_address(written, Q6_COLS, mins, maxs) << np.uint64(32 * block["nplanes"] - 16 * len(Q6_COLS))
    want = [[format(int(z[at:at + 65536].min()), "x"), format(int(z[at:at + 65536].max()), "x")]
            for at in range(0, len(z), 65536)]
    assert spans == {os.path.basename(files[0]): want}
    assert len(want) == 7 and len(z) % 65536 != 0


def test_the_breakdown_is_the_same_measurement(table, tmp_path):
    _session_, _files, root = _build(str(tmp_path / "idx"), table[0], Q6_COLS)
    breakdown = dict(covering_build.last_build_breakdown)
    assert set(Z_STAGES) | {"scan", "sidecar_capture"} <= set(breakdown)
    stages = root.stage_seconds()
    for name in Z_STAGES:
        assert stages[name] == pytest.approx(breakdown[name], abs=1e-9), name


def test_the_device_arm_of_the_sort_names_its_transfers(table, tmp_path, monkeypatch):
    monkeypatch.setattr(sort_ops, "_HOST_SORT_MAX_ROWS", 1000)      # an override wins
    _session_, files, root = _build(str(tmp_path / "idx"), table[0], Q6_COLS)
    sort = _one(_children(root, root), "zorder_sort")
    kids = _children(root, sort)
    assert [s.name for s in kids] == ["h2d", "kernel", "d2h"]
    assert all(_inside(s, sort) for s in kids)
    padded = 1 << (len(table[1]["l_shipdate"]) - 1).bit_length()
    assert kids[0].attrs["bytes"] == 2 * 4 * padded       # two planes up
    assert kids[2].attrs["bytes"] in (4 * padded, 8 * padded)   # the row index back
    assert _inversions_across(files, Q6_COLS, table[1])[1] == 0


def test_the_streamed_build_records_the_same_stage_names(table, tmp_path):
    """Beyond the build memory budget the rows go through the spill and
    the merge, which run the same steps a wave or a z-range."""
    _session_, files, root = _build(
        str(tmp_path / "idx"), table[0], Q6_COLS, **{C.INDEX_BUILD_MEMORY_BUDGET: 200_000})
    names = {s.name for s in _children(root, root)}
    assert set(Z_STAGES) - {"zorder_encode"} <= names
    assert root.attrs["index_files"] == len(files)
    assert _inversions_across(files, Q6_COLS, table[1]) == (len(table[1]["l_shipdate"]), 0)


def test_a_covering_creates_span_names_are_what_they_were(table, tmp_path):
    session = _session(str(tmp_path / "idx"))
    items = session.read.parquet(table[0])
    Hyperspace(session).create_index(items, CoveringIndexConfig(
        "l_idx", ["l_quantity"], ["l_shipdate", "l_extendedprice"]))
    root = trace.finished("action.CreateAction")[-1]
    top = [s.name for s in _children(root, root)]
    assert top == ["validate", "begin_log", "resolve", "scan", "dict_probe", "hash_shuffle", "sort",
                   "write", "sidecar_capture", "sidecar_capture", "log_entry", "log_commit",
                   "publish_event"]
    assert sorted({s.name for s in root.spans} - set(top) - {root.name}) == [
        "bucket_sorts", "host_hash", "key_reps", "partition", "to_arrow"]
    assert not {s.name for s in root.spans} & {"zorder_encode", "zorder_interleave", "zorder_sort", "take", "words"}
