"""The four-chip mesh build held to the plain reference.

The benchmark's configuration ``tpch-4chip-mesh`` builds TPC-H LINEITEM
over a mesh of four chips. Here, on four of the suite's host devices and
at a small size, each exchange strategy is forced through the session's
conf and the build is compared with what ``benchmarks/reference.py`` and
``benchmarks/reference_mesh.py`` expect (numpy over the generated
columns, nothing of the program): the bucket files, the 1-device build
byte for byte, the peer counts, and the exchange's spans and counters.
"""

import glob
import hashlib
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
import reference_mesh  # noqa: E402

from hyperspace_tpu import constants as C  # noqa: E402
from hyperspace_tpu.hyperspace import Hyperspace  # noqa: E402
from hyperspace_tpu.indexes import covering_build  # noqa: E402
from hyperspace_tpu.indexes.covering import CoveringIndexConfig  # noqa: E402
from hyperspace_tpu.obs import trace  # noqa: E402
from hyperspace_tpu.parallel import shuffle  # noqa: E402
from hyperspace_tpu.session import HyperspaceSession  # noqa: E402

CHIPS = 4
BUCKETS = 200
N_ORDERS = 3000
SEED = 2**31 + 27
# what the program moves for a row: one 8-byte key rep and the index's
# four columns as a ColumnarBatch holds them — int64, float64, and the
# date32 widened to int64 (4 bytes more than the configuration's 28)
ROW_BYTES = 8 + 32
PAYLOADS = 5     # each its own plane on the wire: one output a payload
EXCHANGE_SPANS = ("exchange_plan", "pack", "exchange", "unpack")
DEVICE_SPANS = ("h2d", "kernel", "d2h")
COUNTERS = ("exchange_h2d_bytes", "exchange_d2h_bytes", "exchange_wire_bytes",
            "exchange_slot_bytes", "exchange_waves")
_BUCKET_FILE = re.compile(r"bucket_(\d+)\.parquet$")


def _build(root, items_dir, devices, strategy=None):
    """One ``create_index`` of the configuration's index ->
    ({bucket: path}, the action's root span, breakdown, telemetry)."""
    session = HyperspaceSession(devices=jax.devices()[:devices])
    session.conf.set(C.INDEX_SYSTEM_PATH, root)
    if strategy is not None:
        session.conf.set(C.BUILD_EXCHANGE_STRATEGY, strategy)
        session.conf.set(C.BUILD_EXCHANGE_TWOSTAGE_HOSTS, 2)
    assert session.conf.num_buckets == BUCKETS
    items = session.read.parquet(items_dir)
    Hyperspace(session).create_index(items, CoveringIndexConfig(
        "l_idx", ["l_orderkey"], ["l_shipdate", "l_quantity", "l_extendedprice"]))
    files = {}
    for f in glob.glob(os.path.join(root, "l_idx", "v__=*", "*.parquet")):
        m = _BUCKET_FILE.search(f)
        if m and not os.path.basename(f).startswith(("_", ".")):
            assert int(m.group(1)) not in files
            files[int(m.group(1))] = f
    return (files, trace.finished("action.CreateAction")[-1],
            dict(covering_build.last_build_breakdown),
            dict(covering_build.last_build_telemetry))


def _sha(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


@pytest.fixture(scope="module")
def table(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("mesh_items"))
    items_dir, cols = datagen.gen_lineitem(tmp, N_ORDERS, 8, SEED)
    return items_dir, cols


@pytest.fixture(scope="module")
def one_chip(table, tmp_path_factory):
    root = str(tmp_path_factory.mktemp("one_chip"))
    return _build(root, table[0], 1)


def _children(root, span):
    return [s for s in root.spans if s.parent_id == span.span_id]


def _one(spans, name):
    found = [s for s in spans if s.name == name]
    assert len(found) == 1, (name, [s.name for s in spans])
    return found[0]


def _inside(child, parent):
    return parent.start_ns <= child.start_ns <= child.end_ns <= parent.end_ns


def test_one_chip_build_records_no_exchange(one_chip):
    files, root, breakdown, telemetry = one_chip
    assert len(files) == BUCKETS
    names = {s.name for s in root.spans}
    assert not names & {"exchange_plan", "pack", "exchange", "unpack"}
    assert not [k for k in root.attrs if k.startswith("exchange_")]
    assert not set(EXCHANGE_SPANS) & set(breakdown)
    assert "shuffle_strategy" not in telemetry


@pytest.mark.parametrize("strategy", ["compact", "host", "twostage"])
def test_mesh_build_against_the_reference(strategy, table, one_chip, tmp_path):
    items_dir, cols = table
    rows = len(cols["l_orderkey"])
    files, root, breakdown, telemetry = _build(
        str(tmp_path / "idx"), items_dir, CHIPS, strategy)
    assert telemetry["shuffle_strategy"] == strategy
    assert telemetry["shuffle_devices"] == CHIPS
    assert breakdown["tail_shards"] == CHIPS     # the sharded tail ran

    # -- the bucket files against the plain reference ----------------------
    assert sorted(files) == list(range(BUCKETS))
    got_rows, got = 0, {name: [] for name in datagen.ITEM_COLS}
    for bucket, path in files.items():
        t = reference.table_cols(pq.read_table(path, columns=list(datagen.ITEM_COLS)))
        keys = t["l_orderkey"]
        assert (reference.bucket_of(keys, BUCKETS) == bucket).all(), bucket
        assert not np.any(keys[1:] < keys[:-1]), bucket
        got_rows += len(keys)
        for name in got:
            got[name].append(t[name])
    assert got_rows == rows
    assert reference.digest({k: np.concatenate(v) for k, v in got.items()}) == \
        reference.digest(cols)

    # -- byte for byte the 1-device build ------------------------------------
    ref_files = one_chip[0]
    assert {b: os.path.basename(f) for b, f in files.items()} == \
        {b: os.path.basename(f) for b, f in ref_files.items()}
    for bucket, path in files.items():
        assert _sha(path) == _sha(ref_files[bucket]), (strategy, bucket)

    # -- peer counts against the reference's matrix --------------------------
    matrix = reference_mesh.peer_matrix(cols["l_orderkey"], BUCKETS, CHIPS)
    assert matrix.sum() == rows
    assert telemetry["shuffle_max_peer_count"] == matrix.max()
    assert telemetry["shuffle_mean_peer_count"] == round(float(matrix.mean()), 1)

    # -- the exchange's spans: real intervals nested under hash_shuffle ------
    assert all(not s.summed for s in root.spans)
    hash_shuffle = _one(root.spans, "hash_shuffle")
    under = _children(root, hash_shuffle)
    device_leg = strategy != "host"
    want = set(EXCHANGE_SPANS) - (set() if device_leg else {"unpack"})
    parts = {name: _one(under, name) for name in want}
    assert all(_inside(s, hash_shuffle) for s in under)
    ordered = [parts[n] for n in EXCHANGE_SPANS if n in parts]
    assert all(a.end_ns <= b.start_ns for a, b in zip(ordered, ordered[1:]))
    assert sum(s.duration_s for s in under) <= hash_shuffle.duration_s
    legs = _children(root, parts["exchange"])
    if device_leg:
        assert [s.name for s in sorted(legs, key=lambda s: s.start_ns)] == list(DEVICE_SPANS)
        assert all(_inside(s, parts["exchange"]) for s in legs)
        assert sum(s.duration_s for s in legs) <= parts["exchange"].duration_s
    else:
        assert legs == []
    plan = parts["exchange_plan"].attrs
    assert plan["strategy"] == strategy and plan["devices"] == CHIPS
    assert plan["cap"] >= matrix.max() or strategy == "twostage"
    assert plan["skew_ratio"] == round(matrix.max() / matrix.mean(), 2)
    # one measurement: breakdown and telemetry hold the spans' own seconds
    for key, name in shuffle.STAGE_SECONDS_KEYS.items():
        seconds = parts[name].duration_s if name in parts else 0.0
        assert breakdown.get(name, 0.0) == pytest.approx(seconds, abs=1e-9)
        assert telemetry["shuffle_" + key] == pytest.approx(seconds, abs=1e-9)

    # -- the byte counters against the reference's matrix --------------------
    counters = {k: root.attrs[k] for k in COUNTERS}
    assert counters["exchange_waves"] == 1
    wire = reference_mesh.off_chip_rows(matrix) * ROW_BYTES
    if device_leg:
        assert counters["exchange_wire_bytes"] == wire > 0
        assert counters["exchange_slot_bytes"] >= wire
        # packed on the host: what goes up is the slots
        assert counters["exchange_h2d_bytes"] == counters["exchange_slot_bytes"]
        assert counters["exchange_d2h_bytes"] >= rows * ROW_BYTES
        by_name = {s.name: s for s in legs}
        assert by_name["h2d"].attrs["bytes"] == counters["exchange_h2d_bytes"]
        assert by_name["d2h"].attrs["bytes"] == counters["exchange_d2h_bytes"]
        # the fetch: every shard's copy started before the first read,
        # every shard read where it landed, nothing copied a second time
        fetch = by_name["d2h"].attrs
        assert fetch["shards"] == fetch["started"] == CHIPS * PAYLOADS
        assert fetch["assembled_bytes"] == 0
    else:
        assert [counters[k] for k in COUNTERS[:4]] == [0, 0, 0, 0]
    assert telemetry["shuffle_wire_bytes"] == counters["exchange_wire_bytes"]
    assert telemetry["shuffle_slot_bytes"] == counters["exchange_slot_bytes"]


def test_the_compact_program_is_traced_once(table, tmp_path):
    """Two builds of one table run the exchange under ONE jitted program
    (``jit__compact_program`` on the profiler's "XLA Modules" line): the
    second build traces nothing."""
    _build(str(tmp_path / "a"), table[0], CHIPS, "compact")
    traced = shuffle._compact_program._cache_size()
    _build(str(tmp_path / "b"), table[0], CHIPS, "compact")
    assert shuffle._compact_program._cache_size() == traced
    assert shuffle._compact_program.__name__ == "_compact_program"


def test_foreign_buckets_go_through_the_single_tail(table, tmp_path, monkeypatch, caplog):
    """Bucket ids that diverge from the exchange's plan put rows of one
    bucket into two shards' slices. Two shard tails may never write one
    file: the build warns, writes through the single tail what the ids
    say — as a 1-device build does — sorted, and loses no row."""
    real = covering_build._hash_shuffle

    def diverged(ctx, batch, indexed_cols, num_buckets):
        buckets, reps, batch, offs = real(ctx, batch, indexed_cols, num_buckets)
        buckets = buckets.copy()
        buckets[::7] = (buckets[::7] + 1) % num_buckets
        return buckets, reps, batch, offs

    monkeypatch.setattr(covering_build, "_hash_shuffle", diverged)
    with caplog.at_level("WARNING", logger="hyperspace_tpu.build"):
        files, _root, breakdown, _t = _build(str(tmp_path / "mesh"), table[0], CHIPS, "compact")
    assert "single tail" in caplog.text
    assert "tail_shards" not in breakdown
    got = {name: [] for name in datagen.ITEM_COLS}
    for path in files.values():
        t = reference.table_cols(pq.read_table(path, columns=list(datagen.ITEM_COLS)))
        assert not np.any(t["l_orderkey"][1:] < t["l_orderkey"][:-1])
        for name in got:
            got[name].append(t[name])
    assert reference.digest({k: np.concatenate(v) for k, v in got.items()}) == \
        reference.digest(table[1])
