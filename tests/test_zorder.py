"""Z-order covering index tests.

Mirrors ``zordercovering/ZOrderFieldTest.scala`` (encoding order
properties) and ``E2EHyperspaceZOrderIndexTest.scala`` (serve + results
differential; any indexed column may be constrained).
"""

import glob
import json
import os
import pathlib

import jax
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu import constants as C
from hyperspace_tpu import native
from hyperspace_tpu.hyperspace import Hyperspace
from hyperspace_tpu.indexes.zorder import ZOrderCoveringIndexConfig
from hyperspace_tpu.io.columnar import Column
from hyperspace_tpu.obs import trace
from hyperspace_tpu.ops import hash as hash_ops
from hyperspace_tpu.ops import pad_len
from hyperspace_tpu.ops import zorder as zops
from hyperspace_tpu.session import HyperspaceSession


@pytest.fixture
def hs(session):
    return Hyperspace(session)


def sorted_table(t):
    return t.sort_by([(c, "ascending") for c in t.column_names])


class TestZAddress:
    def test_order_encoding_preserves_order(self):
        from hyperspace_tpu.io.columnar import Column
        from hyperspace_tpu.ops.zorder import order_u64_np

        ints = Column.from_arrow(pa.array([-5, -1, 0, 3, 2**40], type=pa.int64()))
        e = order_u64_np(ints)
        assert (e[:-1] < e[1:]).all()
        floats = Column.from_arrow(pa.array([-1e9, -1.5, -0.0, 0.25, 3e7]))
        e = order_u64_np(floats)
        assert (e[:-1] < e[1:]).all()
        strings = Column.from_arrow(pa.array(["b", "a", "c"]))
        e = order_u64_np(strings)
        assert e[1] < e[0] < e[2]

    def test_null_sorts_first(self):
        from hyperspace_tpu.io.columnar import Column
        from hyperspace_tpu.ops.zorder import order_u64_np

        c = Column.from_arrow(pa.array([5, None, -3], type=pa.int64()))
        e = order_u64_np(c)
        assert e[1] == 0 and e[1] < e[2] < e[0]

    def test_z_permutation_locality(self):
        """Sorting by z-address groups near points of BOTH dimensions: for
        a grid, each contiguous quarter of the z-order touches at most a
        quadrant-ish bounding box, unlike a single-column sort."""
        from hyperspace_tpu.io.columnar import Column
        from hyperspace_tpu.ops.zorder import z_order_permutation

        n = 32
        xs, ys = np.meshgrid(np.arange(n), np.arange(n))
        xs, ys = xs.ravel(), ys.ravel()
        cx = Column.from_arrow(pa.array(xs, type=pa.int64()))
        cy = Column.from_arrow(pa.array(ys, type=pa.int64()))
        perm = z_order_permutation([cx, cy], bits=8)
        quarter = len(perm) // 4
        for q in range(4):
            idx = perm[q * quarter : (q + 1) * quarter]
            span_x = xs[idx].max() - xs[idx].min()
            span_y = ys[idx].max() - ys[idx].min()
            # each z-order quarter of a 32x32 grid stays within a half-ish
            # range in both dims (a column sort would span the full 0..31
            # in the secondary dim)
            assert span_x <= n // 2 + 1 and span_y <= n // 2 + 1, (
                q, span_x, span_y,
            )


class TestZOrderIndexE2E:
    def test_create_and_serve_any_indexed_col(self, session, hs, sample_parquet):
        df = session.read.parquet(sample_parquet)
        hs.create_index(
            df,
            ZOrderCoveringIndexConfig("zidx", ["clicks", "imprs"], ["query"]),
        )
        listing = hs.indexes()
        assert listing.column("name").to_pylist() == ["zidx"]
        session.enable_hyperspace()
        # predicate on the SECOND indexed column only — covering rule would
        # refuse (first-indexed-col), z-order rule must accept
        q = lambda d: d.filter(d["imprs"] >= 50).select("imprs", "query")
        plan = q(df).explain()
        assert "Hyperspace(Type: ZOCI, Name: zidx" in plan
        session.disable_hyperspace()
        base = q(df).collect()
        session.enable_hyperspace()
        got = q(df).collect()
        assert sorted_table(got).equals(sorted_table(base))
        assert got.num_rows > 0

    def test_multi_partition_write(self, session, hs, sample_parquet):
        session.conf.set(C.ZORDER_TARGET_SOURCE_BYTES_PER_PARTITION, 2000)
        df = session.read.parquet(sample_parquet)
        hs.create_index(df, ZOrderCoveringIndexConfig("zidx", ["clicks"]))
        entry = session.index_manager.get_index_log_entry("zidx")
        assert len(entry.content.files) > 1

    def test_refresh_incremental(self, session, hs, sample_parquet):
        import os

        df = session.read.parquet(sample_parquet)
        hs.create_index(
            df, ZOrderCoveringIndexConfig("zidx", ["clicks"], ["query"])
        )
        extra = pa.table(
            {
                "date": ["2019-01-01"] * 4,
                "rguid": ["a", "b", "c", "d"],
                "clicks": pa.array([11, 12, 13, 14], pa.int64()),
                "query": ["zz"] * 4,
                "imprs": pa.array([1, 2, 3, 4], pa.int64()),
            }
        )
        pq.write_table(extra, os.path.join(sample_parquet, "part-z.parquet"))
        hs.refresh_index("zidx", "incremental")
        session.enable_hyperspace()
        session.index_manager.clear_cache()
        df2 = session.read.parquet(sample_parquet)
        q = lambda d: d.filter(d["clicks"] <= 20).select("clicks", "query")
        plan = q(df2).explain()
        assert "ZOCI" in plan
        session.disable_hyperspace()
        base = q(df2).collect()
        session.enable_hyperspace()
        assert sorted_table(q(df2).collect()).equals(sorted_table(base))


# -- a column's encoding and its words as native passes, held to their twins ----------------
#
# From the native hash's row threshold up, ``order_u64_np`` is one
# threaded native pass (``native.order_u64``) and a "range"/"dict"
# column's words are another (``native.zorder_words``), written straight
# into the block the interleave uploads; ``order_u64_numpy`` and
# ``range_words_np`` (plus the fill ``top`` past the rows) are their
# numpy twins, byte for byte.

needs_native = pytest.mark.skipif(native.load() is None, reason="the native kernels did not build here")

I64 = np.iinfo(np.int64)
F64 = np.finfo(np.float64)
FLOAT_EDGES = np.array(
    [-0.0, 0.0, np.nan, -np.nan, np.inf, -np.inf, F64.max, -F64.max, F64.tiny, -F64.tiny,
     5e-324, -5e-324, 2.5e-320, -2.5e-320, 1.0, -1.0], dtype=np.float64)
# NaNs with payloads, both signs: their bits are ordered as they lie
FLOAT_EDGES = np.concatenate([FLOAT_EDGES, np.array(
    [0x7FF0000000000001, 0xFFF8000000000123, 0x7FFFFFFFFFFFFFFF], dtype=np.uint64).view(np.float64)])
INT_EDGES = np.array([I64.min, I64.max, I64.min + 1, I64.max - 1, -1, 0, 1, 1 << 32, -(1 << 32)], dtype=np.int64)
N_ODD = 200_003     # several threads' chunks, and a multiple of none of them


def _column(case, n=N_ODD, seed=44):
    """A numeric Column of ``n`` rows: the case's edge values first."""
    rng = np.random.default_rng([seed, n])
    if case in ("int64", "int64_nulls", "one_value"):
        values = np.where(rng.integers(0, 3, n) == 0,
                          rng.integers(I64.min, I64.max, n, dtype=np.int64, endpoint=True),
                          rng.integers(-50, 50, n)).astype(np.int64)
        if case == "one_value":
            values[:] = -7
        else:
            values[:len(INT_EDGES)] = INT_EDGES
        validity = rng.random(n) > 0.25 if case == "int64_nulls" else None
        if validity is not None:
            values[~validity] = 0
        return Column("numeric", pa.int64(), values=values, validity=validity)
    if case in ("float64", "float64_nulls"):
        values = np.where(rng.integers(0, 2, n) == 0, rng.normal(size=n) * 1e300,
                          rng.integers(-1 << 62, 1 << 62, n).view(np.float64))
        values[:len(FLOAT_EDGES)] = FLOAT_EDGES
        validity = rng.random(n) > 0.25 if case == "float64_nulls" else None
        return Column("numeric", pa.float64(), values=values, validity=validity)
    if case in ("date32", "timestamp"):
        days = rng.integers(-30_000, 30_000, n)
        if case == "date32":
            arr = pa.array(days.astype("datetime64[D]"), type=pa.date32())
        else:
            arr = pa.array(days * 86_400_000_003 + rng.integers(0, 1000, n), type=pa.timestamp("ns"))
        col = Column.from_arrow(arr)
        assert col.values.dtype == np.int64      # as the column holds dates and timestamps
        return col
    raise AssertionError(case)


def _guarded_row(row_len, guard=32, fill=0xDEADBEEF):
    flat = np.full(row_len + guard, fill, dtype=np.uint32)
    return flat[:row_len], flat[row_len:]


@needs_native
@pytest.mark.parametrize("case", ["int64", "int64_nulls", "float64", "float64_nulls", "date32", "timestamp",
                                  "one_value", "short"])
def test_the_native_encoding_and_words_equal_their_twins(case):
    """``native.order_u64`` is ``order_u64_numpy`` and
    ``native.zorder_words`` is ``range_words_np`` plus the fill ``top``,
    byte for byte: int64 with both extremes, float64's signed zeros,
    NaNs, infinities, subnormals and largest finite values, dates and
    timestamps as their int64 view, nulls, a row count no chunk divides,
    one value (range 0, scale 0) and a few rows (one thread)."""
    col = _column("int64", n=13) if case == "short" else _column(case)
    n = len(col)
    enc = native.order_u64(col.values, col.validity)
    twin = zops.order_u64_numpy(col)
    assert enc.dtype == twin.dtype == np.uint64 and enc.tobytes() == twin.tobytes()
    if col.validity is not None:
        assert (enc[~col.validity] == 0).all() and (enc[col.validity] >= 1).all()
    bits = 16
    encoder = zops.ZOrderEncoder(bits, [])
    top = (1 << bits) - 1
    lo, hi = int(twin.min()), int(twin.max())
    # the fitted spec, one that leaves rows below min (the subtraction
    # wraps as numpy's does), one of range 0, the whole uint64 range
    for spec in (("range", np.uint64(lo), np.uint64(hi)), ("range", np.uint64((lo + hi) // 2), np.uint64(hi)),
                 ("range", np.uint64(hi), np.uint64(hi)), ("range", np.uint64(0), np.uint64(2**64 - 1)),
                 ("dict", ["x"] * 5)):
        mn, scale = encoder._range(spec)
        if spec[0] == "range" and spec[1] == spec[2]:
            assert scale == 0.0
        row_len = pad_len(n)
        out, after = _guarded_row(row_len)
        assert native.zorder_words(enc, mn, scale, top, out) is True
        want = zops.range_words_np(twin, mn, scale, top)
        assert want.dtype == np.uint32 and out[:n].tobytes() == want.tobytes()
        assert out[:n].tobytes() == encoder._words(twin, spec).tobytes()
        assert (out[n:] == top).all() and (after == 0xDEADBEEF).all()


@needs_native
def test_what_the_passes_cannot_read_in_place_they_leave_to_the_twin():
    values = np.arange(10_000, dtype=np.int64)
    assert native.order_u64(values[::2]) is None                       # strided
    assert native.order_u64(values.astype(np.int32)) is None           # not 8 bytes
    assert native.order_u64(values.astype(np.float32)) is None
    assert native.order_u64(values.view(np.uint64)) is None            # unsigned: the twin's branch
    assert native.order_u64(values.astype(">i8")) is None              # not little-endian
    assert native.order_u64(values, np.ones(9_999, dtype=bool)) is None
    assert native.order_u64(values, np.ones(10_000, dtype=np.uint8)) is None
    enc = native.order_u64(values)
    row, _after = _guarded_row(10_000)
    assert native.zorder_words(enc[::2], 0, 1.0, 65535, row) is False
    assert native.zorder_words(enc.view(np.int64), 0, 1.0, 65535, row) is False
    for bad in (np.zeros(10_000, dtype=np.int32), np.zeros(20_000, dtype=np.uint32)[::2],
                np.zeros(9_999, dtype=np.uint32), np.zeros((1, 10_000), dtype=np.uint32)):
        with pytest.raises(ValueError):
            native.zorder_words(enc, 0, 1.0, 65535, bad)
    readonly = np.zeros(10_000, dtype=np.uint32)
    readonly.flags.writeable = False
    with pytest.raises(ValueError):
        native.zorder_words(enc, 0, 1.0, 65535, readonly)


def _fit_and_planes(columns, quantile=False):
    """``ZOrderEncoder.fit`` then ``planes_from_encodings`` under an
    action root -> (planes, encodings, the zorder_encode-like span's
    attrs, the words span's attrs)."""
    root = trace.root("action.CreateAction", always=True)
    with trace.activate(root):
        with trace.span("zorder_encode") as enc_sp:
            encoder, encs = zops.ZOrderEncoder.fit(columns, 16, quantile, 0.01)
        planes = encoder.planes_from_encodings(encs)
    root.finish()
    (words,) = [s for s in root.spans if s.name == "words"]
    return planes, encs, enc_sp.attrs, words.attrs


@pytest.mark.parametrize("arm", ["native", "below_threshold", "unloaded", "quantile"])
def test_the_native_attr_counts_the_columns_each_pass_made(monkeypatch, arm):
    """At the threshold every numeric column's encoding and words are
    the native passes (``native`` 3 of ``columns`` 3 on both spans);
    below it, with the library not loaded, or under quantile specs
    (encodings native, words the twin's) the twins run and the planes
    are the same bytes."""
    if arm in ("native", "quantile") and native.load() is None:
        pytest.skip("the native kernels did not build here")
    columns = [_column("date32", n=40_001), _column("float64_nulls", n=40_001), _column("int64", n=40_001)]
    monkeypatch.setattr(hash_ops, "_NATIVE_HASH_MIN_ROWS", 1)
    monkeypatch.setattr(native, "load", lambda wait=True: None)
    want = _fit_and_planes(columns, quantile=arm == "quantile")[0]      # the twins throughout
    monkeypatch.undo()
    monkeypatch.setattr(hash_ops, "_NATIVE_HASH_MIN_ROWS", 40_002 if arm == "below_threshold" else 40_001)
    if arm == "unloaded":
        monkeypatch.setattr(native, "load", lambda wait=True: None)
    planes, encs, enc_attrs, words_attrs = _fit_and_planes(columns, quantile=arm == "quantile")
    assert planes.tobytes() == want.tobytes() and planes.shape == (2, 40_001)
    for col, enc in zip(columns, encs):
        assert enc.tobytes() == zops.order_u64_numpy(col).tobytes()
    ran_encode = 3 if arm in ("native", "quantile") else 0
    ran_words = 3 if arm == "native" else 0
    assert (enc_attrs["columns"], enc_attrs["native"]) == (3, ran_encode)
    assert (words_attrs["columns"], words_attrs["native"]) == (3, ran_words)
    if ran_words:       # the pass writes each row and its fill whole
        assert words_attrs["stack_s"] == words_attrs["pad_s"] == 0.0


# -- the build is the same build ------------------------------------------------------------

def _zsource(tmp, rows, seed=4444):
    """Q6's three range columns (date32, float64 0.00..0.10 with 0.0,
    int64 with nulls) and an included float64, in four parquet files."""
    rng = np.random.default_rng([seed, rows])
    qty = rng.integers(1, 51, rows)
    table = pa.table({
        "l_shipdate": pa.array((rng.integers(8000, 10_600, rows)).astype("datetime64[D]"), type=pa.date32()),
        "l_discount": pa.array(rng.integers(0, 11, rows) / 100.0),
        "l_quantity": pa.array(qty, type=pa.int64(), mask=rng.random(rows) < 0.05),
        "l_extendedprice": pa.array(qty * rng.uniform(900.0, 2000.0, rows)),
    })
    for i in range(4):
        lo, hi = i * rows // 4, (i + 1) * rows // 4
        pq.write_table(table.slice(lo, hi - lo), os.path.join(tmp, f"p{i}.parquet"))
    return table


def _zbuild(root, src, monkeypatch, use_native):
    """One z-order ``create_index`` with the passes at any size, or with
    the native library disabled -> ({file: bytes}, the action's root)."""
    monkeypatch.setattr(hash_ops, "_NATIVE_HASH_MIN_ROWS", 1)
    if not use_native:
        monkeypatch.setattr(native, "load", lambda wait=True: None)
    session = HyperspaceSession(devices=jax.devices()[:1])
    session.conf.set(C.INDEX_SYSTEM_PATH, root)
    session.conf.set(C.ZORDER_TARGET_SOURCE_BYTES_PER_PARTITION, 1 << 20)
    items = session.read.parquet(src)
    Hyperspace(session).create_index(items, ZOrderCoveringIndexConfig(
        "z_idx", ["l_shipdate", "l_discount", "l_quantity"], ["l_extendedprice"]))
    files = {os.path.relpath(f, root): pathlib.Path(f).read_bytes()
             for f in glob.glob(os.path.join(root, "z_idx", "v__=*", "*")) if os.path.isfile(f)}
    return files, trace.finished("action.CreateAction")[-1]


def _no_mtimes(doc):
    if isinstance(doc, dict):
        return {k: _no_mtimes(v) for k, v in doc.items() if k != "mtime_ns"}
    if isinstance(doc, list):
        return [_no_mtimes(v) for v in doc]
    return doc


@needs_native
@pytest.mark.parametrize("rows", [12_000, 400_000])
def test_a_zorder_build_with_the_passes_equals_the_build_without_the_library(monkeypatch, tmp_path, rows):
    src = tmp_path / "src"
    src.mkdir()
    table = _zsource(str(src), rows)
    with monkeypatch.context() as m:
        files, root = _zbuild(str(tmp_path / "native"), str(src), m, use_native=True)
    with monkeypatch.context() as m:
        twin_files, twin_root = _zbuild(str(tmp_path / "twin"), str(src), m, use_native=False)
    assert sorted(files) == sorted(twin_files) and any(f.endswith(".parquet") for f in files)
    for name, data in files.items():
        if name.endswith(".parquet"):       # the data files, byte for byte
            assert data == twin_files[name], name
        else:                               # the sidecars, but for the files' mtimes they name
            assert _no_mtimes(json.loads(data)) == _no_mtimes(json.loads(twin_files[name])), name
    for of, ran in ((root, 3), (twin_root, 0)):
        spans = {s.name: s.attrs for s in of.spans if s.name in ("zorder_encode", "words")}
        assert (spans["zorder_encode"]["columns"], spans["zorder_encode"]["native"]) == (3, ran)
        assert (spans["words"]["columns"], spans["words"]["native"]) == (3, ran)

    # the query side's scalar twins agree with the native words on sample rows
    monkeypatch.setattr(hash_ops, "_NATIVE_HASH_MIN_ROWS", 1)
    columns = [Column.from_arrow(table.column(c)) for c in ("l_shipdate", "l_discount", "l_quantity")]
    encoder, encs = zops.ZOrderEncoder.fit(columns, 16, False, 0.01)
    rng = np.random.default_rng(rows)
    sample = np.concatenate([rng.integers(0, rows, 200), [int(np.argmin(encs[1])), int(np.argmax(encs[1]))]])
    for col, enc, spec in zip(columns, encs, encoder.specs):
        out = np.empty(pad_len(rows), dtype=np.uint32)
        assert native.zorder_words(enc, *encoder._range(spec), 65535, out)
        kind = col.values.dtype.kind
        for r in sample:
            if col.validity is not None and not col.validity[r]:
                assert enc[r] == 0
                continue
            e = zops.order_u64_scalar(col.values[r], kind)
            assert e == int(enc[r])
            w_lo, w_hi = zops.spec_word_bounds(spec, e, e, 16)
            assert w_lo == int(out[r]) and w_hi - w_lo in (0, 1)
