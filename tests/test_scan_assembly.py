"""The build scan's assembly (``covering_build._scan_with_lineage``):
each fixed-width column is allocated once and every file's chunks are
copied straight into its rows' slice, the files on a pool. The batch
must be the one per-file ``ColumnarBatch.from_arrow`` and
``ColumnarBatch.concat`` give, written out below as the reference:
names and order, arrow type, kind, dtype, values, validity (None
included) and C-contiguity."""

import datetime as dt
import os
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from hyperspace_tpu.constants import DATA_FILE_NAME_ID
from hyperspace_tpu.indexes import covering_build
from hyperspace_tpu.io import parquet as pio
from hyperspace_tpu.io.columnar import Column, ColumnarBatch
from hyperspace_tpu.obs import trace

_T0 = dt.datetime(2024, 1, 1)


def _table(seed: int, rows: int, nulls: bool, strings: bool) -> pa.Table:
    rng = np.random.default_rng(seed)
    mask = (rng.random(rows) < 0.2) if nulls else None
    cols = {
        "k": pa.array(rng.integers(-(2**62), 2**62, rows), type=pa.int64()),
        "i32": pa.array(rng.integers(-1000, 1000, rows, dtype=np.int32), mask=mask),
        "f64": pa.array(rng.standard_normal(rows), mask=mask),
        "f32": pa.array(rng.standard_normal(rows).astype(np.float32)),
        "d": pa.array(rng.integers(0, 20000, rows, dtype=np.int32), type=pa.date32(), mask=mask),
        "ts": pa.array(
            [_T0 + dt.timedelta(seconds=int(s)) for s in rng.integers(0, 10**7, rows)],
            type=pa.timestamp("us"),
        ),
        "u16": pa.array(rng.integers(0, 60000, rows, dtype=np.uint16)),
    }
    if strings:
        cols["s"] = pa.array([f"v{v}" for v in rng.integers(0, 50, rows)])
    return pa.table(cols)


def _lake(tmp_path, specs, row_group_size=700):
    """One parquet file a ``(rows, nulls)`` spec, several row groups."""
    files = []
    for n, (rows, nulls, strings) in enumerate(specs):
        path = os.path.join(tmp_path, f"part-{n}.parquet")
        pq.write_table(_table(n, rows, nulls, strings), path, row_group_size=row_group_size)
        files.append(path)
    return files


def _reference(files, columns, file_ids) -> ColumnarBatch:
    """Per file ``from_arrow`` (and the lineage column), then one concat."""
    batches = []
    for f in files:
        b = ColumnarBatch.from_arrow(pio.read_table([f], columns, "parquet"))
        if file_ids is not None:
            fid = np.full(b.num_rows, file_ids[f], dtype=np.int64)
            b = b.with_column(DATA_FILE_NAME_ID, Column("numeric", pa.int64(), values=fid))
        batches.append(b)
    return ColumnarBatch.concat(batches)


def _assert_same(got: ColumnarBatch, want: ColumnarBatch) -> None:
    assert got.column_names == want.column_names
    for n in want.column_names:
        g, w = got.column(n), want.column(n)
        assert g.kind == w.kind and g.arrow_type == w.arrow_type, n
        if w.kind == "string":
            assert [g.dictionary[c] if c >= 0 else None for c in g.codes] == [
                w.dictionary[c] if c >= 0 else None for c in w.codes
            ], n
            continue
        assert g.values.dtype == w.values.dtype, n
        assert g.values.flags.c_contiguous, n
        np.testing.assert_array_equal(g.values.view(np.uint8), w.values.view(np.uint8), err_msg=n)
        if w.validity is None:
            assert g.validity is None, n
        else:
            assert g.validity is not None and g.validity.dtype == w.validity.dtype, n
            np.testing.assert_array_equal(g.validity, w.validity, err_msg=n)


_NUMERIC = ["k", "i32", "f64", "f32", "d", "ts", "u16"]

CASES = {
    # (rows, nulls, strings) a file, the projection, lineage on, direct columns
    "row_groups": ([(3000, False, False)] * 3, _NUMERIC, False, 7),
    "nulls_in_some_files": (
        [(2500, True, False), (2000, False, False), (1800, True, False)], _NUMERIC, False, 7,
    ),
    "an_empty_file": ([(2000, True, False), (0, False, False), (2200, False, False)], _NUMERIC, False, 7),
    "strings_beside_numbers": ([(1500, True, True)] * 3, ["s", "k", "d", "f64"], False, 3),
    "lineage": ([(1600, False, False), (0, False, False), (2400, True, False)], ["k", "d"], True, 2),
    "one_file": ([(4000, True, True)], ["k", "i32", "s"], True, 0),
    "file_order": ([(900 + 300 * n, n % 2 == 0, False) for n in range(7)], ["d", "k", "f64"], False, 3),
}


@pytest.mark.parametrize("workers", ["one", "pool"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_scan_equals_per_file_decode_and_concat(tmp_path, monkeypatch, case, workers):
    from hyperspace_tpu import native

    specs, columns, lineage, n_direct = CASES[case]
    files = _lake(tmp_path, specs)
    if case == "file_order":
        files = files[::-1]     # row order follows the list, not the names
    if workers == "pool":
        monkeypatch.setattr(native, "_cores", lambda: 4)
        monkeypatch.setattr(covering_build, "_SCAN_BYTES_PER_WORKER", 1)
    file_ids = {f: 100 + n for n, f in enumerate(files)} if lineage else None
    root = trace.root("test.scan", always=True)
    with trace.activate(root), trace.span("scan") as sp:
        got = covering_build._scan_with_lineage(files, "parquet", list(columns), file_ids)
    root.finish()
    _assert_same(got, _reference(files, columns, file_ids))
    at = sp.attrs
    assert at["workers"] == (1 if workers == "one" else min(4, len(files)))
    assert at["columns"] == len(columns) and at["direct"] == n_direct
    tasks_s = at["read_s"] + at["decode_s"] + at["lineage_s"]
    assert tasks_s <= at["workers"] * sp.duration_s + 1e-6
    assert 0.0 <= at["concat_s"] <= sp.duration_s + 1e-6
    if lineage:
        assert got.column_names[-1] == DATA_FILE_NAME_ID


@pytest.mark.parametrize("workers", [1, 3])
def test_the_read_holds_at_most_workers_tables_the_consumer_has_not_taken(tmp_path, monkeypatch, workers):
    """``_read_source_files`` yields the files in order, and reads no
    more than ``workers`` files ahead of the consumer: one worker reads
    each when it is asked for, a pool keeps that many in hand."""
    from hyperspace_tpu import native

    files = _lake(tmp_path, [(100 + 10 * n, False, False) for n in range(9)])
    monkeypatch.setattr(native, "_cores", lambda: workers)
    monkeypatch.setattr(covering_build, "_SCAN_BYTES_PER_WORKER", 1)
    started, lock, real = [], threading.Lock(), pio.read_table

    def counted(paths, columns=None, fmt="parquet", **kw):
        with lock:
            started.append(paths[0])
        return real(paths, columns, fmt, **kw)

    monkeypatch.setattr(pio, "read_table", counted)
    got, reads = covering_build._read_source_files(files, "parquet", ["k"])
    assert got == workers and started == []     # nothing read before it is asked for
    ahead = 0 if workers == 1 else workers
    for taken, (table, read_ns) in enumerate(reads, 1):
        assert table.num_rows == 100 + 10 * (taken - 1) and read_ns > 0
        want = min(len(files), taken + ahead)
        deadline = time.monotonic() + 10
        while len(started) < want and time.monotonic() < deadline:
            time.sleep(0.005)
        time.sleep(0.02)                        # room to overrun, were it allowed
        assert len(started) == want             # read ahead as far as it may, and no further
        if taken == 4:
            break
    reads.close()                               # a consumer that stops early
    time.sleep(0.05)
    assert len(started) == min(len(files), 4 + ahead)
    assert started == files[: len(started)]


def test_chunks_with_offsets_and_nulls_copy_as_from_arrow_decodes():
    """Sliced chunks start inside their buffers; null slots hold 0."""
    from hyperspace_tpu.io.columnar import copy_chunks_into, direct_layout

    rng = np.random.default_rng(5)
    for t in (pa.int64(), pa.int32(), pa.float64(), pa.date32(), pa.timestamp("ns"), pa.duration("ms")):
        storage, dtype = direct_layout(t)
        raw = rng.integers(1, 10**6, 500).astype(storage)
        base = pa.array(raw).view(t)
        holey = pa.array(raw, mask=rng.random(500) < 0.3).view(t)
        arr = pa.chunked_array([holey.slice(7, 100), base.slice(3, 50), holey.slice(250, 0), holey.slice(300)])
        values = np.full(10 + len(arr), -1, dtype=dtype)
        validity = np.zeros(10 + len(arr), dtype=bool)
        copy_chunks_into(arr, 10, values, validity, storage)
        want = Column.from_arrow(arr)
        np.testing.assert_array_equal(values[10:], want.values, err_msg=str(t))
        np.testing.assert_array_equal(validity[10:], want.validity, err_msg=str(t))
        assert values.dtype == want.values.dtype and not validity[:10].any()
    assert direct_layout(pa.string()) is None and direct_layout(pa.bool_()) is None
