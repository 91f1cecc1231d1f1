"""Reading an index back: what a build wrote, against the plain reference.

Two looks at the last timed index, both once the window has closed:

``readback``   through the rewrite rules and the executor, as a user's
               query goes: every row as one digest, then point lookups,
               and ``explain()`` has to name the index. Under the
               program's defaults no filter query prunes buckets, so this
               look finds a row whatever bucket file it lies in.
``structure``  the bucket files themselves, opened with pyarrow: as many
               files as the configuration states buckets, each bucket
               once; every row in the file of the bucket its key hashes
               to (``reference.bucket_of``, not the program's hash); every
               file sorted on the key. This is what a bucketed join and
               bucket pruning rely on, and what ``readback`` cannot see.
"""

from __future__ import annotations

import glob
import os
import re
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import reference

_VERSION_DIR = re.compile(r"v__=(\d+)$")
_BUCKET_FILE = re.compile(r"bucket_(\d+)\.parquet$")


def served(df, index_name: str, abbr: str = "CI") -> bool:
    """``explain()`` names the index ``index_name`` of the kind that
    prints as ``abbr`` (``CI`` covering, ``ZOCI`` z-order covering)."""
    plan = df.explain()
    return f"Hyperspace(Type: {abbr}," in plan and f"Name: {index_name}" in plan


def point_query(items, key_col: str, key: int, cols):
    """``lineitem WHERE <key_col> = k`` selecting the index's columns:
    a float payload has to come back bit for bit."""
    return items.filter(items[key_col] == key).select(*cols)


def point_answers_wrong(index: reference.KeyIndex, keys, answers, transform=None) -> int:
    """Point answers (pyarrow tables, in the order of ``keys``) that differ
    from the reference, over all of the reference's columns.
    ``transform`` puts a control's answers in the program's place."""
    import pyarrow as pa

    cols = list(index.cols)
    want_cols, want_counts = reference.ref_point(index, keys, cols)
    want = reference.segment_digests(want_cols, want_counts)
    if transform is not None:
        got = reference.segment_digests(transform(want_cols), want_counts)
    else:
        tables = [t.select(cols) for t in answers]
        counts = np.array([t.num_rows for t in tables], dtype=np.int64)
        got = reference.segment_digests(
            reference.table_cols(pa.concat_tables(tables)), counts)
    return int(np.count_nonzero((got != want).any(axis=1)))


def readback(ctx, want_cols: dict, point_keys, transform=None) -> dict:
    """-> the numbers compared, each with its limit. ``want_cols`` are
    the reference's columns (the configuration's indexed + included);
    the key of the lookups is the first indexed column."""
    index_name, abbr = ctx.config["index"]["name"], ctx.index_abbr()
    key_col, cols = ctx.config["index"]["indexed"][0], list(want_cols)
    index = reference.KeyIndex(want_cols, key_col)
    want = reference.digest(want_cols)
    keys = [int(k) for k in point_keys]
    answers, unserved = None, 0
    if transform is not None:
        got = reference.digest(transform(want_cols))
    else:
        ctx.session.enable_hyperspace()
        items = ctx.read_items()
        every = items.filter(items[key_col] >= 0).select(*cols)
        unserved = int(not served(every, index_name, abbr))
        got = reference.digest(reference.table_cols(every.collect()))
        answers = []
        for k in keys:
            q = point_query(items, key_col, k, cols)
            unserved += int(not served(q, index_name, abbr))
            answers.append(q.collect())
    return {
        "readback_rows_gap": {"value": abs(got[0] - want[0]), "limit": 0},
        "readback_digest_differs": {"value": int(got != want), "limit": 0},
        "point_answers_wrong": {
            "value": point_answers_wrong(index, keys, answers, transform), "limit": 0},
        "not_index_served": {"value": unserved, "limit": 0},
    }


def newest_version_dir(index_root: str) -> str:
    found = [(int(m.group(1)), d) for d in glob.glob(os.path.join(index_root, "v__=*"))
             if (m := _VERSION_DIR.search(d))]
    if not found:
        raise RuntimeError(f"no v__=N directory under {index_root}")
    return max(found)[1]


def file_structure(path: str, key: str, bucket: int, num_buckets: int) -> tuple:
    """One bucket file -> (rows, rows whose key belongs in another bucket,
    1 if the file is not sorted on the key)."""
    import pyarrow.parquet as pq

    keys = pq.read_table(path, columns=[key]).column(0).to_numpy()
    stray = int(np.count_nonzero(reference.bucket_of(keys, num_buckets) != bucket))
    return len(keys), stray, int(bool(np.any(keys[1:] < keys[:-1])))


def structure(ctx) -> dict:
    """The bucket files of the newest version of the index -> the numbers
    compared, each with its limit."""
    ix = ctx.config["index"]
    n, key = int(ix["num_buckets"]), ix["indexed"][0]
    version = newest_version_dir(os.path.join(ctx.index_root, ix["name"]))
    # data files only: a name that starts with "_" or "." is a sidecar
    # that no scan reads (the lake convention)
    files = sorted(f for f in glob.glob(os.path.join(version, "*.parquet"))
                   if not os.path.basename(f).startswith(("_", ".")))
    buckets = [int(m.group(1)) if (m := _BUCKET_FILE.search(f)) else -1 for f in files]
    times_seen = np.bincount([b for b in buckets if 0 <= b < n], minlength=n)
    with ThreadPoolExecutor(max_workers=8) as pool:
        per_file = list(pool.map(
            lambda fb: file_structure(fb[0], key, fb[1], n), zip(files, buckets)))
    rows, stray, unsorted = (int(sum(col)) for col in zip(*per_file)) if per_file else (0, 0, 0)
    return {
        "bucket_files_gap": {
            "value": abs(len(files) - n) + int(np.count_nonzero(times_seen != 1)), "limit": 0},
        "bucket_rows_gap": {"value": abs(rows - ctx.rows), "limit": 0},
        "misbucketed_rows": {"value": stray, "limit": 0},
        "unsorted_bucket_files": {"value": unsorted, "limit": 0},
    }
