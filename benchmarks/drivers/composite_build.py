"""Traffic ``composite-build``: build the configuration's covering index
on a TWO-column key over and over.

Window: ``build_loop``'s own — closed loop, one builder, {
``create_index``; ``delete_index``; ``vacuum_index``; ``clear_cache`` }
until ``--seconds`` have passed at the end of a build, and
``build_rows_per_s`` reckoned as there. The last build is kept, and once
the window has closed it is looked at three times, each against the
plain reference (``reference.py``, ``reference_composite.py``: numpy
over the generated columns, nothing of the program). The eight compared
numbers keep ``build_loop``'s names; each is about the PAIR:

read-back     every row through the rewrite rules and the executor (a
              predicate on the first indexed column that every row
              meets), as one order-independent digest; ``explain()``
              names the index as ``CI``
lookups       ``first = p AND second = s`` through the engine, pairs
              from ``--seed``: all but the last drawn from rows (a pair
              has several), the last a first key that exists with a
              second key that is none of its own (the answer is no
              row); each answer's rows as a digest against ``PairIndex``
bucket files  opened with pyarrow: as many files as the configuration
              states buckets, each bucket once; every row's
              ``bucket_of_pairs`` — the hash of BOTH keys, not the
              program's — against its file's bucket id; every file in
              non-decreasing lexicographic order of the pair
              (``lex_unsorted``: a file sorted on its first key alone
              is caught by its ties); the rows in all
"""

from __future__ import annotations

import glob
import os
import re
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import queries
import reference
import reference_composite
from drivers.build_loop import setup, teardown, window  # noqa: F401  (one warm-up build; the loop and its rate)
from harness import log

_BUCKET_FILE = re.compile(r"bucket_(\d+)\.parquet$")


def _keys(ctx) -> tuple:
    first, second = ctx.config["index"]["indexed"]
    return first, second


def lookup_pairs(ctx, index: reference_composite.PairIndex) -> list:
    """-> [(first key, second key)] of the seed's lookups: pairs of rows
    drawn from the seed, and last a drawn row's first key with a second
    key that occurs in the table (where one is left) but never with it:
    the first such from a drawn one on, round the second key's range."""
    first, second = _keys(ctx)
    n = int(ctx.traffic.get("pair_lookups", 8))
    rng = np.random.default_rng([ctx.seed, 0x09])
    rows = rng.integers(0, ctx.rows, n)
    pairs = [(int(ctx.items_cols[first][r]), int(ctx.items_cols[second][r])) for r in rows]
    a = pairs[-1][0]
    own = set(index.seconds_of(a).tolist())
    top = int(ctx.items_cols[second].max())
    start = int(rng.integers(1, top + 1))
    round_the_range = ((start + j - 1) % top + 1 for j in range(top))
    pairs[-1] = (a, next((b for b in round_the_range if b not in own), top + 1))
    return pairs


def _readback(ctx, transform=None) -> dict:
    want = reference.digest(ctx.items_cols)
    unserved = 0
    if transform is not None:
        got = reference.digest(transform(ctx.items_cols))
    else:
        ix, where = ctx.config["index"], ctx.traffic["readback_where"]
        ctx.session.enable_hyperspace()
        items = ctx.read_items()
        every = items.filter(items[where["column"]] >= where["at_least"]).select(*ctx.items_cols)
        unserved = int(not queries.served(every, ix["name"], ctx.index_abbr()))
        got = reference.digest(reference.table_cols(every.collect()))
    return {
        "readback_rows_gap": {"value": abs(got[0] - want[0]), "limit": 0},
        "readback_digest_differs": {"value": int(got != want), "limit": 0},
        "not_index_served": {"value": unserved, "limit": 0},
    }


def _lookups(ctx, transform=None) -> tuple:
    """-> (answers that differ from the reference's, plans that do not
    name the index). ``transform`` puts a control's answers in the
    program's place."""
    ix, cols = ctx.config["index"], list(ctx.items_cols)
    first, second = _keys(ctx)
    index = reference_composite.PairIndex(ctx.items_cols, first, second)
    wrong = unserved = 0
    if transform is None:
        ctx.session.enable_hyperspace()
        items = ctx.read_items()
    for i, (a, b) in enumerate(lookup_pairs(ctx, index)):
        want_cols = index.answer(a, b, cols)
        want = reference.digest(want_cols)
        if transform is not None:
            wrong += int(reference.digest(transform(want_cols)) != want)
            continue
        query = items.filter((items[first] == a) & (items[second] == b)).select(*cols)
        unserved += int(not queries.served(query, ix["name"], ctx.index_abbr()))
        got = reference.digest(reference.table_cols(query.collect()))
        wrong += int(got != want)
        log(f"pair lookup {i}: {first} = {a} AND {second} = {b}: {got[0]} rows (reference {want[0]})")
    return wrong, unserved


def file_structure(path: str, keys: tuple, bucket: int, num_buckets: int) -> tuple:
    """One bucket file -> (rows, rows whose pair belongs in another
    bucket, 1 if the file's pairs decrease anywhere)."""
    import pyarrow.parquet as pq

    table = pq.read_table(path, columns=list(keys))
    a, b = (table.column(k).to_numpy() for k in keys)
    stray = int(np.count_nonzero(reference_composite.bucket_of_pairs(a, b, num_buckets) != bucket))
    return len(a), stray, int(reference_composite.lex_unsorted(a, b) > 0)


def structure(ctx) -> dict:
    """The bucket files of the newest version of the index -> the numbers
    compared, each with its limit."""
    ix = ctx.config["index"]
    n, keys = int(ix["num_buckets"]), _keys(ctx)
    version = queries.newest_version_dir(os.path.join(ctx.index_root, ix["name"]))
    # data files only: a name that starts with "_" or "." is a sidecar
    files = sorted(f for f in glob.glob(os.path.join(version, "*.parquet"))
                   if not os.path.basename(f).startswith(("_", ".")))
    buckets = [int(m.group(1)) if (m := _BUCKET_FILE.search(f)) else -1 for f in files]
    times_seen = np.bincount([b for b in buckets if 0 <= b < n], minlength=n)
    with ThreadPoolExecutor(max_workers=8) as pool:
        per_file = list(pool.map(
            lambda fb: file_structure(fb[0], keys, fb[1], n), zip(files, buckets)))
    rows, stray, unsorted = (int(sum(col)) for col in zip(*per_file)) if per_file else (0, 0, 0)
    log(f"bucket files: {len(files)} data file(s), {rows:,} rows in {os.path.basename(version)}, "
        f"{sum(os.path.getsize(f) for f in files):,} B")
    return {
        "bucket_files_gap": {
            "value": abs(len(files) - n) + int(np.count_nonzero(times_seen != 1)), "limit": 0},
        "bucket_rows_gap": {"value": abs(rows - ctx.rows), "limit": 0},
        "misbucketed_rows": {"value": stray, "limit": 0},
        "unsorted_bucket_files": {"value": unsorted, "limit": 0},
    }


def _answers(ctx, transform=None) -> dict:
    got = _readback(ctx, transform)
    wrong, unserved = _lookups(ctx, transform)
    got["point_answers_wrong"] = {"value": wrong, "limit": 0}
    got["not_index_served"]["value"] += unserved
    return got


def check(ctx, win: dict) -> dict:
    if not win["ops"]:
        return {"builds_completed": {"value": 1, "limit": 0}}
    t0 = time.perf_counter()
    got = _answers(ctx)
    t1 = time.perf_counter()
    got.update(structure(ctx))
    log(f"check: read-back + lookups {t1 - t0:.1f}s, bucket files {time.perf_counter() - t1:.1f}s")
    return got


def controls(ctx, win: dict) -> dict:
    return {"float32_payload": _answers(ctx, transform=reference.lossy)}
