"""Traffic ``skip-build``: build the configuration's data-skipping index
over and over.

Window: ``build_loop``'s own — closed loop, one builder, {
``create_index``; ``delete_index``; ``vacuum_index``; ``clear_cache`` }
until ``--seconds`` have passed at the end of a build, and
``build_rows_per_s`` reckoned as there. The last build is kept, and once
the window has closed it is looked at twice, each against the plain
reference (``reference_skipping.py``: numpy over the generated columns,
nothing of the program):

sketch table  the file the index's log entry names, opened with pyarrow;
              each row's file id taken to its source file by the entry's
              source listing: one row a source file
              (``sketch_rows_gap``), and each file's min and max
              (``minmax_differs``) and bloom bytes (``bloom_bits_differ``)
              as the reference computes them from the file's rows. A file
              with no row, or with more than one, differs in both
queries       point lookups ``l_partkey = p`` and ranges ``l_orderkey
              BETWEEN a AND b`` through the rewrite rules and the
              executor: each answer as a digest against the reference's
              rows (``lookup_answers_wrong``), the optimized plan's file
              list against the files a correct pruning keeps
              (``files_kept_differ``: the bloom's false positives are
              kept by both), files pruned that hold a row of the
              predicate (``pruned_files_with_rows``), and ``explain()``
              has to name the index as ``DS`` (``not_index_served``)
"""

from __future__ import annotations

import os
import re
import time

import numpy as np

import queries
import reference
import reference_skipping as rs
from drivers.build_loop import setup, teardown, window  # noqa: F401  (one warm-up build; the loop and its rate)
from harness import log

_SOURCE_FILE = re.compile(r"part(\d+)\.parquet$")


def _file_of(path: str) -> int:
    """A source file's place in ``datagen``'s order, from its name; -1
    for a path that is none of its files."""
    m = _SOURCE_FILE.search(os.path.basename(path))
    return int(m.group(1)) if m else -1


def lookup_keys(ctx) -> list:
    """Part keys of the seed's lookups: all but the last drawn from rows,
    the last one past the largest part key (the answer is no row)."""
    col = ctx.items_cols[rs.bloom_of(ctx.config)["column"]]
    rng = np.random.default_rng([ctx.seed, 0x5C1F])
    drawn = col[rng.integers(0, ctx.rows, int(ctx.traffic.get("point_lookups", 8)) - 1)]
    return [int(p) for p in drawn] + [int(col.max()) + 1]


def ranges(ctx) -> list:
    """[(a, b)] of the seed's ranges, each ``range_share`` of the key
    span: the first begins at the smallest key, the last ends at the
    largest, the others begin where the seed draws."""
    col = ctx.items_cols[rs.minmax_of(ctx.config)["column"]]
    lo, hi = int(col.min()), int(col.max())
    width = max(1, int(float(ctx.traffic.get("range_share", 0.01)) * (hi - lo)))
    rng = np.random.default_rng([ctx.seed, 0x5C2A])
    n = int(ctx.traffic.get("ranges", 4))
    starts = [lo] + [int(a) for a in rng.integers(lo, hi - width + 1, max(n - 2, 0))] + [hi - width]
    return [(a, a + width) for a in starts[:n]]


def sketch_table(ctx) -> tuple:
    """-> (the sketch table as the rule reads it, {file id: source path})."""
    import pyarrow.parquet as pq

    entry = ctx.session.index_manager.get_index_log_entry(ctx.config["index"]["name"])
    table = pq.read_table(sorted(entry.content.files))
    return table, {info.id: path for path, info in entry.relation.content.file_infos}


def _want(ctx) -> dict:
    """The reference's sketch of every source file, made once a run."""
    if "sketches" not in ctx.state:
        ctx.state["sketches"] = rs.file_sketches(ctx.items_cols, ctx.config)
    return ctx.state["sketches"]


def sketch_structure(ctx) -> dict:
    n_files = int(ctx.config["files_per_table"])
    mm, bf = rs.minmax_of(ctx.config), rs.bloom_of(ctx.config)
    want = _want(ctx)
    table, paths = sketch_table(ctx)
    ids = table.column("_data_file_id").to_pylist()
    lo = table.column(f"MinMax_{mm['column']}__min").to_pylist()
    hi = table.column(f"MinMax_{mm['column']}__max").to_pylist()
    bits = table.column(f"BloomFilter_{bf['column']}__bits").to_pylist()
    rows_of = {}
    for r, fid in enumerate(ids):
        rows_of.setdefault(_file_of(paths.get(fid, "")), []).append(r)
    minmax = bloom = 0
    for i in range(n_files):
        got = rows_of.get(i, [])
        if len(got) != 1:
            minmax, bloom = minmax + 1, bloom + 1
            continue
        r = got[0]
        minmax += int((lo[r], hi[r]) != (want["min"][i], want["max"][i]))
        bloom += int(bits[r] != want["bloom"][i])
    log(f"sketch table: {table.num_rows} row(s) of {n_files} source files, "
        f"{sum(len(b or b'') for b in bits):,} B of bloom bits")
    return {
        "sketch_rows_gap": {"value": abs(table.num_rows - n_files), "limit": 0},
        "minmax_differs": {"value": minmax, "limit": 0},
        "bloom_bits_differ": {"value": bloom, "limit": 0},
    }


def _queries(ctx, rewrite: bool) -> dict:
    """The lookups and ranges through the engine -> the four numbers.
    ``rewrite`` False runs them with the index disabled (the control)."""
    n_files = int(ctx.config["files_per_table"])
    mm, bf = rs.minmax_of(ctx.config), rs.bloom_of(ctx.config)
    ix, cols = ctx.config["index"], list(ctx.items_cols)
    want_sk = _want(ctx)
    bounds = np.array([hi for _lo, hi in rs.file_bounds(ctx.rows, n_files)])
    asked = [("lookup", p, p) for p in lookup_keys(ctx)] + [("range", a, b) for a, b in ranges(ctx)]
    if rewrite:
        ctx.session.enable_hyperspace()
    else:
        ctx.session.disable_hyperspace()
    items = ctx.read_items()
    wrong = kept_differ = pruned_with_rows = unserved = 0
    for kind, a, b in asked:
        if kind == "lookup":
            col = bf["column"]
            query = items.filter(items[col] == a).select(*cols)
            keep = rs.kept_by_lookup(want_sk, a, bf["m"], bf["k"])
        else:
            col = mm["column"]
            query = items.filter((items[col] >= a) & (items[col] <= b)).select(*cols)
            keep = rs.kept_by_range(want_sk, a, b)
        values = ctx.items_cols[col]
        mask = (values >= a) & (values <= b)
        holding = set(np.searchsorted(bounds, np.flatnonzero(mask), side="right").tolist())
        scanned = {_file_of(p) for p in ctx.session.optimize(query.logical_plan).collect_leaves()[0].relation.files}
        unserved += int(not queries.served(query, ix["name"], ctx.index_abbr()))
        got = reference.digest(reference.table_cols(query.collect()))
        want = reference.digest({c: ctx.items_cols[c][mask] for c in cols})
        wrong += int(got != want)
        kept_differ += int(scanned != keep)
        pruned_with_rows += len(holding - scanned)
        log(f"{kind} {col} {a}..{b}: {got[0]} rows (reference {want[0]}), files scanned "
            f"{len(scanned)}, reference keeps {len(keep)}, {len(holding)} hold rows")
    if not rewrite:
        ctx.session.enable_hyperspace()
    return {
        "lookup_answers_wrong": {"value": wrong, "limit": 0},
        "files_kept_differ": {"value": kept_differ, "limit": 0},
        "pruned_files_with_rows": {"value": pruned_with_rows, "limit": 0},
        "not_index_served": {"value": unserved, "limit": 0},
    }


def check(ctx, win: dict) -> dict:
    if not win["ops"]:
        return {"builds_completed": {"value": 1, "limit": 0}}
    t0 = time.perf_counter()
    got = sketch_structure(ctx)
    t1 = time.perf_counter()
    got.update(_queries(ctx, rewrite=True))
    log(f"check: sketch table {t1 - t0:.1f}s, queries {time.perf_counter() - t1:.1f}s")
    return got


def controls(ctx, win: dict) -> dict:
    return {"no_rewrite": _queries(ctx, rewrite=False)}
