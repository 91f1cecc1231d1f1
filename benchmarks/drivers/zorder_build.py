"""Traffic ``zorder-build``: build the configuration's z-order covering
index over and over.

Window: ``build_loop``'s own — closed loop, one builder, {
``create_index``; ``delete_index``; ``vacuum_index``; ``clear_cache`` }
until ``--seconds`` have passed at the end of a build, and
``build_rows_per_s`` reckoned as there. The last build is kept, and once
the window has closed it is looked at three times, each against the
plain reference (``reference.py``, ``reference_zorder.py``: numpy over
the generated columns, nothing of the program):

read-back   every row through the rewrite rules and the executor (a
            predicate on an indexed column that every row meets), as one
            order-independent digest; ``explain()`` names the index as
            ``ZOCI``
layout      the data files of the newest ``v__=N``, opened with pyarrow
            in name order: the reference's z-address of every row from
            the file's own indexed columns, under the generated columns'
            min/max; adjacent pairs, inside a file and across each file
            boundary, whose address decreases; the rows in all
ranges      TPC-H Q6's predicate (clause 2.4.6.2) through the engine,
            parameters from ``--seed`` within clause 2.4.6.3's ranges —
            DATE the first of January of 1993..1997 (every year once,
            in the seed's order, before any comes twice), DISCOUNT 0.02
            .. 0.09, QUANTITY 24 or 25 — selecting the index's columns:
            each answer's rows as a digest against ``range_rows`` (the
            qualifying rows, not Q6's float SUM, whose value depends on
            the order of addition); every plan names the index
"""

from __future__ import annotations

import datetime
import glob
import os
import time

import numpy as np

import queries
import reference
import reference_zorder
from drivers.build_loop import setup, teardown, window  # noqa: F401  (one warm-up build; the loop and its rate)
from harness import log

_EPOCH = datetime.date(1970, 1, 1)
YEARS = (1993, 1994, 1995, 1996, 1997)


def range_params(ctx) -> list:
    """-> [(first day, day after the last, discount low, discount high,
    quantity)] of the seed's Q6-shaped queries. The discount's ends are
    the float literals (d -+ 1) / 100.0 that engine and reference both
    get: what ``datagen`` writes for a discount of d -+ 1 hundredths."""
    n = int(ctx.traffic.get("range_queries", 8))
    rng = np.random.default_rng([ctx.seed, 0x26])
    years = np.concatenate([rng.permutation(YEARS), rng.choice(YEARS, max(n - len(YEARS), 0))])[:n]
    hundredths = rng.integers(2, 10, n)
    quantities = rng.integers(24, 26, n)
    return [(datetime.date(int(y), 1, 1), datetime.date(int(y) + 1, 1, 1),
             (int(d) - 1) / 100.0, (int(d) + 1) / 100.0, int(q))
            for y, d, q in zip(years, hundredths, quantities)]


def _readback(ctx, transform=None) -> dict:
    want = reference.digest(ctx.items_cols)
    unserved = 0
    if transform is not None:
        got = reference.digest(transform(ctx.items_cols))
    else:
        ix, where = ctx.config["index"], ctx.traffic["readback_where"]
        ctx.session.enable_hyperspace()
        items = ctx.read_items()
        # a range predicate on an indexed column that every row meets
        every = items.filter(items[where["column"]] >= where["at_least"]).select(*ctx.items_cols)
        unserved = int(not queries.served(every, ix["name"], ctx.index_abbr()))
        got = reference.digest(reference.table_cols(every.collect()))
    return {
        "readback_rows_gap": {"value": abs(got[0] - want[0]), "limit": 0},
        "readback_digest_differs": {"value": int(got != want), "limit": 0},
        "not_index_served": {"value": unserved, "limit": 0},
    }


def _ranges(ctx, transform=None) -> tuple:
    """-> (answers that differ from the reference's, plans that do not
    name the index). ``transform`` puts a control's answers in the
    program's place."""
    from hyperspace_tpu.indexes import zonemaps

    ix, cols = ctx.config["index"], list(ctx.items_cols)
    wrong = unserved = 0
    if transform is None:
        ctx.session.enable_hyperspace()
        items = ctx.read_items()
    for i, (d0, d1, lo, hi, q) in enumerate(range_params(ctx)):
        mask = reference_zorder.range_rows(
            ctx.items_cols, (d0 - _EPOCH).days, (d1 - _EPOCH).days, lo, hi, q)
        want_cols = {c: v[mask] for c, v in ctx.items_cols.items()}
        want = reference.digest(want_cols)
        if transform is not None:
            wrong += int(reference.digest(transform(want_cols)) != want)
            continue
        query = items.filter(
            (items["l_shipdate"] >= d0) & (items["l_shipdate"] < d1)
            & (items["l_discount"] >= lo) & (items["l_discount"] <= hi)
            & (items["l_quantity"] < q)).select(*cols)
        unserved += int(not queries.served(query, ix["name"], ctx.index_abbr()))
        before = zonemaps.last_prune_stats
        got = reference.digest(reference.table_cols(query.collect()))
        wrong += int(got != want)
        # what the executor's pruning pass says it kept, if it ran for this query
        read = zonemaps.last_prune_stats if zonemaps.last_prune_stats is not before else {}
        log(f"range query {i}: {d0} +1y, discount {lo}..{hi}, quantity < {q}: {got[0]:,} rows "
            f"(reference {want[0]:,}), row groups read {read.get('row_groups_kept')} of "
            f"{read.get('row_groups_total')}, z-pruned {read.get('z_pruned')}")
    return wrong, unserved


def layout(ctx) -> dict:
    """The data files of the newest version of the index, in name order
    -> the numbers compared, each with its limit."""
    import pyarrow.parquet as pq

    ix = ctx.config["index"]
    indexed = ix["indexed"]
    mins, maxs = reference_zorder.min_max(ctx.items_cols, indexed)
    version = queries.newest_version_dir(os.path.join(ctx.index_root, ix["name"]))
    # data files only: a name that starts with "_" or "." is a sidecar
    files = sorted(f for f in glob.glob(os.path.join(version, "*.parquet"))
                   if not os.path.basename(f).startswith(("_", ".")))
    rows, inversions = reference_zorder.inversions_across(
        (reference.table_cols(pq.read_table(f, columns=indexed)) for f in files), indexed, mins, maxs)
    log(f"layout: {len(files)} data file(s), {rows:,} rows in {os.path.basename(version)}")
    return {
        "index_rows_gap": {"value": abs(rows - ctx.rows), "limit": 0},
        "zorder_inversions": {"value": inversions, "limit": 0},
    }


def check(ctx, win: dict) -> dict:
    if not win["ops"]:
        return {"builds_completed": {"value": 1, "limit": 0}}
    t0 = time.perf_counter()
    got = _readback(ctx)
    t1 = time.perf_counter()
    got.update(layout(ctx))
    t2 = time.perf_counter()
    wrong, unserved = _ranges(ctx)
    log(f"check: read-back {t1 - t0:.1f}s, layout {t2 - t1:.1f}s, ranges {time.perf_counter() - t2:.1f}s")
    got["range_answers_wrong"] = {"value": wrong, "limit": 0}
    got["not_index_served"]["value"] += unserved
    return got


def controls(ctx, win: dict) -> dict:
    got = _readback(ctx, transform=reference.lossy)
    got["range_answers_wrong"] = {"value": _ranges(ctx, transform=reference.lossy)[0], "limit": 0}
    return {"float32_payload": got}
