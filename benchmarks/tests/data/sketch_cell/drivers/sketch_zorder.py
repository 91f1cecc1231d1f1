"""Traffic ``sketch-zorder``: build the configuration's z-order covering
index over and over (the minimal driver of the additions-only proof,
``tests/test_added_cell.py``; not a cell of the benchmark).

Once the window has closed the last build is read back in full through
the rewrite rules and the executor, as one digest against numpy over the
generated columns, and ``explain()`` has to name the index as ``ZOCI``.
"""

from __future__ import annotations

import time

import queries
import reference
from drivers.build_loop import _drop, setup, teardown  # noqa: F401  (one warm-up build; drop = delete + vacuum)


def window(ctx, seconds: float) -> dict:
    items, cfg = ctx.state["items"], ctx.index_config()
    ops, t0, end = [], time.perf_counter(), 0.0
    while True:
        t = time.perf_counter()
        with ctx.span("bench.create_index"):
            ctx.hs.create_index(items, cfg)
        end = time.perf_counter() - t0
        ops.append({"kind": "build", "wall_s": time.perf_counter() - t, "breakdown": {}})
        if end >= seconds:
            break
        _drop(ctx)
    return {"window_s": end, "attempted": len(ops), "failed": 0, "ops": ops,
            "metrics": {"build_rows_per_s": len(ops) * ctx.rows / end},
            "resolved": {"builds": len(ops)}}


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


def check(ctx, win: dict) -> dict:
    return _readback(ctx)


def controls(ctx, win: dict) -> dict:
    return {"float32_payload": _readback(ctx, transform=reference.lossy)}
