"""Traffic ``sketch-orders``: build the configuration's covering index on
ORDERS over and over (the minimal driver of the string-column proof,
``tests/test_orders_cell.py``; not a cell of the benchmark).

The window is ``build_loop``'s. Once it has closed the last build is read
back in full and by point lookups on ``o_custkey`` (``queries.readback``),
string payload and all, and its bucket files are opened
(``queries.structure``), both against the plain reference.
"""

from __future__ import annotations

import numpy as np

import datagen
import queries
import reference
from drivers.build_loop import setup, teardown, window  # noqa: F401  (the covering build loop)


def _point_keys(ctx) -> np.ndarray:
    """Customer keys of orders drawn from the seed, and last a multiple
    of 3, a customer with no order (the answer is no row)."""
    rng = np.random.default_rng([ctx.seed, 0xC0FFEE])
    custkeys = ctx.items_cols["o_custkey"]
    keys = custkeys[rng.integers(0, len(custkeys), int(ctx.traffic.get("check_points", 8)))]
    keys[-1] = 3 * rng.integers(1, max(datagen.customers(ctx.n_orders) // 3, 1) + 1)
    return keys


def check(ctx, win: dict) -> dict:
    if not win["ops"]:
        return {"builds_completed": {"value": 1, "limit": 0}}
    got = queries.readback(ctx, ctx.items_cols, _point_keys(ctx))
    got.update(queries.structure(ctx))
    return got


def controls(ctx, win: dict) -> dict:
    return {"clipped_strings": queries.readback(
        ctx, ctx.items_cols, _point_keys(ctx), transform=reference.clipped)}
