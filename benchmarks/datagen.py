"""TPC-H's LINEITEM and ORDERS from ``--seed``: the one general data
generator of the benchmark.

``gen_lineitem`` populates LINEITEM as the specification's clause 4.2.3
sets out (v3.0.1), all sixteen columns of clause 1.4.1, for ``n_orders``
orders:

- order keys are sparse, the first 8 of every 32 (``order_key``), and the
  rows come in order-key order, as dbgen writes them;
- an order has 1 to 7 line items. Every seed deals the same multiset of
  counts (as many orders of each size) in another order, so every seed
  gives exactly ``4 * n_orders`` rows: the same shapes in every run;
- part and supplier keys, quantity, the price from the part's retail
  price, discount, tax, the three dates from the order's date, the return
  flag and line status from CURRENTDATE, the ship instruction and mode
  are the specification's formulas and ranges;
- a comment is 10 to 43 characters of a text pool, as in dbgen (here
  consecutive slices of the pool, so that a column is one buffer).

``gen_table(..., "orders", ...)`` populates ORDERS, all nine columns of clause 1.4.1, one row
an order, from the same seed's LINEITEM draws (nothing of LINEITEM is
written): the same sparse keys and order dates; the status F, O or P from
its lines' ``l_linestatus``; the total price as the sum of
``l_extendedprice * (1 + l_tax) * (1 - l_discount)`` over its lines, in
line-number order, unrounded. Its own draws come from a child stream of
the seed, so LINEITEM's bytes do not depend on whether ORDERS is made: the
customer key uniform over [1, n_orders / 10] and never a multiple of 3
(SF x 150,000 customers at SF x 1,500,000 orders, every third without
orders), one of the five priorities, ``Clerk#`` and nine digits over
[1, n_orders / 1500], ship priority 0, and a comment of 19 to 78
characters (0.4 to 1.6 x 49, dbgen's ``text[49]``) sliced from the same
pool as LINEITEM's.

Decimals are 8-byte floats (``l_quantity`` an 8-byte integer, its values
are whole), identifiers 8-byte integers, dates date32. A table is cut into
``n_files`` parquet files of equal row counts (dbgen's ``-C``), in order-key
order. The same seed gives the same bytes.

The columns a cell's index holds are also returned by name (``cols``; any
column of the table): numbers as numpy arrays, dates as int32 days since
the epoch, strings as numpy object arrays of ``str``, equal to the file's
column. They are the input of the plain reference (``reference.py``),
which never sees anything the program wrote.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

ITEM_COLS = ("l_orderkey", "l_shipdate", "l_quantity", "l_extendedprice")
LINEITEM_COLS = (
    "l_orderkey", "l_partkey", "l_suppkey", "l_linenumber", "l_quantity",
    "l_extendedprice", "l_discount", "l_tax", "l_returnflag", "l_linestatus",
    "l_shipdate", "l_commitdate", "l_receiptdate", "l_shipinstruct",
    "l_shipmode", "l_comment",
)
# LINEITEM's columns that are numbers or dates
NUMPY_COLS = LINEITEM_COLS[:8] + LINEITEM_COLS[10:13]
ORDERS_COLS = (
    "o_orderkey", "o_custkey", "o_orderstatus", "o_totalprice", "o_orderdate",
    "o_orderpriority", "o_clerk", "o_shippriority", "o_comment",
)
TABLES = {"lineitem": LINEITEM_COLS, "orders": ORDERS_COLS}
ITEMS_PER_ORDER = 4          # the mean of 1..7
ROWS_PER_ORDER = {"lineitem": ITEMS_PER_ORDER, "orders": 1}

_EPOCH = np.datetime64("1970-01-01")
STARTDATE = int((np.datetime64("1992-01-01") - _EPOCH).astype(np.int64))
ENDDATE = int((np.datetime64("1998-12-31") - _EPOCH).astype(np.int64))
CURRENTDATE = int((np.datetime64("1995-06-17") - _EPOCH).astype(np.int64))
INSTRUCTIONS = ("DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN")
MODES = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")
PRIORITIES = ("1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW")
ORDER_STATUSES = ("F", "O", "P")
O_COMMENT_LEN = (19, 78)     # dbgen's text[49]: 0.4 to 1.6 x 49 characters
_WORDS = (
    "furiously sly carefully blithe quick fluffy slow quiet ruthless thin close "
    "dogged daring bold final ironic even special pending regular express "
    "packages requests accounts deposits foxes ideas theodolites pinto beans "
    "instructions dependencies excuses platelets asymptotes courts dolphins "
    "sleep wake are cajole haggle nag use boost affix detect integrate "
    "about above according to across after against along among around"
).split()
_POOL_BYTES = 1 << 22


def order_key(index):
    """The key of order ``index`` (from 0): the first 8 of every 32."""
    index = np.asarray(index, dtype=np.int64)
    return (index // 8) * 32 + index % 8 + 1


def rows_per_order(table: str) -> int:
    """Rows of ``table`` an order; an unknown table raises."""
    if table not in ROWS_PER_ORDER:
        raise ValueError(f"no generator of table {table!r}: one of {sorted(ROWS_PER_ORDER)}")
    return ROWS_PER_ORDER[table]


def customers(n_orders: int) -> int:
    """Customer keys are 1..customers(n_orders): SF x 150,000 at SF x 1,500,000 orders."""
    return max(n_orders // 10, 1)


def clerks(n_orders: int) -> int:
    """Clerks are 1..clerks(n_orders): SF x 1,000 at SF x 1,500,000 orders."""
    return max(n_orders // 1500, 1)


def _items_per_order(rng, n_orders: int) -> np.ndarray:
    """As many orders of each size 1..7, the remainder of size 4, dealt in
    the seed's order: the sum is 4 * n_orders whatever the seed."""
    each, rest = divmod(n_orders, 7)
    counts = np.concatenate([np.repeat(np.arange(1, 8), each), np.full(rest, 4)])
    return rng.permutation(counts).astype(np.int64)


def _text_pool(rng) -> np.ndarray:
    words = rng.integers(0, len(_WORDS), _POOL_BYTES // 5)
    text = " ".join(_WORDS[i] for i in words).encode()[:_POOL_BYTES]
    return np.frombuffer(text, dtype=np.uint8)


def _comments(pool: np.ndarray, start: int, lengths: np.ndarray) -> pa.Array:
    """Consecutive slices of the pool (taken round and round from
    ``start``) of the given lengths, as one string array."""
    offsets = np.zeros(len(lengths) + 1, dtype=np.int32)
    np.cumsum(lengths, out=offsets[1:])
    total = int(offsets[-1])
    data = np.resize(np.roll(pool, -start), total)
    return pa.Array.from_buffers(
        pa.string(), len(lengths),
        [None, pa.py_buffer(offsets), pa.py_buffer(data)])


def _choice(values, picks: np.ndarray) -> pa.Array:
    return pa.DictionaryArray.from_arrays(
        pa.array(picks), pa.array(values)).cast(pa.string())


def _draw_lineitem(rng, n_orders: int, n_files: int) -> dict:
    """Every draw of LINEITEM, in the order its bytes depend on."""
    per_order = _items_per_order(rng, n_orders)
    n = int(per_order.sum())
    first = np.cumsum(per_order) - per_order
    order_of = np.repeat(np.arange(n_orders, dtype=np.int64), per_order)
    d = {"n": n, "per_order": per_order, "first": first}
    d["l_orderkey"] = order_key(order_of)
    d["l_linenumber"] = (np.arange(n, dtype=np.int64) - first[order_of] + 1).astype(np.int32)
    d["o_orderdate"] = rng.integers(STARTDATE, ENDDATE - 151 + 1, n_orders, dtype=np.int32)
    orderdate = d["o_orderdate"][order_of]
    del order_of

    n_parts = max(n_orders * 2 // 15, 1)       # SF x 200,000 at SF x 1,500,000 orders
    n_supps = max(n_orders // 150, 1)          # SF x 10,000
    l_partkey = rng.integers(1, n_parts + 1, n, dtype=np.int64)
    hop = rng.integers(0, 4, n, dtype=np.int64)
    d["l_partkey"] = l_partkey
    d["l_suppkey"] = (l_partkey + hop * (n_supps // 4 + (l_partkey - 1) // n_supps)) % n_supps + 1
    del hop
    l_quantity = rng.integers(1, 51, n, dtype=np.int64)
    retail_cents = 90000 + (l_partkey // 10) % 20001 + 100 * (l_partkey % 1000)
    d["l_quantity"] = l_quantity
    d["l_extendedprice"] = (l_quantity * retail_cents) / 100.0
    del retail_cents
    d["l_discount"] = rng.integers(0, 11, n) / 100.0
    d["l_tax"] = rng.integers(0, 9, n) / 100.0
    l_shipdate = orderdate + rng.integers(1, 122, n, dtype=np.int32)
    d["l_shipdate"] = l_shipdate
    d["l_commitdate"] = orderdate + rng.integers(30, 91, n, dtype=np.int32)
    del orderdate
    l_receiptdate = l_shipdate + rng.integers(1, 31, n, dtype=np.int32)
    d["l_receiptdate"] = l_receiptdate
    returned = rng.integers(0, 2, n, dtype=np.int8)   # R or A
    d["flag"] = np.where(l_receiptdate <= CURRENTDATE, returned, 2).astype(np.int8)
    d["status"] = (l_shipdate > CURRENTDATE).astype(np.int8)     # F or O
    d["instruct"] = rng.integers(0, len(INSTRUCTIONS), n, dtype=np.int8)
    d["mode"] = rng.integers(0, len(MODES), n, dtype=np.int8)
    d["comment_len"] = rng.integers(10, 44, n, dtype=np.int32)
    d["pool"] = _text_pool(rng)
    d["pool_starts"] = rng.integers(0, _POOL_BYTES, n_files)
    return d


def _made(d: dict, name: str):
    return lambda i, s: d[name][s]


def _dated(d: dict, name: str):
    return lambda i, s: pa.array(d[name][s], type=pa.date32())


def _lineitem(d: dict) -> tuple:
    """-> (rows, LINEITEM's columns in file order as ``make(i, s)``, the
    column of file ``i``'s rows ``s``; the numbers and dates whole)."""
    makers = {
        "l_orderkey": _made(d, "l_orderkey"), "l_partkey": _made(d, "l_partkey"),
        "l_suppkey": _made(d, "l_suppkey"), "l_linenumber": _made(d, "l_linenumber"),
        "l_quantity": _made(d, "l_quantity"), "l_extendedprice": _made(d, "l_extendedprice"),
        "l_discount": _made(d, "l_discount"), "l_tax": _made(d, "l_tax"),
        "l_returnflag": lambda i, s: _choice(("R", "A", "N"), d["flag"][s]),
        "l_linestatus": lambda i, s: _choice(("F", "O"), d["status"][s]),
        "l_shipdate": _dated(d, "l_shipdate"), "l_commitdate": _dated(d, "l_commitdate"),
        "l_receiptdate": _dated(d, "l_receiptdate"),
        "l_shipinstruct": lambda i, s: _choice(INSTRUCTIONS, d["instruct"][s]),
        "l_shipmode": lambda i, s: _choice(MODES, d["mode"][s]),
        "l_comment": lambda i, s: _comments(d["pool"], int(d["pool_starts"][i]), d["comment_len"][s]),
    }
    return d["n"], makers, {c: d[c] for c in NUMPY_COLS}


def _sum_by_order(values: np.ndarray, first: np.ndarray, per_order: np.ndarray) -> np.ndarray:
    """Each order's lines' values added one after another in line-number
    order (``np.add.reduceat`` may add them in another order)."""
    total = np.zeros(len(first), dtype=values.dtype)
    for line in range(int(per_order.max(initial=0))):
        has = per_order > line
        total[has] += values[first[has] + line]
    return total


def _orders(d: dict, seed: int, n_orders: int, n_files: int) -> tuple:
    """ORDERS from LINEITEM's draws ``d`` and a child stream of the seed,
    as ``_lineitem`` gives LINEITEM."""
    rng = np.random.default_rng(np.random.SeedSequence(seed).spawn(1)[0])
    first = d["first"]
    charge = d["l_extendedprice"] * (1.0 + d["l_tax"]) * (1.0 - d["l_discount"])
    open_lines = np.add.reduceat(d["status"].astype(np.int64), first)
    eligible = customers(n_orders) - customers(n_orders) // 3
    pick = rng.integers(0, eligible, n_orders, dtype=np.int64)
    o = {
        "o_orderkey": order_key(np.arange(n_orders)),
        "o_custkey": 3 * (pick // 2) + 1 + pick % 2,     # the pick-th key that is no multiple of 3
        "o_totalprice": _sum_by_order(charge, first, d["per_order"]),
        "o_orderdate": d["o_orderdate"],
        "o_shippriority": np.zeros(n_orders, dtype=np.int32),
        # F: every line F; O: every line O; P: both
        "status": np.where(open_lines == 0, 0, np.where(open_lines == d["per_order"], 1, 2)).astype(np.int8),
        "priority": rng.integers(0, len(PRIORITIES), n_orders, dtype=np.int8),
        "clerk": rng.integers(0, clerks(n_orders), n_orders, dtype=np.int32),
        "comment_len": rng.integers(O_COMMENT_LEN[0], O_COMMENT_LEN[1] + 1, n_orders, dtype=np.int32),
        "pool": d["pool"],
        "pool_starts": rng.integers(0, _POOL_BYTES, n_files),
    }
    clerk_names = [f"Clerk#{k:09d}" for k in range(1, clerks(n_orders) + 1)]
    makers = {
        "o_orderkey": _made(o, "o_orderkey"), "o_custkey": _made(o, "o_custkey"),
        "o_orderstatus": lambda i, s: _choice(ORDER_STATUSES, o["status"][s]),
        "o_totalprice": _made(o, "o_totalprice"), "o_orderdate": _dated(o, "o_orderdate"),
        "o_orderpriority": lambda i, s: _choice(PRIORITIES, o["priority"][s]),
        "o_clerk": lambda i, s: _choice(clerk_names, o["clerk"][s]),
        "o_shippriority": _made(o, "o_shippriority"),
        "o_comment": lambda i, s: _comments(o["pool"], int(o["pool_starts"][i]), o["comment_len"][s]),
    }
    numbers = ("o_orderkey", "o_custkey", "o_totalprice", "o_orderdate", "o_shippriority")
    return n_orders, makers, {c: o[c] for c in numbers}


def gen_table(tmp: str, table: str, n_orders: int, n_files: int, seed: int, cols=()):
    """Write ``table`` (``lineitem`` or ``orders``) of ``n_orders`` orders
    under ``tmp/<table>`` -> (directory, the columns named by ``cols``).
    What is written does not depend on ``cols``."""
    rows_per_order(table)
    unknown = [c for c in cols if c not in TABLES[table]]
    if unknown:
        raise ValueError(f"no column(s) {unknown} in {table.upper()}: one of {TABLES[table]}")
    d = _draw_lineitem(np.random.default_rng(seed), n_orders, n_files)
    n, makers, numbers = _orders(d, seed, n_orders, n_files) if table == "orders" else _lineitem(d)
    del d
    files = [slice(i * n // n_files, (i + 1) * n // n_files) for i in range(n_files)]
    out_dir = os.path.join(tmp, table)
    os.makedirs(out_dir)

    def write(i: int) -> None:
        t = pa.table({name: make(i, files[i]) for name, make in makers.items()})
        pq.write_table(t, os.path.join(out_dir, f"part{i}.parquet"))

    with ThreadPoolExecutor(max_workers=min(n_files, 8)) as pool:
        list(pool.map(write, range(n_files)))
    out = {}
    for c in cols:
        if c in numbers:
            out[c] = numbers[c]
        else:   # a string column, file by file as it was written
            out[c] = np.concatenate([makers[c](i, s).to_numpy(zero_copy_only=False)
                                     for i, s in enumerate(files)])
    return out_dir, out


def gen_lineitem(tmp: str, n_orders: int, n_files: int, seed: int, cols=ITEM_COLS):
    """Write LINEITEM under ``tmp/lineitem`` -> (directory, the columns
    named by ``cols``)."""
    return gen_table(tmp, "lineitem", n_orders, n_files, seed, cols)
