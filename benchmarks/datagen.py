"""LINEITEM from ``--seed``: the one general data generator of the benchmark.

``gen_lineitem`` populates TPC-H's LINEITEM as the specification's clause
4.2.3 sets out (v3.0.1), all sixteen columns of clause 1.4.1, for
``n_orders`` orders:

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

Decimals are 8-byte floats (``l_quantity`` an 8-byte integer, its values
are whole), identifiers 8-byte integers, dates date32. The table is cut
into ``n_files`` parquet files of equal row counts (dbgen's ``-C``). The
same seed gives the same bytes.

The columns a cell's index holds are also returned as numpy arrays, by
name (``cols``; any of the eleven numeric and date columns): they are
the input of the plain reference (``reference.py``), which never sees
anything the program wrote.
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
# the columns that are numbers or dates: what a reference can be handed
NUMPY_COLS = LINEITEM_COLS[:8] + LINEITEM_COLS[10:13]
ITEMS_PER_ORDER = 4          # the mean of 1..7

_EPOCH = np.datetime64("1970-01-01")
STARTDATE = int((np.datetime64("1992-01-01") - _EPOCH).astype(np.int64))
ENDDATE = int((np.datetime64("1998-12-31") - _EPOCH).astype(np.int64))
CURRENTDATE = int((np.datetime64("1995-06-17") - _EPOCH).astype(np.int64))
INSTRUCTIONS = ("DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN")
MODES = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")
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


def _choice(values: tuple, picks: np.ndarray) -> pa.Array:
    return pa.DictionaryArray.from_arrays(
        pa.array(picks, type=pa.int8()), pa.array(values)).cast(pa.string())


def gen_lineitem(tmp: str, n_orders: int, n_files: int, seed: int, cols=ITEM_COLS):
    """Write LINEITEM under ``tmp`` -> (directory, the columns named by
    ``cols`` as numpy, dates as int32 days since the epoch). What is
    written does not depend on ``cols``."""
    unknown = [c for c in cols if c not in NUMPY_COLS]
    if unknown:
        raise ValueError(f"no numpy form of LINEITEM column(s) {unknown}: one of {NUMPY_COLS}")
    rng = np.random.default_rng(seed)
    items_dir = os.path.join(tmp, "lineitem")
    os.makedirs(items_dir)
    per_order = _items_per_order(rng, n_orders)
    n = int(per_order.sum())
    first = np.cumsum(per_order) - per_order
    order_of = np.repeat(np.arange(n_orders, dtype=np.int64), per_order)
    l_orderkey = order_key(order_of)
    l_linenumber = (np.arange(n, dtype=np.int64) - first[order_of] + 1).astype(np.int32)
    o_orderdate = rng.integers(STARTDATE, ENDDATE - 151 + 1, n_orders, dtype=np.int32)
    orderdate = o_orderdate[order_of]
    del order_of, first

    n_parts = max(n_orders * 2 // 15, 1)       # SF x 200,000 at SF x 1,500,000 orders
    n_supps = max(n_orders // 150, 1)          # SF x 10,000
    l_partkey = rng.integers(1, n_parts + 1, n, dtype=np.int64)
    hop = rng.integers(0, 4, n, dtype=np.int64)
    l_suppkey = (l_partkey + hop * (n_supps // 4 + (l_partkey - 1) // n_supps)) % n_supps + 1
    l_quantity = rng.integers(1, 51, n, dtype=np.int64)
    retail_cents = 90000 + (l_partkey // 10) % 20001 + 100 * (l_partkey % 1000)
    l_extendedprice = (l_quantity * retail_cents) / 100.0
    l_discount = rng.integers(0, 11, n) / 100.0
    l_tax = rng.integers(0, 9, n) / 100.0
    l_shipdate = orderdate + rng.integers(1, 122, n, dtype=np.int32)
    l_commitdate = orderdate + rng.integers(30, 91, n, dtype=np.int32)
    l_receiptdate = l_shipdate + rng.integers(1, 31, n, dtype=np.int32)
    returned = rng.integers(0, 2, n, dtype=np.int8)   # R or A
    flag = np.where(l_receiptdate <= CURRENTDATE, returned, 2).astype(np.int8)
    status = (l_shipdate > CURRENTDATE).astype(np.int8)
    instruct = rng.integers(0, len(INSTRUCTIONS), n, dtype=np.int8)
    mode = rng.integers(0, len(MODES), n, dtype=np.int8)
    comment_len = rng.integers(10, 44, n, dtype=np.int32)
    pool = _text_pool(rng)
    pool_starts = rng.integers(0, _POOL_BYTES, n_files)

    def write(i: int) -> None:
        lo, hi = i * n // n_files, (i + 1) * n // n_files
        s = slice(lo, hi)
        table = pa.table({
            "l_orderkey": l_orderkey[s], "l_partkey": l_partkey[s],
            "l_suppkey": l_suppkey[s], "l_linenumber": l_linenumber[s],
            "l_quantity": l_quantity[s], "l_extendedprice": l_extendedprice[s],
            "l_discount": l_discount[s], "l_tax": l_tax[s],
            "l_returnflag": _choice(("R", "A", "N"), flag[s]),
            "l_linestatus": _choice(("F", "O"), status[s]),
            "l_shipdate": pa.array(l_shipdate[s], type=pa.date32()),
            "l_commitdate": pa.array(l_commitdate[s], type=pa.date32()),
            "l_receiptdate": pa.array(l_receiptdate[s], type=pa.date32()),
            "l_shipinstruct": _choice(INSTRUCTIONS, instruct[s]),
            "l_shipmode": _choice(MODES, mode[s]),
            "l_comment": _comments(pool, int(pool_starts[i]), comment_len[s]),
        })
        pq.write_table(table, os.path.join(items_dir, f"part{i}.parquet"))

    with ThreadPoolExecutor(max_workers=min(n_files, 8)) as pool_:
        list(pool_.map(write, range(n_files)))
    made = dict(zip(NUMPY_COLS, (
        l_orderkey, l_partkey, l_suppkey, l_linenumber, l_quantity, l_extendedprice,
        l_discount, l_tax, l_shipdate, l_commitdate, l_receiptdate)))
    return items_dir, {c: made[c] for c in cols}
