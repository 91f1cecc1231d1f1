"""The z-layout of a z-order covering index, written down once and
independently: numpy only, nothing of ``hyperspace_tpu`` is imported and
nothing the program wrote is read.

A z-order covering index holds its rows in non-decreasing order of a
**z-address** built from the indexed columns, within each data file and
from file to file in name order. The address, for ``k`` indexed columns
and 16 bits a column:

``enc``      the value's order-preserving unsigned 64-bit encoding: a
             signed integer or a ``date32`` day count offset by 2^63; a
             float64 in IEEE total order (sign bit set -> all bits
             flipped, else the sign bit set)
``word``     trunc(min((enc - min) as float64 x ((2^16 - 1) / (max - min)
             as float64), 2^16 - 1)), min and max being the column's
             extremes in encoding space; 0 where max = min
``address``  bit t (most significant first) = bit (15 - t div k) of
             column (t mod k), the first indexed column first: 16 k bits

Rows of equal address are in no stated order.

Departures from upstream Hyperspace, which this program makes and this
reference follows: upstream's ``ZOrderField`` keeps (value - min) at the
bit length of (max - min) and interleaves through a bit-index map; here
every column is scaled onto 16 bits (``hyperspace_tpu/ops/zorder.py``).
And a float column is scaled in *encoding* space, not value space: on
``l_discount`` (0.00 .. 0.10) the value 0.00 maps to word 0 and 0.01 ..
0.10 to words 65321 .. 65534, the top ~0.3%, so the column separates
"no discount" from "some" in its first bit and tells the ten other
values apart only in its last eight: that is what the layout is worth
on it.

``range_rows`` is the plain answer of a TPC-H Q6-shaped range query
(clause 2.4.6.2's predicate) over the generated columns.
"""

from __future__ import annotations

import numpy as np

BITS = 16
_TOP_BIT = np.uint64(1 << 63)


def order_u64(values) -> np.ndarray:
    """Order-preserving uint64 of a column: a < b as values <=> enc(a) <
    enc(b) as unsigned integers (-0.0 below +0.0)."""
    v = np.asarray(values)
    if v.dtype.kind == "f":
        bits = np.ascontiguousarray(v, dtype=np.float64).view(np.uint64)
        return np.where(bits >> np.uint64(63) == 1, ~bits, bits | _TOP_BIT)
    if v.dtype.kind in "mM":
        v = v.astype("datetime64[D]").astype(np.int64)
    if v.dtype.kind != "i":
        raise TypeError(f"no z-encoding here for dtype {v.dtype}")
    return np.ascontiguousarray(v, dtype=np.int64).view(np.uint64) ^ _TOP_BIT


def words(values, lo, hi, bits: int = BITS) -> np.ndarray:
    """The column's ``bits``-bit words under min/max scaling; ``lo`` and
    ``hi`` are the column's extremes, as values."""
    enc = order_u64(values)
    enc_lo = int(order_u64(np.asarray([lo], dtype=np.asarray(values).dtype))[0])
    enc_hi = int(order_u64(np.asarray([hi], dtype=np.asarray(values).dtype))[0])
    top = float((1 << bits) - 1)
    if enc_hi == enc_lo:
        return np.zeros(len(enc), dtype=np.uint64)
    scale = top / float(enc_hi - enc_lo)
    scaled = (enc - np.uint64(enc_lo)).astype(np.float64)
    scaled *= scale
    np.minimum(scaled, top, out=scaled)
    return scaled.astype(np.uint64)     # truncates: the values are not negative


def min_max(cols: dict, indexed: list) -> tuple:
    """(mins, maxs) of the indexed columns, as values."""
    return ([cols[c].min() for c in indexed], [cols[c].max() for c in indexed])


def _spread(k: int, bits: int) -> np.ndarray:
    """table[w] = the word ``w`` with its bit b moved to position b * k:
    room for the other columns' bits between its own. Built bit by bit
    over every possible word, so that an address is k look-ups and not
    k * bits passes over the rows."""
    w = np.arange(1 << bits, dtype=np.uint64)
    table = np.zeros(1 << bits, dtype=np.uint64)
    for b in range(bits):
        table |= ((w >> np.uint64(b)) & np.uint64(1)) << np.uint64(b * k)
    return table


def z_address(cols: dict, indexed: list, mins, maxs, bits: int = BITS) -> np.ndarray:
    """The z-address of every row -> uint64 [n], ``len(indexed) * bits``
    bits wide (48 for three columns). Address bit t from the top is bit
    (bits - 1 - t div k) of column (t mod k): counted from the bottom,
    bit b of column j lies at position b * k + (k - 1 - j)."""
    k = len(indexed)
    if k * bits > 64:
        raise ValueError(f"{k} columns of {bits} bits do not fit 64")
    table = _spread(k, bits)
    n = len(cols[indexed[0]]) if indexed else 0
    address = np.zeros(n, dtype=np.uint64)
    for j, c in enumerate(indexed):
        address |= table[words(cols[c], mins[j], maxs[j], bits)] << np.uint64(k - 1 - j)
    return address


def inversions(address: np.ndarray) -> int:
    """Adjacent pairs whose address decreases."""
    return int(np.count_nonzero(address[1:] < address[:-1]))


def inversions_across(files_cols, indexed: list, mins, maxs) -> tuple:
    """The indexed columns of each data file, in name order -> (rows in
    all, adjacent pairs whose address decreases: inside a file and
    across each boundary between one file's last row and the next's
    first)."""
    rows = found = 0
    last = None
    for cols in files_cols:
        address = z_address(cols, indexed, mins, maxs)
        if not len(address):
            continue
        found += inversions(address) + int(last is not None and address[0] < last)
        rows, last = rows + len(address), address[-1]
    return rows, found


def range_rows(cols: dict, d0: int, d1: int, lo: float, hi: float, q: int) -> np.ndarray:
    """TPC-H Q6's predicate -> the mask of the rows it keeps:
    ``l_shipdate >= d0 AND l_shipdate < d1`` (days since the epoch)
    ``AND l_discount BETWEEN lo AND hi`` (both ends in) ``AND
    l_quantity < q``."""
    ship = np.asarray(cols["l_shipdate"])
    if ship.dtype.kind in "mM":
        ship = ship.astype("datetime64[D]").astype(np.int64)
    disc, qty = np.asarray(cols["l_discount"]), np.asarray(cols["l_quantity"])
    return (ship >= d0) & (ship < d1) & (disc >= lo) & (disc <= hi) & (qty < q)
