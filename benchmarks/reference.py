"""The plain reference: numpy over the generated columns.

Imports nothing of ``hyperspace_tpu`` and reads nothing the program
wrote. An answer is compared as an order-independent digest of its rows
— [row count, sum and xor of a 64-bit mix of every value's bit pattern]
— so a float payload has to come back bit for bit.

``bucket_of`` is the bucket a key belongs in, as the configuration states
it: MurmurHash3 (x86, 32 bits, seed 42) over the key's eight little-endian
bytes, modulo the number of buckets. It is written here from the published
algorithm, so that a build whose hash went wrong is seen.

``lossy`` is the control: the reference's own answers with the float
payload passed through float32, the loss a float64 column suffers on a
device that holds no IEEE double (PERF.md, PR 21). A comparison that
cannot tell that from the exact answer decides nothing.
"""

from __future__ import annotations

import numpy as np

_MIX = np.uint64(0x9E3779B97F4A7C15)


def _bits(values) -> np.ndarray:
    v = np.asarray(values)
    if v.dtype.kind == "f":
        return np.ascontiguousarray(v, dtype=np.float64).view(np.uint64)
    if v.dtype.kind in "mM":
        v = v.astype("datetime64[D]").astype(np.int64)
    return np.ascontiguousarray(v, dtype=np.int64).view(np.uint64)


def row_hashes(cols: dict) -> np.ndarray:
    """One uint64 per row over all columns, taken in name order."""
    names = sorted(cols)
    n = len(cols[names[0]]) if names else 0
    h = np.zeros(n, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for i, name in enumerate(names):
            h = (h ^ (_bits(cols[name]) + np.uint64(i + 1))) * _MIX
            h ^= h >> np.uint64(29)
    return h


def digest(cols: dict) -> tuple:
    """(rows, sum, xor) of the row hashes: equal multisets of rows give
    equal digests."""
    h = row_hashes(cols)
    if not len(h):
        return (0, 0, 0)
    with np.errstate(over="ignore"):
        return (len(h), int(h.sum(dtype=np.uint64)), int(np.bitwise_xor.reduce(h)))


def segment_digests(cols: dict, counts: np.ndarray) -> np.ndarray:
    """Digests of consecutive row groups of ``cols`` of the given sizes
    -> uint64 [len(counts), 3] (rows, sum, xor); an empty group is 0s."""
    counts = np.asarray(counts, dtype=np.int64)
    out = np.zeros((len(counts), 3), dtype=np.uint64)
    out[:, 0] = counts.astype(np.uint64)
    h = row_hashes(cols)
    full = counts > 0
    if len(h) and full.any():
        starts = (np.cumsum(counts) - counts)[full]
        with np.errstate(over="ignore"):
            out[full, 1] = np.add.reduceat(h, starts)
        out[full, 2] = np.bitwise_xor.reduceat(h, starts)
    return out


def table_cols(table) -> dict:
    """pyarrow table -> {name: numpy}, dates as int32 days."""
    import pyarrow as pa

    out = {}
    for name in table.column_names:
        col = table.column(name)
        if pa.types.is_date32(col.type):
            col = col.cast(pa.int32())
        out[name] = col.combine_chunks().to_numpy(zero_copy_only=False)
    return out


def lossy(cols: dict) -> dict:
    """The control's answer: float columns through float32."""
    return {
        k: v.astype(np.float32).astype(np.float64) if v.dtype.kind == "f" else v
        for k, v in cols.items()
    }


class KeyIndex:
    """Rows of a table by key, by a sort of the key column."""

    def __init__(self, cols: dict, key: str):
        self.cols = cols
        self.key = key
        self.order = np.argsort(cols[key], kind="stable")
        self.sorted_keys = cols[key][self.order]

    def rows_of_ranges(self, lo, hi):
        """Row ids of keys in [lo[i], hi[i]) for each i, concatenated ->
        (row ids, rows per range)."""
        a = np.searchsorted(self.sorted_keys, np.asarray(lo), side="left")
        b = np.searchsorted(self.sorted_keys, np.asarray(hi), side="left")
        counts = (b - a).astype(np.int64)
        total = int(counts.sum())
        if not total:
            return np.zeros(0, dtype=np.int64), counts
        starts = np.repeat(a - (np.cumsum(counts) - counts), counts)
        return self.order[starts + np.arange(total)], counts


# -- the point lookup's plain answer -----------------------------------------

def ref_point(index: KeyIndex, keys, select) -> tuple:
    """Point lookups ``key == k`` -> (columns of all answers' rows
    concatenated in request order, rows per answer)."""
    keys = np.asarray(keys, dtype=np.int64)
    rows, counts = index.rows_of_ranges(keys, keys + 1)
    return {c: index.cols[c][rows] for c in select}, counts


# -- where a key belongs -------------------------------------------------------

def _rotl(x: np.ndarray, r: int) -> np.ndarray:
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def murmur3_32_int64(keys, seed: int) -> np.ndarray:
    """MurmurHash3_x86_32 of each int64's eight little-endian bytes (two
    4-byte blocks, low word first; no tail) -> uint32."""
    u = np.ascontiguousarray(keys, dtype=np.int64).view(np.uint64)
    blocks = ((u & np.uint64(0xFFFFFFFF)).astype(np.uint32),
              (u >> np.uint64(32)).astype(np.uint32))
    h = np.full(len(u), seed, dtype=np.uint32)
    with np.errstate(over="ignore"):
        for k in blocks:
            k = _rotl(k * np.uint32(0xCC9E2D51), 15) * np.uint32(0x1B873593)
            h = _rotl(h ^ k, 13) * np.uint32(5) + np.uint32(0xE6546B64)
        h ^= np.uint32(8)                      # the length in bytes
        h ^= h >> np.uint32(16)
        h *= np.uint32(0x85EBCA6B)
        h ^= h >> np.uint32(13)
        h *= np.uint32(0xC2B2AE35)
        h ^= h >> np.uint32(16)
    return h


def bucket_of(keys, num_buckets: int, seed: int = 42) -> np.ndarray:
    return (murmur3_32_int64(keys, seed) % np.uint32(num_buckets)).astype(np.int64)
