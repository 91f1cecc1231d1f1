"""The plain reference of a covering index on a composite key: numpy over
the generated columns.

Imports nothing of ``hyperspace_tpu`` and reads nothing the program
wrote; digests stay ``reference.digest``. The configuration
``tpch-q9-1chip`` indexes LINEITEM on TPC-H Q9's two-column join key
(``l_partkey``, ``l_suppkey``), and states three things of a row that
this file works out on its own:

``bucket_of_pairs``  the bucket a pair belongs in: MurmurHash3 (x86, 32
                     bits, seed 42) over the two keys' sixteen
                     little-endian bytes as ONE stream of four 4-byte
                     blocks (the first key's low word, its high word,
                     the second key's low word, its high word), length
                     16, modulo the number of buckets, unsigned. Written
                     here from the published algorithm; ``bucket_of_keys``
                     is the same over any number of key columns, and for
                     one it is ``reference.bucket_of``.
``lex_unsorted``     adjacent rows of a file whose pair DEcreases in
                     lexicographic order, the first key major: a file
                     sorted on its first key alone is caught by its ties.
``PairIndex``        the rows of a pair, for lookups.
"""

from __future__ import annotations

import numpy as np


def _rotl(x: np.ndarray, r: int) -> np.ndarray:
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def _blocks(keys) -> list:
    """Each int64 column's eight little-endian bytes as two 4-byte
    blocks, low word first, one column after another."""
    out = []
    for column in keys:
        u = np.ascontiguousarray(column, dtype=np.int64).view(np.uint64)
        out.append((u & np.uint64(0xFFFFFFFF)).astype(np.uint32))
        out.append((u >> np.uint64(32)).astype(np.uint32))
    return out


def murmur3_32_blocks(blocks, seed: int) -> np.ndarray:
    """MurmurHash3_x86_32 of a stream of whole 4-byte blocks (no tail),
    row by row -> uint32. ``blocks`` are uint32 arrays of one length."""
    h = np.full(len(blocks[0]), seed, dtype=np.uint32)
    with np.errstate(over="ignore"):
        for k in blocks:
            k = _rotl(k * np.uint32(0xCC9E2D51), 15) * np.uint32(0x1B873593)
            h = _rotl(h ^ k, 13) * np.uint32(5) + np.uint32(0xE6546B64)
        h ^= np.uint32(4 * len(blocks))        # the length in bytes
        h ^= h >> np.uint32(16)
        h *= np.uint32(0x85EBCA6B)
        h ^= h >> np.uint32(13)
        h *= np.uint32(0xC2B2AE35)
        h ^= h >> np.uint32(16)
    return h


def bucket_of_keys(keys, num_buckets: int, seed: int = 42) -> np.ndarray:
    """``keys``: the indexed columns in the index's order, int64 each."""
    return (murmur3_32_blocks(_blocks(keys), seed) % np.uint32(num_buckets)).astype(np.int64)


def bucket_of_pairs(first, second, num_buckets: int, seed: int = 42) -> np.ndarray:
    return bucket_of_keys((first, second), num_buckets, seed)


def lex_unsorted(first, second) -> int:
    """Adjacent rows whose (first, second) pair decreases."""
    a, b = np.asarray(first), np.asarray(second)
    if len(a) < 2:
        return 0
    falls = (a[1:] < a[:-1]) | ((a[1:] == a[:-1]) & (b[1:] < b[:-1]))
    return int(np.count_nonzero(falls))


class PairIndex:
    """Rows of a table by a pair of key columns, by one sort of both."""

    def __init__(self, cols: dict, first: str, second: str):
        self.cols = cols
        self.order = np.lexsort((cols[second], cols[first]))    # the last key is the major one
        self.first = cols[first][self.order]
        self.second = cols[second][self.order]

    def rows_of(self, a: int, b: int) -> np.ndarray:
        """Row ids of the rows whose pair is (a, b); none is an answer."""
        lo = np.searchsorted(self.first, a, side="left")
        hi = np.searchsorted(self.first, a, side="right")
        seconds = self.second[lo:hi]
        return self.order[lo + np.searchsorted(seconds, b, side="left"):
                          lo + np.searchsorted(seconds, b, side="right")]

    def seconds_of(self, a: int) -> np.ndarray:
        """The distinct second keys that occur with the first key ``a``."""
        lo = np.searchsorted(self.first, a, side="left")
        hi = np.searchsorted(self.first, a, side="right")
        return np.unique(self.second[lo:hi])

    def answer(self, a: int, b: int, select) -> dict:
        rows = self.rows_of(a, b)
        return {c: self.cols[c][rows] for c in select}
