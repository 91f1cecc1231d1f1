"""Bytes of the z-order build's two device programs, from shapes and the
configuration alone, for the work of ONE build: one z-address a row, one
permutation of the rows. Independent of what implements them: a later PR
that changes the program cannot change this. Both are memory-bound: a
few shifts and adds, or compares, a byte."""

from __future__ import annotations

# bits of the z-address a column gets: the layout's, which no
# configuration key changes (``reference_zorder.BITS``)
BITS_PER_COLUMN = 16


def planes(config: dict) -> int:
    """32-bit planes of the z-address: ceil(indexed x 16 / 32)."""
    return -(-len(config["index"]["indexed"]) * BITS_PER_COLUMN // 32)


def interleave_bytes(rows: int, config: dict) -> int:
    """The bit interleave reads one 32-bit word a row of each indexed
    column and writes the z-address as 32-bit planes."""
    return rows * 4 * (len(config["index"]["indexed"]) + planes(config))


def lexsort_bytes(rows: int, config: dict) -> int:
    """The bytes any sort by z-address must read and write once,
    whatever implements it: the planes in, a 4-byte row index out."""
    return rows * (4 * planes(config) + 4)
