"""Bytes of the z-order build's bit interleave, from shapes alone (the
sketch's kernel of the additions-only proof: a new kernel's bytes
function comes in a module of its own, named by the metric's data file
under ``bytes_from``, and sees the configuration)."""

from __future__ import annotations

# bits of the z-address a column gets: ``ops/zorder.z_order_permutation``'s
# default, which no configuration key changes
BITS_PER_COLUMN = 16


def interleave_bytes(rows: int, config: dict) -> int:
    """``jit__interleave`` reads one 32-bit word a row of each indexed
    column and writes the z-address as 32-bit planes, ceil(indexed x 16
    / 32) of them. A few shifts and adds a bit: memory bounds it."""
    indexed = len(config["index"]["indexed"])
    planes = -(-indexed * BITS_PER_COLUMN // 32)
    return rows * 4 * (indexed + planes)
