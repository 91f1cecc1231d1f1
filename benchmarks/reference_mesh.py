"""What a mesh adds to the plain reference: who owns a bucket, and how
many rows each chip has to send to each other chip.

A build over ``chips`` chips cuts the table, in row order, into ``chips``
equal contiguous blocks (the last one shorter when the rows do not
divide), one a chip; bucket ``b`` is exchanged to, sorted by and written
from chip ``b mod chips``.
Everything here follows from ``reference.bucket_of`` alone; nothing of
``hyperspace_tpu`` is imported.
"""

from __future__ import annotations

import numpy as np

import reference


def owner_of(bucket, chips: int):
    """The chip that owns a bucket."""
    return np.asarray(bucket) % chips


def source_of(n_rows: int, chips: int) -> np.ndarray:
    """The chip that holds each row before the exchange: contiguous
    blocks of ceil(n_rows / chips) rows."""
    block = -(-n_rows // chips) or 1
    return np.arange(n_rows, dtype=np.int64) // block


def peer_matrix(keys, num_buckets: int, chips: int) -> np.ndarray:
    """[chips, chips] rows that source chip i holds and chip j owns."""
    keys = np.asarray(keys)
    owner = owner_of(reference.bucket_of(keys, num_buckets), chips)
    flat = source_of(len(keys), chips) * chips + owner
    return np.bincount(flat, minlength=chips * chips).reshape(chips, chips)


def off_chip_rows(matrix: np.ndarray) -> int:
    """Rows whose owner is another chip than their source: what any
    exchange has to move between chips."""
    return int(matrix.sum() - np.trace(matrix))
