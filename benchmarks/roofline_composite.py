"""Bytes of the bucket hash over a composite key, from shapes and the
configuration alone. Independent of what implements the kernel: a later
PR that changes the program cannot change this.

``roofline.bucket_hash_bytes`` is one 8-byte key's (12 B a row, whatever
the configuration says); this one counts the configuration's indexed
columns, so it is the same number for one column and the right one for
Q9's pair."""

from __future__ import annotations


def bucket_hash_bytes(rows: int, config: dict) -> int:
    """The bucket hash reads each indexed column's 8-byte key rep of a
    row (two 32-bit words a column) and writes the row's 4-byte bucket
    id. The murmur mix is a few dozen integer operations a word, far
    under the chip's rate: memory bounds it."""
    return rows * (8 * len(config["index"]["indexed"]) + 4)
