"""Bytes of the bloom filter's bit-index kernel, from shapes and the
configuration alone. Independent of what implements the kernel: a later
PR that changes the program cannot change this."""

from __future__ import annotations

import reference_skipping


def bit_indices_bytes(rows: int, config: dict) -> int:
    """The kernel reads each source row's 8-byte key rep (two 32-bit
    words) and writes its k 4-byte bit indices: (8 + 4 k) B a row, for
    the configuration's bloom (k = 7: 36 B). Its operations are two
    MurmurHash3_x86_32 of the two words (a few dozen integer operations
    each) and k multiply-adds and modulo operations a row, far under the
    chip's integer rate: memory bounds it. These are the source's rows,
    not the padded rows the program dispatches, so the share shows what
    the padding costs."""
    return rows * (8 + 4 * reference_skipping.bloom_of(config)["k"])
