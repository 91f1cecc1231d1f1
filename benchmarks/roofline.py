"""Operations and bytes of the device kernels, from shapes alone, and the
least time the chip could take for them. Independent of what implements
the kernel: a later PR that changes the program cannot change this."""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmarks/peaks.json: "
            "add its published peaks with their source"
        )
    return table[device_kind]


def bucket_hash_bytes(rows: int, config: dict) -> int:
    """The bucket hash reads each row's 8-byte key (two 32-bit words) and
    writes its 4-byte bucket id. The murmur mix is a few dozen integer
    operations a row, far under the chip's rate: memory bounds it.

    Every bytes function takes (rows, configuration); this one needs no
    more than the rows, since any key is hashed as one 8-byte rep."""
    return rows * (8 + 4)


def least_seconds(n_bytes: int, device_kind: str) -> float:
    return n_bytes / (peaks(device_kind)["hbm_gb_per_s"] * 1e9)
