"""Bytes of the mesh exchange, from shapes alone, and the least time the
chips could take for them. Independent of what implements the exchange:
a later PR that changes the program cannot change this.

What any implementation must move: each row's index columns, at the
widths the configuration states for them (no key rep, no padding, no
bucket or validity plane), for the rows whose owner is another chip than
the one that holds them. Under a uniform hash that is (chips - 1) / chips
of the rows, and each chip sends its 1 / chips share of them. A chip
cannot send faster than its published interconnect rate, whatever the
topology: that bounds the time from below.
"""

from __future__ import annotations

import roofline

TYPE_BYTES = {"int64": 8, "float64": 8, "date32": 4, "int32": 4}


def index_row_bytes(config: dict) -> int:
    """Bytes of one row of the index: its indexed and included columns,
    each at the width of the type the configuration's schema states."""
    schema = config["schema"]["lineitem"]
    columns = config["index"]["indexed"] + config["index"]["included"]
    return sum(TYPE_BYTES[schema[c].split(":")[0].split()[0]] for c in columns)


def bytes_out_of_a_chip(rows: int, chips: int, row_bytes: int) -> float:
    """Bytes one chip has to send to the others in one exchange."""
    return rows * row_bytes * (chips - 1) / chips / chips


def least_seconds(n_bytes: float, device_kind: str) -> float:
    return n_bytes / (roofline.peaks(device_kind)["ici_gbit_per_s"] / 8 * 1e9)
