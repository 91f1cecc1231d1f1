"""The faults of a data-skipping build (driver ``skip_build``), each
planted in the sketch table as the build hands it to its write: a bit of
a key that is there cleared, a file's range narrowed, two files' rows
given each other's ids. A check that looked at answers alone would pass
the first two wherever no query meets the broken file."""

from faults import Fault

# what a lookup or a range reads where it meets the broken row
_QUERIES = frozenset({"files_kept_differ", "pruned_files_with_rows", "lookup_answers_wrong"})


def _at_write(change) -> None:
    """Every build's sketch table passes through ``change`` before it is written."""
    from hyperspace_tpu.indexes.dataskipping import DataSkippingIndex

    real = DataSkippingIndex.write

    def broken(self, ctx, index_data):
        return real(self, ctx, change(index_data))

    DataSkippingIndex.write = broken


def _column(table, prefix: str) -> str:
    return next(c for c in table.column_names if c.startswith(prefix))


def _minmax(table) -> tuple:
    """(the min column's name, the max column's)."""
    lo = next(c for c in table.column_names if c.startswith("MinMax_") and c.endswith("__min"))
    return lo, lo[: -len("__min")] + "__max"


def _with(table, name: str, values: list):
    import pyarrow as pa

    i = table.column_names.index(name)
    return table.set_column(i, name, pa.array(values, type=table.schema.field(name).type))


def bloom_bit_cleared() -> None:
    """The lowest set bit of the first row's bloom cleared: a bit that
    some key of that file set."""
    def change(table):
        name = _column(table, "BloomFilter_")
        blobs = table.column(name).to_pylist()
        b = bytearray(blobs[0])
        at = next(i for i, byte in enumerate(b) if byte)
        b[at] &= b[at] - 1
        blobs[0] = bytes(b)
        return _with(table, name, blobs)

    _at_write(change)


def minmax_narrowed() -> None:
    """The first row's max set to its min."""
    def change(table):
        lo, hi = _minmax(table)
        highs = table.column(hi).to_pylist()
        highs[0] = table.column(lo)[0].as_py()
        return _with(table, hi, highs)

    _at_write(change)


def file_ids_swapped() -> None:
    """The file ids of the rows of the first and the last file in key
    order (the smallest min, the largest max) swapped: a range at either
    end of the keys scans the other file."""
    def change(table):
        lo, hi = _minmax(table)
        mins, maxs = table.column(lo).to_pylist(), table.column(hi).to_pylist()
        first, last = mins.index(min(mins)), maxs.index(max(maxs))
        ids = table.column("_data_file_id").to_pylist()
        ids[first], ids[last] = ids[last], ids[first]
        return _with(table, "_data_file_id", ids)

    _at_write(change)


FAULTS = {
    # a lookup of a key whose bit was cleared prunes that file
    "bloom_bit_cleared": Fault(bloom_bit_cleared, frozenset({"bloom_bits_differ"}), _QUERIES),
    "minmax_narrowed": Fault(minmax_narrowed, frozenset({"minmax_differs"}), _QUERIES),
    # the first range begins at the smallest key: its plan holds the wrong file
    "file_ids_swapped": Fault(file_ids_swapped,
                              frozenset({"minmax_differs", "bloom_bits_differ", "files_kept_differ"}),
                              frozenset({"pruned_files_with_rows", "lookup_answers_wrong"})),
}
