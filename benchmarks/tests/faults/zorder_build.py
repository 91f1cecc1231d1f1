"""The faults of a z-order covering build (driver ``zorder_build``): an
answer altered where it is written, the written order broken, a captured
z-span narrowed so that pruning drops rows that qualify."""

from faults import Fault


def _first_file(path: str) -> bool:
    return path.endswith("part-00000-zorder.parquet")


def altered_value() -> None:
    """One float payload changed in the first z-ordered file written."""
    import pyarrow as pa

    from hyperspace_tpu.io import parquet as pio

    real = pio.write_table

    def broken(path, table, *args, **kw):
        if _first_file(path):
            i = table.column_names.index("l_extendedprice")
            prices = table.column(i).to_numpy().copy()
            prices[0] += 1.0
            table = table.set_column(i, table.field(i), pa.array(prices))
        return real(path, table, *args, **kw)

    pio.write_table = broken


def order_broken() -> None:
    """Two rows of different z-address swapped in the first file written:
    its first row and its last (whole rows, so every answer keeps its
    rows and only the order is wrong)."""
    import numpy as np
    import pyarrow as pa

    from hyperspace_tpu.io import parquet as pio

    real = pio.write_table

    def broken(path, table, *args, **kw):
        if _first_file(path) and table.num_rows > 1:
            order = np.arange(table.num_rows)
            order[0], order[-1] = order[-1], order[0]
            table = table.take(pa.array(order))
        return real(path, table, *args, **kw)

    pio.write_table = broken


def zspan_narrowed() -> None:
    """One row group's captured z-span replaced by the single address 0
    (the least ship date with no discount: in no Q6 box, whose discounts
    start at 0.01), so the range pruning drops that row group for every
    Q6-shaped query. The row group is the first whose footer says that
    every row of it has a discount, a quantity under 24 and a ship date
    in 1993..1997 — rows that qualify for whichever such year is asked —
    or, where no row group is that narrow, the largest. The read-back's
    predicate keeps every address, so it still sees every row."""
    import datetime
    import os

    import pyarrow.parquet as pq

    from hyperspace_tpu.indexes import zonemaps

    real = zonemaps._capture_zspans
    lo, hi = datetime.date(1993, 1, 1), datetime.date(1997, 12, 31)

    def all_rows_qualify(group, column_of) -> bool:
        def stats(name):
            return group.column(column_of[name]).statistics

        ship, disc, qty = stats("l_shipdate"), stats("l_discount"), stats("l_quantity")
        return disc.min > 0.0 and qty.max < 24 and lo <= ship.min and ship.max <= hi

    def broken(doc, files, *args, **kw):
        real(doc, files, *args, **kw)
        groups = []     # (all rows qualify, rows, file, row group)
        for f in files:
            meta = pq.read_metadata(f)
            column_of = {meta.schema.column(i).name: i for i in range(meta.num_columns)}
            groups += [(all_rows_qualify(meta.row_group(g), column_of), meta.row_group(g).num_rows, f, g)
                       for g in range(meta.num_row_groups)]
        narrow = [g for g in groups if g[0]]
        _q, _rows, f, g = narrow[0] if narrow else max(groups, key=lambda x: x[1])
        doc["files"][os.path.basename(f)]["rg_zspans"][g] = ["0", "0"]

    zonemaps._capture_zspans = broken


FAULTS = {
    "altered_value": Fault(altered_value, frozenset({"readback_digest_differs"}),
                           frozenset({"range_answers_wrong"})),
    "order_broken": Fault(order_broken, frozenset({"zorder_inversions"})),
    "zspan_narrowed": Fault(zspan_narrowed, frozenset({"range_answers_wrong"})),
}
