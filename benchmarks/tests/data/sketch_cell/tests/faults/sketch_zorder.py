"""The fault of a z-order build (driver ``sketch_zorder``): an answer
altered where it is produced."""

from faults import Fault


def altered_value() -> None:
    """One float payload changed in the first z-ordered file written."""
    import pyarrow as pa

    from hyperspace_tpu.io import parquet as pio

    real = pio.write_table

    def broken(path, table, *args, **kw):
        if path.endswith("part-00000-zorder.parquet"):
            i = table.column_names.index("l_extendedprice")
            prices = table.column(i).to_numpy().copy()
            prices[0] += 1.0
            table = table.set_column(i, table.field(i), pa.array(prices))
        return real(path, table, *args, **kw)

    pio.write_table = broken


FAULTS = {"altered_value": Fault(altered_value, frozenset({"readback_digest_differs"}))}
