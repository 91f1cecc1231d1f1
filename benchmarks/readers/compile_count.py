"""Backend compiles inside the window that the persistent cache did not
serve (``jax.monitoring``). Should read 0: every shape is warmed in set-up."""


def read(record: dict, arg: dict):
    return float(record["compiles_in_window"])
