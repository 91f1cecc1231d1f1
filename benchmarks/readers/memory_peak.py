"""``memory_stats()["peak_bytes_in_use"]`` of the fullest device, in GB;
nothing where the backend reports none."""


def read(record: dict, arg: dict):
    peak = max(record["memory_peak_bytes"], default=0)
    return peak / 1e9 if peak else None
