"""Index kind ``dataskipping``: upstream's ``DataSkippingIndex`` — one
sketch row a source file, no included columns, no buckets; the rewritten
plan scans the source, fewer files."""

ABBR = "DS"     # what ``explain()`` prints for the kind


def index_config(config: dict):
    from hyperspace_tpu.indexes.dataskipping import DataSkippingIndexConfig
    from hyperspace_tpu.indexes.sketches import sketch_from_dict

    ix = config["index"]
    return DataSkippingIndexConfig(ix["name"], *map(sketch_from_dict, ix["sketches"]))
