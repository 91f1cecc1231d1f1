"""``covering_build.last_build_breakdown`` of every operation in the window.

arg: {"kind": <the operation's kind, as its driver names it>, "stage":
<key>} -> mean seconds of that stage per operation; {"kind": ..., "unnamed_share": true} -> the share
(%) of the operations' wall time outside the four named stages. On a
mesh the sort and write of the shards run side by side, so their wall
time (``tail_wall``) stands for the two.
"""

NAMED = ("scan", "hash_shuffle", "sort", "write")


def named_seconds(bd: dict) -> float:
    if "tail_wall" in bd:
        return bd.get("scan", 0.0) + bd.get("hash_shuffle", 0.0) + bd["tail_wall"]
    return sum(bd.get(k, 0.0) for k in NAMED)


def read(record: dict, arg: dict):
    ops = [o for o in record["ops"] if o["kind"] == arg["kind"] and o["breakdown"]]
    if not ops:
        return None
    if arg.get("unnamed_share"):
        wall = sum(o["wall_s"] for o in ops)
        return 100.0 * (1.0 - sum(named_seconds(o["breakdown"]) for o in ops) / wall)
    vals = [o["breakdown"][arg["stage"]] for o in ops if arg["stage"] in o["breakdown"]]
    return sum(vals) / len(vals) if vals else None
