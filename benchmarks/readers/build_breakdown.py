"""``covering_build.last_build_breakdown`` of every operation in the window.

arg: {"kind": <the operation's kind, as its driver names it>, "stage":
<key>} -> mean seconds of that stage per operation. On a mesh the sort
and write of the shards run side by side, and their wall time is the
stage ``tail_wall``.
"""


def read(record: dict, arg: dict):
    ops = [o for o in record["ops"] if o["kind"] == arg["kind"] and o["breakdown"]]
    vals = [o["breakdown"][arg["stage"]] for o in ops if arg["stage"] in o["breakdown"]]
    return sum(vals) / len(vals) if vals else None
