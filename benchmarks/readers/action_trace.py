"""The program's own account of a lifecycle action: the spans of its
root in ``hyperspace_tpu.obs.trace.finished`` (one clock,
``perf_counter_ns``; every span has a start, an end and a parent).

arg: {"root": <root span name>, "kind": <the operation's kind, as its
driver names it>} and one of
  {"spans": [names]}           -> mean seconds per operation of the union
                                  of those spans' intervals (spans marked
                                  ``summed`` have no interval and are left out)
  {"unattributed_share": true} -> 100 x (sum of the operations' wall time -
                                  sum of the union of each root's direct
                                  children) / sum of the wall time, in %
  {"counter": name}            -> mean per operation of that root attribute

The last N roots of that name are read, N = the window's operations of
that kind, paired in order; nothing is returned unless there are N and
each lies inside its operation (``root.duration_s <= op["wall_s"]``), so
the warm-up's root is never read. A program that keeps no such trace (or
no such span or counter) gives nothing, and the metric is left out.
One ``bench: spans:`` table per root goes to stderr, once.
"""

import sys

_logged = set()


def finished_roots(name: str) -> list:
    """The program's finished roots of that name, oldest first, in a
    neutral form: {"trace_id", "span_id", "duration_s", "attrs",
    "spans": [{"name", "span_id", "parent_id", "start_ns", "end_ns",
    "summed", "attrs"}]} — or [] where the program records none."""
    from hyperspace_tpu.obs import trace

    out = []
    for root in trace.finished(name):
        spans = [s.to_dict() for s in list(root.spans)]
        if any(s.get("start_ns") is None or s.get("end_ns") is None for s in spans):
            return []   # a program whose spans have no interval on one clock
        out.append({"trace_id": root.trace_id, "span_id": root.span_id,
                    "duration_s": root.duration_s, "attrs": dict(root.attrs),
                    "spans": spans})
    return out


def union_s(spans: list) -> float:
    """Seconds that the spans' intervals cover (overlaps count once)."""
    covered, at = 0, None
    for s, e in sorted((sp["start_ns"], sp["end_ns"]) for sp in spans if not sp["summed"]):
        s = s if at is None else max(s, at)
        if e > s:
            covered += e - s
            at = e
    return covered / 1e9


def children(root: dict, span_id: str) -> list:
    return [s for s in root["spans"] if s["parent_id"] == span_id]


def table(root: dict) -> list:
    """name (indented by depth), seconds, self seconds, attrs."""
    by_id = {s["span_id"]: s for s in root["spans"]}
    lines = []

    def walk(span, depth):
        kids = sorted(children(root, span["span_id"]), key=lambda s: s["start_ns"])
        seconds = span["duration_s"]
        self_s = "summed" if span["summed"] else f"{seconds - union_s(kids):9.4f}"
        attrs = ", ".join(f"{k}={v}" for k, v in span["attrs"].items())
        lines.append(f"{'  ' * depth + span['name']:<28} {seconds:9.4f} {self_s:>9}  {attrs}")
        for k in kids:
            walk(k, depth + 1)

    top = by_id.get(root["span_id"])
    if top is not None:
        walk(top, 0)
    return lines


def paired_roots(record: dict, arg: dict):
    """-> ([(root, op)]) or None."""
    ops = [o for o in record["ops"] if o["kind"] == arg["kind"]]
    roots = finished_roots(arg["root"])[-len(ops):] if ops else []
    if not ops or len(roots) != len(ops):
        return None
    if any(r["duration_s"] > o["wall_s"] for r, o in zip(roots, ops)):
        return None
    for r in roots:
        if r["trace_id"] not in _logged:
            _logged.add(r["trace_id"])
            print(f"bench: spans: {arg['root']} {r['trace_id']}\n" + "\n".join(
                "bench: spans:   " + line for line in table(r)), file=sys.stderr, flush=True)
    return list(zip(roots, ops))


def read(record: dict, arg: dict):
    pairs = paired_roots(record, arg)
    if pairs is None:
        return None
    if "spans" in arg:
        found = [[s for s in r["spans"] if s["name"] in arg["spans"] and not s["summed"]]
                 for r, _o in pairs]
        if not any(found):
            return None
        return sum(union_s(spans) for spans in found) / len(pairs)
    if arg.get("unattributed_share"):
        wall = sum(o["wall_s"] for _r, o in pairs)
        named = sum(union_s(children(r, r["span_id"])) for r, _o in pairs)
        return 100.0 * (wall - named) / wall
    vals = [r["attrs"][arg["counter"]] for r, _o in pairs if arg["counter"] in r["attrs"]]
    return sum(vals) / len(pairs) if vals else None
