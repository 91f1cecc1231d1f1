"""What a span of the program's action trace says its seconds went to:
one attribute of the spans of one name (``readers/action_trace.py`` for
the trace itself and for which roots are the window's).

arg: {"root": <root span name>, "kind": <the operation's kind>,
      "span": <span name>, "attr": <attribute name>,
      "where": {<attribute>: <value>, ...} (optional)}
  -> mean per operation of that attribute, summed over the root's spans
     of that name whose attributes have those values. The root's own
     span is one of its spans (``"span"`` = the root's name) and its
     attributes are the root's counters; ``"attr": "duration_s"`` is a
     span's own seconds.
With ``"op": "warmup"`` the same of ONE root instead: the one that
precedes the window's N, which is the last build of set-up (its span
table goes to stderr too, once).

Nothing where the window's roots cannot be paired with its operations,
where no span of that name holds the attribute (a program that does not
record it), or — for the warm-up — where no root precedes the window's.
"""

import sys

from readers import action_trace


def of_root(root: dict, arg: dict):
    """The attribute summed over the root's matching spans, or None."""
    where = arg.get("where", {})
    found = []
    for span in root["spans"]:
        if span["name"] != arg["span"]:
            continue
        if any(span["attrs"].get(k) != v for k, v in where.items()):
            continue
        value = span["duration_s"] if arg["attr"] == "duration_s" else span["attrs"].get(arg["attr"])
        if value is not None:
            found.append(value)
    return sum(found) if found else None


def warmup_root(record: dict, arg: dict):
    """The root before the window's own, or None. Its ``bench: spans:``
    table goes to stderr once, marked as set-up's."""
    pairs = action_trace.paired_roots(record, arg)
    roots = action_trace.finished_roots(arg["root"])
    if pairs is None or len(roots) <= len(pairs):
        return None
    root = roots[-len(pairs) - 1]
    if root["trace_id"] not in action_trace._logged:
        action_trace._logged.add(root["trace_id"])
        print(f"bench: spans: {arg['root']} {root['trace_id']} (set-up's build)\n" + "\n".join(
            "bench: spans:   " + line for line in action_trace.table(root)), file=sys.stderr, flush=True)
    return root


def read(record: dict, arg: dict):
    if arg.get("op") == "warmup":
        root = warmup_root(record, arg)
        return None if root is None else of_root(root, arg)
    pairs = action_trace.paired_roots(record, arg)
    if pairs is None:
        return None
    found = [v for v in (of_root(r, arg) for r, _o in pairs) if v is not None]
    return sum(found) / len(pairs) if found else None
