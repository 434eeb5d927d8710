"""What no layer span names, per completed audit cycle: the own time of
each ``audit.sweep`` span on its thread, its length less the union of
its direct children on the same thread."""

from harness.devtrace import union_length


def read(rec):
    if rec.spans is None or rec.cycles <= 0:
        return None
    roots = {e["args"]["span_id"]: e for e in rec.spans
             if e["name"] == "audit.sweep"}
    if not roots:
        return None
    covered = {sid: [] for sid in roots}
    for e in rec.spans:
        sid = e["args"].get("parent_span_id")
        root = roots.get(sid)
        if root is not None and e["tid"] == root["tid"]:
            a = max(e["ts"], root["ts"])
            b = min(e["ts"] + e["dur"], root["ts"] + root["dur"])
            if b > a:
                covered[sid].append((a, b))
    us = sum(r["dur"] - union_length(covered[sid])
             for sid, r in roots.items())
    return us / 1000.0 / rec.cycles
