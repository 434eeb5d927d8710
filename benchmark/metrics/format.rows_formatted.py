"""Rows brought to the host for re-evaluation and formatting per
completed audit cycle: the ``rows`` args of the program's
``kind.format`` spans of the window's sweeps."""


def read(rec):
    if rec.spans is None or rec.cycles <= 0:
        return None
    n = [e["args"]["rows"] for e in rec.spans
         if e["name"] == "kind.format" and "rows" in e.get("args", {})]
    if not n:
        return None
    return float(sum(n)) / rec.cycles
