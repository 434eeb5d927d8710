"""Bytes uploaded to the device per completed audit cycle, in MB: the
``h2d_bytes`` args of the program's ``kind.h2d`` (full sweep) and
``device.dispatch`` (memoized sweep) spans of the window's sweeps."""


def read(rec):
    if rec.spans is None or rec.cycles <= 0:
        return None
    n = [e["args"]["h2d_bytes"] for e in rec.spans
         if "h2d_bytes" in e.get("args", {})]
    if not n:
        return None
    return sum(n) / 1e6 / rec.cycles
