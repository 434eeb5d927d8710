"""Host format of the kinds evaluated on the scalar oracle per completed
audit cycle: the program's ``kind.format`` spans whose ``mode`` is
``scalar`` (every matched row evaluated on the host, then formatted) of
the window's sweeps; 0 where every kind ran on the device."""


def read(rec):
    if rec.spans is None or rec.cycles <= 0:
        return None
    us = sum(e["dur"] for e in rec.spans if e["name"] == "kind.format"
             and e["args"].get("mode") == "scalar")
    return us / 1000.0 / rec.cycles
