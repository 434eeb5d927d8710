"""Host format per completed audit cycle: the program's ``kind.format``
spans (top-k results re-evaluated and formatted on the host) of the
window's sweeps."""


def read(rec):
    if rec.spans is None or rec.cycles <= 0:
        return None
    us = sum(e["dur"] for e in rec.spans if e["name"] == "kind.format")
    return us / 1000.0 / rec.cycles
