"""Row order per completed audit cycle: the program's ``audit.order``
spans (the sort of the row keys and the rank array, redone when a
create or delete changed the keys) of the window's sweeps."""


def read(rec):
    if rec.spans is None or rec.cycles <= 0:
        return None
    us = [e["dur"] for e in rec.spans if e["name"] == "audit.order"]
    if not us:
        return None
    return sum(us) / 1000.0 / rec.cycles
