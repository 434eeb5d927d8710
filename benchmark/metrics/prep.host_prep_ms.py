"""Host prep per completed audit cycle: the program's ``kind.host_prep``
spans (``ir/prep.py`` bindings, ``engine/match.py`` masks) of the
window's sweeps."""


def read(rec):
    if rec.spans is None or rec.cycles <= 0:
        return None
    us = sum(e["dur"] for e in rec.spans if e["name"] == "kind.host_prep")
    return us / 1000.0 / rec.cycles
