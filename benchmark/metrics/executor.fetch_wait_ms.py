"""Time the sweep thread waited for device results per completed audit
cycle: the program's ``kind.fetch`` spans (each kind's packed result
brought to the host) of the window's sweeps."""


def read(rec):
    if rec.spans is None or rec.cycles <= 0:
        return None
    us = [e["dur"] for e in rec.spans if e["name"] == "kind.fetch"]
    if not us:
        return None
    return sum(us) / 1000.0 / rec.cycles
