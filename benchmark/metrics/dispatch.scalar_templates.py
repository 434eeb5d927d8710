"""Templates that ran on the scalar oracle instead of the device, per
completed audit cycle: the ``templates_scalar`` arg of the window's
``audit.sweep`` spans (the sizes of each sweep's dispatch stanza).  None
where the program's sweep span carries no such arg."""


def read(rec):
    if rec.spans is None or rec.cycles <= 0:
        return None
    n = [e["args"]["templates_scalar"] for e in rec.spans
         if e["name"] == "audit.sweep" and "templates_scalar" in e["args"]]
    if not n:
        return None
    return float(sum(n)) / rec.cycles
