"""Device time per completed audit cycle: the own time of the device's
operations in the traced window (an op nested in another, as a while
loop's body, counted once), over the cycles completed."""


def read(rec):
    if rec.trace is None or rec.cycles <= 0 or not rec.trace.op_seconds:
        return None
    return 1000.0 * sum(rec.trace.op_seconds.values()) / rec.cycles
