"""Share of the traced window in which no operation ran on the device,
in audit cells (device trace, ``harness/devtrace.py``)."""


def read(rec):
    if rec.trace is None:
        return None
    return 100.0 * rec.trace.idle_share
