"""Executables compiled inside the window: the executor's ``compiles``
and background ``upgrades`` counters, window end minus window start.
It should read 0."""


def read(rec):
    return float(rec.compiles + rec.upgrades)
