"""Churn applied to the store per cycle: the benchmark's own clock around
``add_data_batch`` and ``remove_data`` (``store/table.py``,
``store/columns.py``, ``native/colext.c``)."""


def read(rec):
    if not rec.upsert_s:
        return None
    return 1000.0 * sum(rec.upsert_s) / len(rec.upsert_s)
