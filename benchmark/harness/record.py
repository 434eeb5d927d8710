"""What a window measured, for the end-to-end metrics and the per-layer
readers (``benchmark/metrics/<metric>.py``)."""

from __future__ import annotations


class Record:
    def __init__(self):
        self.window_s = 0.0
        self.cycles = 0
        self.attempted = 0
        self.failed = 0
        self.upsert_s: list[float] = []       # churn applied per cycle, s
        self.compiles = 0
        self.upgrades = 0
        self.spans: list[dict] | None = None  # program spans of the window
        self.trace = None                     # devtrace.Reduced
