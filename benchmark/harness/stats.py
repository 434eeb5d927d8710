"""Arithmetic of the end-to-end metrics."""

from __future__ import annotations


def per_cycle(window_s: float, cycles: int) -> float:
    """Seconds per completed cycle over the whole window."""
    if cycles <= 0:
        raise ValueError("no cycle completed in the window")
    return window_s / cycles
