"""How ``correct`` is decided: each number compared, with its limit.

The configuration's plain reference (``benchmark/reference/<name>.py``)
declares the numbers that decide ``correct`` and their limits in
``LIMITS``.  Numbers that only say how much was compared end in
``_compared`` and have no limit; at least one of them has to be above 0.
"""

from __future__ import annotations


def correct(numbers: dict, limits: dict) -> bool:
    """Every number within its limit, and something was compared."""
    if not any(v > 0 for k, v in numbers.items() if k.endswith("_compared")):
        return False
    return all(v <= limits[k] for k, v in numbers.items() if k in limits)


def table(numbers: dict, limits: dict) -> dict:
    return {k: {"value": v, "limit": limits.get(k)}
            for k, v in numbers.items()}
