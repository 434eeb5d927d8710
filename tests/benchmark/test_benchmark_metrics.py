"""The arithmetic of the end-to-end metrics and of each per-layer
reader, on made-up records."""

import pytest
from harness import cell as cellmod
from harness import checks, stats
from harness.devtrace import Reduced
from harness.record import Record


def test_window_over_cycles():
    assert stats.per_cycle(10.5, 7) == 1.5
    with pytest.raises(ValueError):
        stats.per_cycle(10.0, 0)


@pytest.mark.parametrize("numbers,want", [
    ({"audit_missing": 0, "audit_extra": 0, "cycles_compared": 3}, True),
    ({"audit_missing": 1, "audit_extra": 0, "cycles_compared": 3}, False),
    ({"audit_missing": 0, "audit_extra": 2, "cycles_compared": 3}, False),
    ({"audit_missing": 0, "audit_extra": 0, "cycles_compared": 0}, False),
])
def test_correct_holds_every_number_to_its_limit(numbers, want):
    limits = {"audit_missing": 0, "audit_extra": 0}
    assert checks.correct(numbers, limits) is want
    table = checks.table(numbers, limits)
    assert table["cycles_compared"]["limit"] is None
    assert table["audit_missing"] == {"value": numbers["audit_missing"],
                                      "limit": 0}


def _record():
    r = Record()
    r.cycles = 4
    r.trace = Reduced(10.0, 0.5, {"fusion": 0.3, "sort": 0.5}, [])
    r.spans = [{"name": "kind.host_prep", "dur": 1000.0},
               {"name": "kind.host_prep", "dur": 3000.0},
               {"name": "kind.format", "dur": 8000.0},
               {"name": "audit.sweep", "dur": 1e6}]
    r.upsert_s = [0.1, 0.3]
    r.compiles, r.upgrades = 1, 2
    return r


@pytest.mark.parametrize("metric,want", [
    ("device.idle_share.audit", 95.0),
    ("kernels.sweep_device_ms", 200.0),      # 0.8 s of ops / 4 cycles
    ("prep.host_prep_ms", 1.0),              # 4 ms of spans / 4 cycles
    ("format.format_ms", 2.0),
    ("store.upsert_ms", 200.0),
    ("executor.compiles_in_window", 3.0),
])
def test_reader(metric, want):
    read = cellmod.load("large-cluster.churn_audit").reader(metric)
    assert read(_record()) == pytest.approx(want)


@pytest.mark.parametrize("metric", [
    "device.idle_share.audit", "kernels.sweep_device_ms",
    "prep.host_prep_ms", "format.format_ms", "store.upsert_ms"])
def test_reader_with_nothing_to_read_returns_nothing(metric):
    read = cellmod.load("large-cluster.churn_audit").reader(metric)
    assert read(Record()) is None
