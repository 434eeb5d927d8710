"""``correct`` has to come out false for the reference's control and for
the faults a cell can have, planted in the timed path underneath a run
(the look for a chip skipped): a step that returns its state unchanged,
half of the batch left out, and an answer altered where it is produced.
The exchange between chips is not among them: every cell runs on one
chip."""

import dataclasses

import pytest
from bench_helpers import setup_cell
from harness import checks

from gatekeeper_tpu.client.client import Client


class _Results:
    def __init__(self, results):
        self._r = results

    def results(self):
        return self._r


def _stale(orig):
    """Every audit returns the first one's results: state unchanged."""
    first = []

    def audit(self, *a, **k):
        res = orig(self, *a, **k).results()
        if not first:
            first.append(res)
        return _Results(first[0])
    return audit


def _half(orig):
    """Half of each audit's results left out."""
    def audit(self, *a, **k):
        return _Results(orig(self, *a, **k).results()[::2])
    return audit


def _altered(orig):
    """One message of each audit altered where it is produced."""
    def audit(self, *a, **k):
        res = list(orig(self, *a, **k).results())
        res[0] = dataclasses.replace(res[0], msg=res[0].msg + " ")
        return _Results(res)
    return audit


def _correct(cell, t, monkeypatch=None, fault=None, control=False):
    if fault is not None:
        monkeypatch.setattr(Client, "audit", fault(Client.audit))
    t.window(1.0)
    if fault is not None:
        monkeypatch.undo()
    numbers = t.check(cell.reference, control=control)
    return checks.correct(numbers, cell.reference.LIMITS)


@pytest.fixture(scope="module")
def churn_cell():
    # a tenth churned per cycle: 1% of a few hundred Pods would rarely
    # reach the capped audit, so a stale state would pass unseen
    cell, t = setup_cell("large-cluster.churn_audit", 300, churn=0.1)
    yield cell, t
    t.close()


def test_churn_cell_sound_run_is_correct(churn_cell):
    assert _correct(*churn_cell)


def test_churn_cell_control_is_not_correct(churn_cell):
    assert not _correct(*churn_cell, control=True)


@pytest.mark.parametrize("fault", [_stale, _half, _altered])
def test_churn_cell_fault_is_not_correct(churn_cell, monkeypatch, fault):
    assert not _correct(*churn_cell, monkeypatch, fault)


@pytest.mark.parametrize("fault", [None, "control", _half, _altered])
def test_full_sweep_cell(monkeypatch, fault):
    """A full sweep's inventory never changes, so a step that returns
    its state unchanged is no fault there."""
    cell, t = setup_cell("large-cluster.full_sweep", 300)
    try:
        ok = _correct(cell, t, monkeypatch,
                      fault if callable(fault) else None,
                      control=fault == "control")
        assert ok is (fault is None)
    finally:
        t.close()
