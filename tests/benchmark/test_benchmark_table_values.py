"""The reader of the ``table_values_host`` args on the program's
``kind.host_prep`` spans (``prep.table_host_values``)."""

import pytest
from bench_helpers import BENCH_DIR
from harness import cell as cellmod
from harness.record import Record


def _read(rec):
    return cellmod.load_module(BENCH_DIR, "metrics",
                               "prep.table_host_values").read(rec)


def _prep(host, batched=0):
    return {"name": "kind.host_prep", "dur": 10.0,
            "args": {"table_values_host": host,
                     "table_values_batched": batched}}


def test_sums_the_host_evaluations_per_cycle():
    r = Record()
    r.cycles = 2
    r.spans = [_prep(300, 9000), _prep(0, 4000), _prep(100),
               {"name": "kind.format", "dur": 5.0, "args": {"rows": 7}}]
    assert _read(r) == pytest.approx(200.0)


@pytest.mark.parametrize("spans", [
    None,
    [],
    # a program whose host-prep spans carry no table counts
    [{"name": "kind.host_prep", "dur": 10.0, "args": {"kind": "K"}}],
], ids=["no_spans", "empty", "no_args"])
def test_nothing_to_read_returns_nothing(spans):
    r = Record()
    r.cycles = 3
    r.spans = spans
    assert _read(r) is None


def test_the_full_sweep_cell_lists_it():
    names = {m["name"] for m in
             cellmod.load("large-cluster.full_sweep").per_layer}
    assert "prep.table_host_values" in names
