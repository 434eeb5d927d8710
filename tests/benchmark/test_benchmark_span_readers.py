"""The readers of the program's sweep spans (``audit.order``,
``kind.fetch``, ``h2d_bytes``, ``kind.format`` rows, the sweep's own
time) on made-up spans, and a traced churn window on the CPU whose idle
gaps carry the program's span names."""

import os

import pytest
from bench_helpers import BENCH_DIR, cpu_op_lines, setup_cell
from harness import cell as cellmod
from harness.devtrace import Tracing
from harness.record import Record

READERS = ("prep.order_ms", "executor.fetch_wait_ms", "executor.h2d_mb",
           "format.rows_formatted", "audit.unspanned_ms")
SWEEP_SPANS = ("audit.order", "kind.host_prep", "kind.h2d", "kind.block",
               "device.dispatch", "executor.wait", "kind.fetch",
               "kind.format", "external.prefetch", "dedup.host_eval")


def _reader(name):
    return cellmod.load_module(BENCH_DIR, "metrics", name).read


def _span(name, ts, dur, sid, parent=None, tid=1, **args):
    args["span_id"] = sid
    if parent is not None:
        args["parent_span_id"] = parent
    return {"name": name, "ts": ts, "dur": dur, "tid": tid, "args": args}


def _record():
    """Two sweeps of 1,000 us on thread 1; children on thread 1 cover
    [0, 300) and [200, 500) of the first and [1000, 1100) of the
    second; work on thread 2 does not cover the sweep's own time."""
    r = Record()
    r.cycles = 2
    r.spans = [
        _span("audit.sweep", 0, 1000, 1, full=False),
        _span("audit.order", 0, 300, 2, 1, resorted=True, rows=10),
        _span("kind.format", 200, 300, 3, 1, kind="A", rows=40),
        _span("kind.fetch", 210, 50, 4, 1, kind="A"),
        _span("device.dispatch", 100, 800, 5, 1, tid=2, kind="A",
              h2d_bytes=3_000_000),
        _span("audit.sweep", 1000, 1000, 6, full=True),
        _span("audit.order", 1000, 100, 7, 6, resorted=False, rows=10),
        _span("kind.h2d", 1200, 100, 8, 6, tid=2, kind="A",
              h2d_bytes=1_000_000),
        _span("kind.format", 1500, 0, 9, 6, tid=3, kind="A", rows=20),
        _span("kind.fetch", 1500, 150, 10, 6, tid=3, kind="A"),
    ]
    return r


@pytest.mark.parametrize("metric,want", [
    ("prep.order_ms", 0.2),              # 400 us of order / 2 cycles
    ("executor.fetch_wait_ms", 0.1),     # 200 us of fetch / 2 cycles
    ("executor.h2d_mb", 2.0),            # 4 MB / 2 cycles
    ("format.rows_formatted", 30.0),     # 60 rows / 2 cycles
    # sweep 1: 1000 - 500 covered; sweep 2: 1000 - 100 covered
    ("audit.unspanned_ms", 0.7),
])
def test_span_reader(metric, want):
    assert _reader(metric)(_record()) == pytest.approx(want)


@pytest.mark.parametrize("metric", READERS)
def test_span_reader_with_nothing_to_read_returns_nothing(metric):
    assert _reader(metric)(Record()) is None


@pytest.mark.parametrize("metric", READERS[:4])
def test_span_reader_without_its_spans_returns_nothing(metric):
    """A program without these spans or args reads as nothing, not 0."""
    r = Record()
    r.cycles = 1
    r.spans = [_span("audit.sweep", 0, 100, 1),
               _span("kind.format", 0, 50, 2, 1, kind="A")]
    assert _reader(metric)(r) is None


def test_traced_churn_window_names_gaps_by_program_span(tmp_path):
    _cell, t = setup_cell("large-cluster.churn_audit", 400)
    try:
        tracing = Tracing(str(tmp_path / "trace"), cpu_op_lines)
        with tracing:
            rec = t.window(1.0)
        rec.trace = tracing.reduce()
    finally:
        t.close()
    named = [name for name, _ in rec.trace.top_gaps()]
    assert any(n.startswith("bench.") and n.split(" > ")[-1] in SWEEP_SPANS
               for n in named), named
    assert rec.spans is not None and rec.cycles > 0
    values = {m: _reader(m)(rec) for m in READERS}
    assert all(v is not None and v >= 0 for v in values.values()), values
    assert values["format.rows_formatted"] > 0
    assert not os.path.exists(tracing.log_dir)
