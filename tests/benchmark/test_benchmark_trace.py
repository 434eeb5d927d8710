"""The reduction from a profiler trace to busy time, idle share, per-op
device time and named idle gaps."""

import os

import pytest
from bench_helpers import DATA, cpu_op_lines
from harness import devtrace


@pytest.mark.parametrize("intervals,length", [
    ([], 0.0),
    ([(0, 2)], 2.0),
    ([(0, 2), (1, 3)], 3.0),                 # overlap counts once
    ([(5, 6), (0, 1), (0.5, 0.75)], 2.0),    # order does not matter
    ([(0, 1), (1, 2)], 2.0),                 # touching
])
def test_union_length(intervals, length):
    assert devtrace.union_length(intervals) == length


@pytest.mark.parametrize("intervals,lo,hi,want", [
    ([], 0, 10, [(0, 10)]),
    ([(2, 3), (5, 7)], 0, 10, [(0, 2), (3, 5), (7, 10)]),
    ([(-1, 4), (3, 12)], 0, 10, []),
    ([(2, 6), (3, 4)], 0, 10, [(0, 2), (6, 10)]),
])
def test_gaps(intervals, lo, hi, want):
    assert devtrace.gaps(intervals, lo, hi) == want


def test_self_times_count_nested_ops_once():
    events = [(0, 10, "while"), (1, 3, "a"), (4, 8, "b"), (5, 6, "c"),
              (12, 13, "d")]
    own = devtrace.self_times(events)
    assert own == [4, 2, 3, 1, 1]
    assert sum(own) == devtrace.union_length([e[:2] for e in events])


@pytest.mark.parametrize("name,module,want", [
    ("%while.1 = (s32[], pred[8]) while(...)", "jit_raw(42)",
     "jit_raw/while.1"),
    ("%fusion.3 = f32[4] fusion(f32[4] %p)", None, "fusion.3"),
])
def test_op_name(name, module, want):
    assert devtrace.op_name(name, module) == want


def test_reduce_recorded_cpu_trace():
    """A window recorded with jax.profiler on the CPU: three jitted steps
    under bench.audit, each followed by a 30 ms sleep under
    bench.churn_apply (tests/benchmark/data/cpu_window.xplane.pb)."""
    r = devtrace.reduce_trace(os.path.join(DATA, "cpu_window.xplane.pb"),
                              cpu_op_lines)
    assert 0.1 < r.window_s < 1.0
    assert 0 < r.busy_s < r.window_s
    assert r.idle_share == pytest.approx(1 - r.busy_s / r.window_s)
    assert r.top_ops(1)[0][0] == "sort.0"
    # one device line here, no overlap: own times add up to busy time
    assert sum(v for _, v in r.top_ops(100)) == pytest.approx(r.busy_s)
    longest = r.top_gaps(3)
    assert [name for name, _ in longest] == ["bench.churn_apply"] * 3
    assert all(0.029 < s < 0.06 for _, s in longest)
    assert len(r.top_gaps()) <= 10 and len(r.top_ops()) <= 10


def test_reduce_refuses_a_trace_without_device_ops():
    with pytest.raises(ValueError, match="no device operations"):
        devtrace.reduce_trace(os.path.join(DATA, "cpu_window.xplane.pb"))
