"""Each cell's mix driven end to end on the CPU at a tiny size, with the
look for a chip skipped: set-up, a short window, and the comparison
with the plain reference, which must find the program correct."""

import json

from bench_helpers import Args, CpuDevice, cpu_op_lines, shrink

import run


def _run(workload, n, trace=0):
    return run.run(Args(workload, trace=trace),
                   device_check=lambda chips, peaks: CpuDevice(),
                   adjust=lambda cell: shrink(cell, n),
                   device_line=cpu_op_lines)


def _assert_result(out, names):
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == names
    assert out["device"]["count"] == 1
    assert list(out)[-1] == "checks"
    assert all(c["limit"] is None or c["value"] <= c["limit"]
               for c in out["checks"].values())
    json.dumps(out)


def test_full_sweep():
    out = _run("large-cluster.full_sweep", 400)
    _assert_result(out, {"audit_sweep_s", "setup_s"})
    assert out["checks"]["cycles_compared"]["value"] == out["attempted"]


def test_churn_audit_traced():
    out = _run("large-cluster.churn_audit", 400, trace=1)
    _assert_result(out, {
        "device.idle_share.audit", "kernels.sweep_device_ms",
        "prep.host_prep_ms", "format.format_ms", "store.upsert_ms",
        "executor.compiles_in_window"})
    assert out["metrics"]["executor.compiles_in_window"]["value"] == 0
    dev = out["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert len(out["breakdown"]["device_ops"]) <= 10
    assert len(out["breakdown"]["idle_gaps"]) <= 10
