"""Helpers for the benchmark's tests: the harness on the CPU, at tiny
sizes, with the look for a chip skipped."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)


class CpuDevice:
    """The CPU device in the chip's place; it reports no memory."""

    def __init__(self):
        import jax
        d = jax.devices()[0]
        self.platform, self.device_kind = d.platform, d.device_kind

    def memory_stats(self):
        return {"peak_bytes_in_use": 0}


def cpu_op_lines(plane: str, line: str) -> bool:
    """Where XLA's CPU client records its operations in a trace."""
    return plane.startswith("/host:") and \
        line.startswith("tf_XLAPjRtCpuClient")


def shrink(cell, n: int, churn: float | None = None) -> None:
    """A cell at a size a test can hold: ``n`` objects, and where given
    a share ``churn`` of them churned per cycle."""
    cell.config["inventory"]["n"] = n
    if "churn" in cell.mix:
        cell.mix["churn"]["batches"] = 60
        if churn is not None:
            cell.mix["churn"]["fraction"] = churn


class Args:
    def __init__(self, workload, seed=2**31 + 7, seconds=1.0, trace=0):
        self.workload, self.seed = workload, seed
        self.seconds, self.trace = seconds, trace


def setup_cell(workload: str, n: int, seed: int = 2**31 + 7,
               seconds: float = 1.0, root: str = ROOT,
               churn: float | None = None):
    """(cell, traffic) with set-up done, the ``say`` lines dropped."""
    from harness import cell as cellmod
    cell = cellmod.load(workload, root)
    shrink(cell, n, churn)
    t = cell.driver(seed, lambda msg: None)
    t.setup(seconds)
    return cell, t
