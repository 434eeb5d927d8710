"""Run one benchmark cell on the chip this process finds.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Everything belongs to one process, which holds one chip: set-up (inventory
generation, policy install, ingest, warm-up of the cell's own shapes,
compiles), a measured window of ``--seconds``, then the comparison of
what the window produced with the plain reference.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window), ``device``, with ``--trace 1`` a ``breakdown``, and last the
``checks``: each number compared with its limit.  The same numbers are
the last lines of standard error.

It exits nonzero and prints no result on a platform that is not a TPU,
with fewer chips than the cell asks for, or on a device kind missing
from ``benchmark/peaks.json``.  JAX's persistent compilation cache lives
in ``benchmark/.cache/xla`` inside the checkout, so only a checkout's
first run of a cell compiles.
"""

import os
import sys
import time

# set-up is timed from the first start of the process, before the
# re-exec below
T_START = float(os.environ.setdefault("BENCHMARK_T_START",
                                      repr(time.monotonic())))


def pin_hash_seed() -> None:
    """Re-exec under ``PYTHONHASHSEED=0``.  The program lowers a policy
    into a program whose inputs follow a set's order, which follows the
    process's string hashing, so with a random hash seed a run can miss
    the persistent compile cache and compile again in set-up (20-40 s on
    one v5e chip, a third of the runs).  A fixed hash seed finds every
    program in the cache after a checkout's first run."""
    if os.environ.get("PYTHONHASHSEED") != "0":
        os.environ["PYTHONHASHSEED"] = "0"
        os.execv(sys.executable, [sys.executable] + sys.argv)


import argparse  # noqa: E402
import json  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, ".cache", "xla")


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class RunError(Exception):
    """The run cannot give a result."""


def check_device(chips: int, peaks_of):
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise RunError(f"platform is {dev.platform!r}, not tpu")
    if len(devs) < chips:
        raise RunError(f"{len(devs)} device(s), the cell asks for {chips}")
    peaks_of(dev.device_kind)
    return dev


def memory_peak(dev) -> int:
    st = dev.memory_stats() or {}
    if "peak_bytes_in_use" not in st:
        raise RunError("the device reports no peak_bytes_in_use")
    return int(st["peak_bytes_in_use"])


def run(args, device_check=check_device, adjust=None, device_line=None,
        root=ROOT) -> dict:
    """One run of a cell.  ``adjust(cell)``, ``device_line`` and ``root``
    are for tests on the CPU: a smaller configuration, which trace lines
    hold device operations there, and another checkout's BENCHMARK.json."""
    from harness import cell as cellmod
    from harness import checks
    from harness.devtrace import Tracing, tpu_op_lines

    cell = cellmod.load(args.workload, root)
    if adjust is not None:
        adjust(cell)
    dev = device_check(cell.chips, cellmod.peaks)
    say(f"device {dev.platform} {dev.device_kind}, cell {cell.name}, "
        f"seed {args.seed}, {args.seconds}s, trace {args.trace}")
    t = cell.driver(args.seed, say)
    try:
        t.setup(args.seconds)
        setup_s = time.monotonic() - T_START
        say(f"setup_s {setup_s:.3f}")
        if args.trace:
            tracing = Tracing(os.path.join(
                BENCH_DIR, ".cache", f"trace-{os.getpid()}"),
                device_line or tpu_op_lines)
            with tracing:
                rec = t.window(args.seconds)
            rec.trace = tracing.reduce()
        else:
            rec = t.window(args.seconds)
        peak = memory_peak(dev)
    finally:
        t.close()
    say(f"window {rec.window_s:.3f}s, attempted {rec.attempted}, failed "
        f"{rec.failed}, compiles {rec.compiles}, upgrades {rec.upgrades}")
    limits = cell.reference.LIMITS
    numbers = t.check(cell.reference)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": peak}
    out = {"correct": checks.correct(numbers, limits),
           "attempted": rec.attempted, "failed": rec.failed}
    if args.trace:
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        metrics = {}
        for m in cell.per_layer:
            v = cell.reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = {"device_ops": rec.trace.top_ops(),
                            "idle_gaps": rec.trace.top_gaps()}
    else:
        values = dict(t.metrics, setup_s=setup_s)
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
        out["device"] = device
    out["checks"] = checks.table(numbers, limits)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    pin_hash_seed()
    # a fixed path inside the checkout: the path is part of the cache key
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    sys.path.insert(0, BENCH_DIR)
    sys.path.insert(0, ROOT)
    from harness.cell import CellError
    try:
        out = run(args)
    except (RunError, CellError, ImportError) as e:
        say(f"FAILED: {e}")
        return 1
    for name, c in out["checks"].items():
        say(f"check {name} = {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
