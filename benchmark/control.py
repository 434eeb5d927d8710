"""Read a cell's comparison for the program and for its control.

    python3 benchmark/control.py --workload <cell> --seeds 1 2 3 \\
        --seconds <s>

For each seed, in one process: the cell's set-up and a window of
``--seconds`` as ``run.py`` makes them, then the numbers ``correct`` is
decided on, once for what the program produced and once with the
reference's control (the reference computed one precision below the one
the configuration states) put in the program's place.  The control has
to come out not correct.  One JSON line per seed.  The benchmark's own
runs never run the control; this is how its readings, and the limits'
lower readings, are taken on the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        BENCH_DIR, ".cache", "xla")
    sys.path.insert(0, BENCH_DIR)
    sys.path.insert(0, os.path.dirname(BENCH_DIR))
    import run
    run.pin_hash_seed()
    from harness import cell as cellmod
    from harness import checks

    cell = cellmod.load(args.workload)
    run.check_device(cell.chips, cellmod.peaks)
    ref, limits = cell.reference, cell.reference.LIMITS
    for seed in args.seeds:
        t = cell.driver(seed, run.say)
        try:
            t.setup(args.seconds)
            rec = t.window(args.seconds)
        finally:
            t.close()
        program = t.check(ref)
        control = t.check(ref, control=True)
        print(json.dumps({
            "workload": cell.name, "seed": seed, "attempted": rec.attempted,
            "failed": rec.failed, "metrics": t.metrics, "program": program,
            "program_correct": checks.correct(program, limits),
            "control": control,
            "control_correct": checks.correct(control, limits),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
