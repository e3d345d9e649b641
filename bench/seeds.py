"""Run one cell on many seeds in one process, optionally with the timed
call replaced by the control or broken by a planted fault
(``bench/faults.py``), and print one JSON line per seed.

    python bench/seeds.py --workload <cell> --seconds <s> --seeds 1 2 3 [--fault control]

Each seed is a whole run as ``bench/run.py`` makes it (data from the seed,
warm-up, window, comparison with the reference); only the first seed
pays for compiles. This is how the limits of ``correct`` are read: the
program's readings over a dozen seeds, and the control's and each fault's
on three or more. The benchmark's own runs never use it.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import faults, harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    try:
        devices = harness.guard_devices(cell.chips)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    wrap = faults.FAULTS[args.fault] if args.fault else None
    t = T_PROCESS
    for seed in args.seeds:
        res = harness.execute(cell, seed, args.seconds, False, t, devices, wrap)
        print(json.dumps({"seed": seed, "fault": args.fault, "correct": res["correct"],
                          "attempted": res["attempted"], "checks": res["checks"],
                          "metrics": res["metrics"],
                          "memory_peak_bytes": res["device"]["memory_peak_bytes"]}),
              flush=True)
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
