#!/usr/bin/env python3
"""The control of the comparison that decides a run's ``correct``.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10

For each seed, in one process: the cell's set-up and a window of
``--seconds`` through the program, then the numbers ``correct`` compares,
for the program's sampled answers and for the control on the same answers.
The control is the plain reference put in the program's place in the next
precision below the configuration's (bfloat16 for the program's float32):
each sampled device sits where bfloat16 pricing puts it first, and a
finalized answer's (f, beta) and eq.-17 cost are the reference's own
optimum of problem (18) in bfloat16. Every number is read in float64. One JSON line per seed on
standard output. The benchmark's own runs do not run this.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from benchlib import reference as ref  # noqa: E402
from benchlib import spec as spec_mod  # noqa: E402
from benchlib.window import run_window  # noqa: E402


def control_runs(cell, seeds, seconds: float):
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    for seed in seeds:
        loop = spec_mod.loop_class(cell.traffic["loop"])(cell, seed)
        loop.setup()
        win = run_window(loop, seconds)
        loop.release()
        nums, ctrl = loop.check(control=ref.BF16)
        yield {"seed": seed, "requests": len(win["records"]),
               "program": nums, "control": ctrl,
               "limits": cell.limits["limits"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec_mod.load_cell(args.workload)
    from benchlib.device import require_chips

    require_chips(cell.chips)
    seeds = [int(s) for s in args.seeds.split(",")]
    for line in control_runs(cell, seeds, args.seconds):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
