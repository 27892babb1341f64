#!/usr/bin/env python3
"""One run of one benchmark cell on the chip(s) this machine holds:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

See ``bench/benchlib/harness.py``. Exits non-zero, with no result, where
JAX finds no TPU or fewer chips than the cell asks for.
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from benchlib.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
