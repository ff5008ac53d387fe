#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

Prints the result as one JSON object on the last line of standard output;
exits non-zero without it off a TPU or short of the cell's chips.  See
``chipbench/harness.py``."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main())
