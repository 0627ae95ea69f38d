"""One run of one benchmark cell on the chip(s) of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Exits non-zero, printing no result,
when JAX finds no TPU, a TPU kind missing from ``chipbench/peaks.json``,
or fewer chips than the cell asks for.  See ``chipbench/harness.py``.
"""

import time

T_START = time.perf_counter()   # set-up is timed from here

import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t_start=T_START))
