"""The benchmark's one command: run one cell once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the contract's result object and nothing
follows it (``harness/guard.py``); everything else the run prints goes to
``chiprun_out/benchmarks/<cell>.seed<n>.trace<t>/run.log``. Exits non-zero
and prints no result when JAX finds no accelerator, or fewer chips than the
cell asks for.
"""

import time

T_PROCESS_START = time.monotonic()  # before every other import: set-up counts

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import runner  # noqa: E402

if __name__ == "__main__":
    runner.main(sys.argv[1:], t_start=T_PROCESS_START)
