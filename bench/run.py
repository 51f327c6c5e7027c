"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the repository root.  Exits nonzero, printing no result, where JAX
finds no TPU or fewer chips than the cell asks for.  The last line of
standard output is the result object; the numbers compared with the plain
references are the last lines of standard error.
"""

import os
import sys
import time

if __name__ == "__main__":
    T_START = time.perf_counter()
    # the TPU runtime would otherwise log under a fixed path in /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from bench.harness import main

    sys.exit(main(t_start=T_START))
