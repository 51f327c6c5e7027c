"""The controls: each cell's reference put in the program's place with one
guarantee its configuration states broken.  A control must come out as not
correct; the limits in ``bench/reference`` are set between the program's
readings and these.  Each family's ``control`` (``bench/families/<family>.py``)
says what it breaks: for ``block_writes``, membership keyed by the low 32 bits
of each fingerprint, read as ``dup_count_gap``.

On the chip, at the cell's size and a short window, several seeds in one
process (the program's own checks are printed beside the control's):

    python3 bench/control.py --workload vmA.deep --seconds 5 --seeds 11 12 13
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import List


def run(workload: str, seeds: List[int], seconds: float, config=None, traffic=None, root=None,
        log=print, **opts) -> List[dict]:
    """The program's checks and the control's, per seed; ``opts`` go to the
    family's ``control``."""
    from . import harness

    root = root or harness.ROOT
    bench = harness.load_benchmark(root)
    cell = harness.cell_of(bench, workload)
    cfg = config if config is not None else harness.load_config(bench, cell["config"], root)
    tr = traffic if traffic is not None else harness.load_traffic(cell["traffic"], root)
    family = harness.load_family(tr["family"], root)
    out = []
    for seed in seeds:
        o = family.run(cfg, tr, seed, seconds, None, lambda m: None, time.perf_counter())
        ctl = family.control(cfg, o.info, seed, **opts)
        row = {"seed": seed,
               "program": {c.name: c.value for c in o.checks},
               "program_correct": all(c.ok for c in o.checks),
               "control": {c.name: c.value for c in ctl},
               "control_correct": all(c.ok for c in ctl)}
        log(json.dumps(row))
        out.append(row)
    return out


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run a cell's control on several seeds.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU found", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), "src"))
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    rows = run(args.workload, args.seeds, args.seconds)
    return 0 if all(r["program_correct"] and not r["control_correct"] for r in rows) else 1


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from bench.control import main as _main

    sys.exit(_main())
