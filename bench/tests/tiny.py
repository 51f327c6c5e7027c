"""Each cell's own files, cut to sizes a CPU test run can hold by its
family's ``shrink``."""

import time

from bench import harness


def cell_files(workload: str, root: str = harness.ROOT):
    bench = harness.load_benchmark(root)
    cell = harness.cell_of(bench, workload)
    return bench, harness.load_config(bench, cell["config"], root), \
        harness.load_traffic(cell["traffic"], root)


def shrink(cfg: dict, traffic: dict, root: str = harness.ROOT) -> None:
    harness.load_family(traffic["family"], root).shrink(cfg, traffic)


def run(workload: str, seed: int = 2147483700, seconds: float = 1.0, trace: bool = False,
        trace_dir=None, root: str = harness.ROOT):
    bench, cfg, traffic = cell_files(workload, root)
    shrink(cfg, traffic, root)
    return harness.run_cell(bench, workload, seed, seconds, trace, time.perf_counter(),
                            root=root, config=cfg, traffic=traffic, trace_dir=trace_dir,
                            log=lambda msg: None)
