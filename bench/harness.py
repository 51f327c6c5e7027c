"""The benchmark's data-driven core: find a cell's files by name, run it,
and print the result line.

A cell in ``BENCHMARK.json`` names a configuration and a traffic mix.  The
harness reads ``bench/configs/<file>`` (the configuration entry's ``file``),
``bench/traffic/<traffic>.json`` and, for each per-layer metric the cell
reports, ``bench/metrics/<metric>.py``; the traffic's ``family`` names
``bench/families/<family>.py``, which holds that family's generator, run
and control.  Adding a configuration, a mix, a family or a metric is adding
files and entries, never editing one.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
from typing import Callable, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_of(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def load_config(bench: dict, name: str, root: str = ROOT) -> dict:
    for cfg in bench["configs"]:
        if cfg["name"] == name:
            with open(os.path.join(root, cfg["file"])) as f:
                return json.load(f)
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_traffic(name: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, "bench", "traffic", f"{name}.json")) as f:
        return json.load(f)


def load_peaks(device_kind: str, root: str = ROOT) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks recorded for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]


def _load_module(kind: str, name: str, root: str):
    path = os.path.join(root, "bench", kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses look their module up by name
    spec.loader.exec_module(mod)
    return mod


def load_metric(name: str, root: str = ROOT) -> Callable[[dict], Optional[float]]:
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    return _load_module("metrics", name, root).read


def load_family(name: str, root: str = ROOT):
    """``bench/families/<name>.py``: ``run(cfg, traffic, seed, seconds,
    trace_dir, log, t_start)`` returns a ``common.Outcome``; ``control(cfg,
    info, seed, **opts)`` the control's checks; ``shrink(cfg, traffic)``
    cuts the files to a CPU test's sizes."""
    return _load_module("families", name, root)


def metrics_of(bench: dict, workload: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` entries a cell reports."""
    return [m for m in bench[kind] if workload in m.get("workloads", [workload])]


def run_cell(bench: dict, workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, root: str = ROOT, config: Optional[dict] = None,
             traffic: Optional[dict] = None, trace_dir: Optional[str] = None,
             log=None) -> dict:
    """Run one cell in this process and return the result object;
    ``t_start`` is when the run began (``setup_s`` counts from it).

    ``config`` / ``traffic`` replace the cell's files (the tests run cells
    at tiny sizes this way); the trace goes to ``trace_dir``, by default
    ``.bench_trace`` in the checkout."""
    log = log or (lambda msg: print(msg, flush=True))
    cell = cell_of(bench, workload)
    config = config if config is not None else load_config(bench, cell["config"], root)
    traffic = traffic if traffic is not None else load_traffic(cell["traffic"], root)
    trace_dir = (trace_dir or os.path.join(root, ".bench_trace")) if trace else None
    out = load_family(traffic["family"], root).run(config, traffic, seed, seconds, trace_dir,
                                                   log, t_start)

    import jax

    kind = jax.devices()[0].device_kind
    summary = None
    if trace:
        from . import trace as tr

        summary = tr.reduce(out.planes) if out.planes is not None else None
        ctx = dict(out.ctx, trace=summary, peaks=load_peaks(kind, root) if
                   jax.devices()[0].platform == "tpu" else None)
        metrics = {}
        for m in metrics_of(bench, workload, "per_layer"):
            value = load_metric(m["name"], root)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": out.e2e[m["name"]], "unit": m["unit"]}
                   for m in metrics_of(bench, workload, "end_to_end")}
    result = {
        "correct": all(c.ok for c in out.checks),
        "attempted": out.attempted,
        "failed": out.failed,
        "metrics": metrics,
        "device": dict(out.device, **({"busy_s": summary.busy_s, "window_s": summary.window_s}
                                      if summary is not None else {})),
    }
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in out.checks}
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell on this machine's chips.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_benchmark()
    cell = cell_of(bench, args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: needs {cell['chips']} TPU chip(s), JAX found {len(devices)} "
              f"{devices[0].platform!r} device(s)", file=sys.stderr)
        return 2
    load_peaks(devices[0].device_kind)  # an unknown chip fails before any work
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    result = run_cell(bench, args.workload, args.seed, args.seconds, bool(args.trace), t_start)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0
