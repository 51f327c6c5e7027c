"""Benchmark driver: one section per paper table/figure + kernels + roofline,
plus ``--suite`` to run every gated JSON bench and merge the results.

Prints ``name,us_per_call,derived`` CSV rows (per the repo convention):
``name`` identifies the figure/bench and parameters, ``us_per_call`` is the
primary timing where meaningful (0 for ratio-style results), ``derived``
packs the figure's headline quantity.

``--suite`` runs the standalone gated benches (fingerprint index, CDC,
replay throughput, cluster scaling, resharding, GC, serving latency,
replication) as
subprocesses — each still writes its own ``BENCH_*.json`` — and merges
every payload plus each bench's gate verdict into one
``BENCH_summary.json``, so the perf trajectory across PRs is one file
instead of eight.  Exit code 1 if any bench's gate failed.  The ``--suite``
parent never imports JAX: a chip belongs to one process, so only the
children may take it.

Usage:
    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig6,...]
    PYTHONPATH=src python -m benchmarks.run --suite [--smoke]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

# (suite name, script, emitted JSON) — run order is cheap-first
SUITE = [
    ("fp_index", "benchmarks/fp_index.py", "BENCH_fp_index.json"),
    ("cdc", "benchmarks/cdc.py", "BENCH_cdc.json"),
    ("replay", "benchmarks/replay_throughput.py", "BENCH_replay.json"),
    ("cluster", "benchmarks/cluster_scaling.py", "BENCH_cluster.json"),
    ("resharding", "benchmarks/resharding.py", "BENCH_resharding.json"),
    ("gc", "benchmarks/gc_reclaim.py", "BENCH_gc.json"),
    ("serving", "benchmarks/serving_latency.py", "BENCH_serving.json"),
    ("replication", "benchmarks/replication.py", "BENCH_replication.json"),
]


def run_suite(smoke: bool, out: str = "BENCH_summary.json") -> int:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(root, "src"), env.get("PYTHONPATH")) if p
    )
    summary = {"meta": {"smoke": smoke}, "suites": {}}
    failed = []
    for name, script, emitted in SUITE:
        cmd = [sys.executable, os.path.join(root, script)]
        if smoke:
            cmd.append("--smoke")
        print(f"== {name}: {' '.join(cmd[1:])}", flush=True)
        rc = subprocess.call(cmd, cwd=root, env=env)
        entry = {"script": script, "exit_code": rc, "gate_pass": rc == 0}
        path = os.path.join(root, emitted)
        if os.path.exists(path):
            with open(path) as f:
                entry["payload"] = json.load(f)
        if rc != 0:
            failed.append(name)
        summary["suites"][name] = entry
    summary["meta"]["all_gates_pass"] = not failed
    with open(os.path.join(root, out), "w") as f:
        json.dump(summary, f, indent=2)
    print(f"wrote {out}; gates: "
          + ", ".join(f"{n}={'ok' if n not in failed else 'FAIL'}" for n, _, _ in SUITE))
    return 1 if failed else 0


def _emit(rows, primary=None):
    for row in rows:
        name_bits = []
        derived_bits = []
        us = 0.0
        for k, v in row.items():
            if k in ("figure", "bench"):
                name_bits.insert(0, str(v))
            elif isinstance(v, str) or k in ("workload", "cache", "template", "threshold",
                                             "interval", "interval_factor", "stream", "blocks",
                                             "words", "counts", "arch", "shape", "mesh"):
                name_bits.append(f"{k}={v}")
            else:
                if "us" in k or "ms" in k:
                    if primary and k == primary:
                        us = float(v) * (1e3 if "ms" in k else 1.0)
                derived_bits.append(f"{k}={v}")
        print(f"{'/'.join(name_bits)},{us},{';'.join(derived_bits)}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale workloads (slower)")
    ap.add_argument("--only", default="", help="comma list: fig4,fig5,fig6,fig7,fig9,fig10,fig11,table4,kernels,roofline")
    ap.add_argument("--suite", action="store_true",
                    help="run the gated JSON benches and merge into BENCH_summary.json")
    ap.add_argument("--smoke", action="store_true", help="(--suite) CI-sized runs")
    args = ap.parse_args()
    if args.suite:
        raise SystemExit(run_suite(args.smoke))
    from repro.compile_cache import enable_compile_cache

    from . import kernel_bench, paper_validation, roofline

    enable_compile_cache()
    n = 600_000 if args.full else 250_000
    only = set(args.only.split(",")) if args.only else None

    def want(tag):
        return only is None or tag in only

    print("name,us_per_call,derived")
    t0 = time.time()
    if want("fig6"):
        _emit(paper_validation.bench_cache_efficiency(n))
    if want("fig7"):
        _emit(paper_validation.bench_capacity(n))
    if want("table4"):
        _emit(paper_validation.bench_avg_hits(n))
    if want("fig4"):
        _emit(paper_validation.bench_estimation_quality(max(n // 2, 100_000)))
    if want("fig9"):
        _emit(paper_validation.bench_ldss_accuracy(max(n // 2, 100_000)))
    if want("fig5") or want("fig10"):
        _emit(paper_validation.bench_threshold(max(n // 2, 100_000)))
    if want("fig11"):
        _emit(paper_validation.bench_overhead())
    if want("kernels"):
        _emit(kernel_bench.bench_fingerprint(), primary="us_per_call_interpret")
        _emit(kernel_bench.bench_ffh(), primary="us_per_call_interpret")
        _emit(kernel_bench.bench_paged_attention(), primary="us_per_call_interpret")
        _emit(kernel_bench.bench_ingest_dataplane())
    if want("roofline"):
        _emit(roofline.rows_for_run())
    print(f"# total bench time: {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
