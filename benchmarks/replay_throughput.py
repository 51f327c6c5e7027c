"""Replay throughput: scalar per-record path vs columnar batched engine.

Replays the paper's default synthetic workloads (A/B/C, repro.core.traces)
through every Engine implementation twice — once via the per-record
reference path (``replay``) and once via the columnar batched path
(``replay_batched``) — and reports requests/sec plus the speedup.  Each
pair is also cross-checked: the two paths must produce identical
``HybridReport``s (the batched engine's core guarantee).

Emits ``BENCH_replay.json``:

    {"meta": {...}, "rows": [
        {"workload": "A", "engine": "hpdedup", "requests": ...,
         "scalar_rps": ..., "batched_rps": ..., "speedup": ...,
         "reports_equal": true}, ...]}

Usage:
    python benchmarks/replay_throughput.py            # default scale
    python benchmarks/replay_throughput.py --smoke    # CI-sized
    python benchmarks/replay_throughput.py --requests 500000 --reps 5
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Callable, Dict, List

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src"))

from repro.core import (
    DIODE,
    HPDedup,
    PurePostProcessing,
    generate_workload,
    make_idedup,
)
from repro.core.batch_replay import DEFAULT_BATCH_SIZE


def engine_factories(cache_entries: int, stream_of: Dict[int, str]) -> Dict[str, Callable]:
    return {
        "hpdedup": lambda: HPDedup(cache_entries=cache_entries),
        "idedup": lambda: make_idedup(cache_entries=cache_entries),
        "diode": lambda: DIODE(cache_entries=cache_entries, stream_templates=stream_of),
        "postproc": lambda: PurePostProcessing(),
    }


def _time_best(fn: Callable[[], object], reps: int) -> float:
    """Min-of-reps process time — this host is noisy; min is the stable stat."""
    best = float("inf")
    for _ in range(reps):
        t0 = time.process_time()
        fn()
        best = min(best, time.process_time() - t0)
    return best


def bench(
    workloads: List[str],
    n_requests: int,
    cache_entries: int,
    batch_size: int,
    reps: int,
    engines: List[str],
) -> List[dict]:
    rows = []
    for wl in workloads:
        trace, stream_of = generate_workload(wl, total_requests=n_requests, seed=0)
        n = len(trace)
        factories = engine_factories(cache_entries, stream_of)
        for name in engines:
            factory = factories[name]
            t_scalar = _time_best(lambda: factory().replay(trace), reps)
            t_batched = _time_best(
                lambda: factory().replay_batched(trace, batch_size=batch_size), reps
            )
            # equivalence cross-check: the batched path must be bit-exact
            rep_s = factory().replay(trace).finish()
            rep_b = factory().replay_batched(trace, batch_size=batch_size).finish()
            row = {
                "workload": wl,
                "engine": name,
                "requests": n,
                "scalar_rps": round(n / t_scalar),
                "batched_rps": round(n / t_batched),
                "speedup": round(t_scalar / t_batched, 2),
                "reports_equal": rep_s == rep_b,
            }
            rows.append(row)
            print(
                f"{wl} {name:9s} scalar {row['scalar_rps']:>9,d} rps   "
                f"batched {row['batched_rps']:>9,d} rps   "
                f"speedup {row['speedup']:.2f}x   equal={row['reports_equal']}"
            )
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="CI-sized quick run")
    ap.add_argument("--requests", type=int, default=200_000)
    ap.add_argument("--cache-entries", type=int, default=32_768)
    ap.add_argument("--batch-size", type=int, default=DEFAULT_BATCH_SIZE)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--workloads", nargs="+", default=["A", "B", "C"])
    ap.add_argument(
        "--engines", nargs="+", default=["hpdedup", "idedup", "diode", "postproc"],
        choices=["hpdedup", "idedup", "diode", "postproc"],
    )
    ap.add_argument("--out", default="BENCH_replay.json")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.smoke:
        args.requests = min(args.requests, 30_000)
        args.workloads = args.workloads[:1]
        args.reps = 1

    rows = bench(
        args.workloads, args.requests, args.cache_entries, args.batch_size, args.reps,
        args.engines,
    )
    by_engine: Dict[str, List[float]] = {}
    for r in rows:
        by_engine.setdefault(r["engine"], []).append(r["speedup"])
    summary = {e: round(sum(v) / len(v), 2) for e, v in by_engine.items()}
    payload = {
        "meta": {
            "requests": args.requests,
            "cache_entries": args.cache_entries,
            "batch_size": args.batch_size,
            "reps": args.reps,
            "workloads": args.workloads,
            "mean_speedup_by_engine": summary,
        },
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"\nmean speedup by engine: {summary}")
    print(f"wrote {args.out}")
    if not all(r["reports_equal"] for r in rows):
        print("ERROR: batched reports diverged from scalar oracle")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
