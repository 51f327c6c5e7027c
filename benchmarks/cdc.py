"""Content-defined chunking: fused pipeline throughput + correctness gates.

Four gates (ISSUE 10):

* **Throughput** — the batched default-backend pipeline (bytes -> boundary
  candidates -> chunk fingerprints; the fused device chain on TPU, its
  bit-identical vectorized fallback elsewhere) must process batched input at
  >= 3x the scalar reference (the per-byte rolling-hash recurrence plus
  per-chunk unbatched hashing).  Both sides run live in this process, so
  the ratio is host-independent.
* **Bit-exactness** — scalar oracle, numpy path and Pallas path agree on
  boundaries AND chunk fingerprints over an edge-size buffer sweep.
* **Shift resistance** — a 64-byte insert into a 200 KB buffer changes at
  most 8 chunks (prefix/suffix fingerprint compare), i.e. O(1), not O(n).
* **Analytic bounds** — both byte-backed workload generators
  (VM-image-with-edits, log-append) land their measured byte-weighted dedup
  ratio inside the Niesen envelope computed from generator ground truth.

The interpret-mode Pallas rate is recorded for reference (the TPU path's
CPU proxy — a correctness artifact, not a throughput target).  Emits
``BENCH_cdc.json``; exit code 1 if a gate fails.

Usage:
    python benchmarks/cdc.py            # default scale
    python benchmarks/cdc.py --smoke    # CI-sized
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Callable, List

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src"))

from repro.core.cdc import ContentDefinedChunker
from repro.core.traces import trace_stats
from repro.data.byte_workloads import (
    analytic_bounds,
    byte_trace,
    log_append_workload,
    vm_image_workload,
)

MIN_SPEEDUP = 3.0
SHIFT_BUDGET = 8  # max chunks a 64-byte insert may change
CFG = (2048, 4096, 16384)       # throughput config (paper-scale chunk sizes)
CFG_SMALL = (256, 1024, 4096)   # correctness/workload config (denser chunks)


def _time_best(fn: Callable[[], object], reps: int) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_throughput(batch_mb: float, scalar_kb: int, reps: int) -> List[dict]:
    rng = np.random.default_rng(0)
    n_bufs = 4
    per = int(batch_mb * 1e6 / n_bufs)
    batched_bufs = [rng.integers(0, 256, size=per, dtype=np.uint8) for _ in range(n_bufs)]
    scalar_buf = [rng.integers(0, 256, size=scalar_kb * 1024, dtype=np.uint8)]

    default = ContentDefinedChunker(*CFG)
    scalar = ContentDefinedChunker(*CFG, backend="scalar")
    pallas = ContentDefinedChunker(*CFG, backend="pallas")
    warm = [batched_bufs[0][:32768]]
    default.chunk_fingerprints_many(warm)
    pallas.chunk_fingerprints_many(warm)

    t_def = _time_best(lambda: default.chunk_fingerprints_many(batched_bufs), reps)
    t_sca = _time_best(lambda: scalar.chunk_fingerprints_many(scalar_buf), 1)
    t_pal = _time_best(lambda: pallas.chunk_fingerprints_many(batched_bufs), 1)

    mb_batch = sum(b.size for b in batched_bufs) / 1e6
    mb_scalar = scalar_buf[0].size / 1e6
    def_mbps = mb_batch / t_def
    sca_mbps = mb_scalar / t_sca
    speedup = def_mbps / sca_mbps
    return [{
        "bench": "throughput",
        "batch_mb": round(mb_batch, 2),
        "scalar_mb": round(mb_scalar, 3),
        "scalar_mbps": round(sca_mbps, 2),
        "fused_mbps": round(def_mbps, 2),
        "pallas_interpret_mbps": round(mb_batch / t_pal, 2),
        "speedup": round(speedup, 2),
        "pass": speedup >= MIN_SPEEDUP,
    }]


def bench_exactness() -> dict:
    rng = np.random.default_rng(1)
    sizes = [0, 100, 1000, 2048, 2049, 5000, 40000]
    bufs = [rng.integers(0, 256, size=n, dtype=np.uint8) for n in sizes]
    ref = ContentDefinedChunker(*CFG_SMALL, backend="scalar").chunk_fingerprints_many(bufs)
    ok = True
    for backend in ("numpy", "pallas"):
        got = ContentDefinedChunker(*CFG_SMALL, backend=backend).chunk_fingerprints_many(bufs)
        for (e1, f1), (e2, f2) in zip(ref, got):
            ok = ok and bool(np.array_equal(e1, e2) and np.array_equal(f1, f2))
    return {"bench": "exactness", "sizes": str(sizes), "bit_exact": ok}


def bench_shift_resistance() -> dict:
    rng = np.random.default_rng(2)
    data = rng.integers(0, 256, size=200_000, dtype=np.uint8)
    ck = ContentDefinedChunker(*CFG_SMALL)
    _, fa = ck.chunk_fingerprints(data)
    worst = 0
    for pos in (0, 63_000, 140_000, 199_999):
        ins = rng.integers(0, 256, size=64, dtype=np.uint8)
        _, fb = ck.chunk_fingerprints(np.concatenate([data[:pos], ins, data[pos:]]))
        pre = 0
        m = min(fa.size, fb.size)
        while pre < m and fa[pre] == fb[pre]:
            pre += 1
        suf = 0
        while suf < m - pre and fa[fa.size - 1 - suf] == fb[fb.size - 1 - suf]:
            suf += 1
        worst = max(worst, int(fa.size + fb.size - 2 * (pre + suf)))
    return {
        "bench": "shift_resistance",
        "chunks": int(fa.size),
        "worst_changed": worst,
        "budget": SHIFT_BUDGET,
        "pass": worst <= SHIFT_BUDGET,
    }


def bench_workload_bounds(smoke: bool) -> List[dict]:
    scale = 1 if smoke else 2
    workloads = [
        vm_image_workload(num_streams=2, base_size=scale * 128 * 1024,
                          versions=3, edits_per_version=3, seed=0),
        log_append_workload(num_streams=2, snapshots=4,
                            append_size=scale * 32 * 1024, seed=1),
    ]
    ck = ContentDefinedChunker(*CFG_SMALL)
    rows = []
    for w in workloads:
        trace, lens = byte_trace(ck, w)
        lower, upper = analytic_bounds(w, ck.config.max_size)
        measured = trace_stats(trace, chunk_bytes=lens)["byte_dup_ratio"]
        rows.append({
            "bench": "analytic_bounds",
            "workload": w.name,
            "total_mb": round(w.total_bytes / 1e6, 2),
            "chunks": int(len(trace)),
            "lower": round(lower, 4),
            "measured": round(measured, 4),
            "upper": round(upper, 4),
            "pass": lower <= measured <= upper + 1e-9,
        })
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="CI-sized quick run")
    ap.add_argument("--batch-mb", type=float, default=4.0)
    ap.add_argument("--scalar-kb", type=int, default=128)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default="BENCH_cdc.json")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.smoke:
        args.batch_mb = min(args.batch_mb, 1.0)
        args.scalar_kb = min(args.scalar_kb, 32)
        args.reps = 1

    rows = bench_throughput(args.batch_mb, args.scalar_kb, args.reps)
    rows.append(bench_exactness())
    rows.append(bench_shift_resistance())
    rows.extend(bench_workload_bounds(args.smoke))

    for r in rows:
        print(" ".join(f"{k}={v}" for k, v in r.items()))

    gates = {
        "fused_vs_scalar_speedup": all(r["pass"] for r in rows if r["bench"] == "throughput"),
        "backends_bit_exact": all(r["bit_exact"] for r in rows if r["bench"] == "exactness"),
        "shift_resistance": all(r["pass"] for r in rows if r["bench"] == "shift_resistance"),
        "analytic_bounds_pass": all(r["pass"] for r in rows if r["bench"] == "analytic_bounds"),
    }
    payload = {
        "meta": {
            "batch_mb": args.batch_mb,
            "scalar_kb": args.scalar_kb,
            "reps": args.reps,
            "min_speedup": MIN_SPEEDUP,
            "cfg": list(CFG),
            "cfg_small": list(CFG_SMALL),
            "gates": gates,
        },
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"\ngates: {gates}")
    print(f"wrote {args.out}")
    return 0 if all(gates.values()) else 1


if __name__ == "__main__":
    raise SystemExit(main())
