"""Online-GC benchmark: reclaim/compaction throughput + ingest-latency impact.

Drives an overwrite-heavy trace (every key from the first half is
overwritten with new content in the second half, so half the blocks ever
written become garbage) through a sharded cluster twice:

* **baseline** — parallel chunked ingest, no GC;
* **gc-under-load** — identical ingest with ``run_gc(wait=False)`` queued
  on the shard worker lanes every ``--gc-every`` chunks: epoch drain + a
  budgeted compaction step interleave with live traffic, no quiesce.

Per mode it records the per-chunk ingest latency distribution (p50/p99 of
the synchronous ``write_batch`` calls, which include any GC work queued
ahead on the lanes) and the reclaim counters; a final timed full
compaction measures steady-state relocation throughput.

Emits ``BENCH_gc.json``.  Gates (all runs):

* **exactness** — the GC run's ``HybridReport`` and live-block digest are
  identical to the baseline's;
* **reclaim** — the GC run physically reclaimed blocks (> 0) and closed
  PBA holes (> 0 relocations) while ingest was live;
* **bounded impact** — ingest p99 under GC stays within
  ``P99_DEGRADATION_X`` of baseline (plus an absolute grace for timer
  noise on tiny smoke chunks).

Usage:
    python benchmarks/gc_reclaim.py            # default scale
    python benchmarks/gc_reclaim.py --smoke    # CI-sized
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src"))

import numpy as np

from repro.core import ShardedCluster, generate_workload

# generous: a budgeted GC step every few chunks should cost well under one
# chunk of work, but 1-CPU CI runners timeshare the GC step with the
# coordinator thread, so the bar only catches pathological stalls
P99_DEGRADATION_X = 10.0
P99_ABS_GRACE_MS = 5.0


def overwrite_trace(total: int, seed: int, workload: str = "A") -> np.ndarray:
    base = generate_workload(workload, total_requests=total, seed=seed)[0]
    over = base.copy()
    over["ts"] = over["ts"] + int(base["ts"].max()) + 1
    over["fp"] = over["fp"] ^ np.uint64(0x9E3779B97F4A7C15)
    both = np.concatenate([base, over])
    both.sort(order="ts", kind="stable")
    return both


def live_digest(cluster) -> tuple:
    keys = sorted(
        (k[0], k[1], e.store.fp_of_pba[p])
        for e in cluster.shards
        for k, p in e.store.lba_map.items()
    )
    copies = sorted(
        (fp, len(pbas)) for e in cluster.shards for fp, pbas in e.store.fp_table.items()
    )
    return keys, copies


def run_ingest(trace, args, gc_every: int = 0) -> dict:
    """One chunked parallel ingest; ``gc_every`` > 0 queues an online-GC
    step after every that-many chunks.  Returns timings + reclaim stats."""
    c = ShardedCluster(num_shards=args.shards, cache_entries=args.cache_entries)
    c.min_parallel_batch = 0  # keep the worker path even for smoke chunks
    c.start_executor()
    cols = (trace["stream"], trace["lba"].astype(np.int64), trace["fp"])
    chunk = args.chunk
    lat_ms = []
    gc_calls = 0
    t0 = time.perf_counter()
    for i, lo in enumerate(range(0, len(trace), chunk)):
        t1 = time.perf_counter()
        c.write_batch(*(col[lo : lo + chunk] for col in cols))
        lat_ms.append((time.perf_counter() - t1) * 1e3)
        if gc_every and (i + 1) % gc_every == 0:
            c.run_gc(max_moves_per_shard=args.max_moves, wait=False)
            gc_calls += 1
    if gc_every:
        c.run_gc(wait=True)  # drain the last grace periods while still live
        gc_calls += 1
    ingest_wall = time.perf_counter() - t0
    # steady-state compaction throughput: one timed unbudgeted sweep
    t2 = time.perf_counter()
    final_stats = c.run_gc() if gc_every else None
    gc_wall = time.perf_counter() - t2
    rep = c.finish()
    digest = live_digest(c)
    c.check_consistency()
    freed, moved = c.reclaimed_blocks, c.relocated_blocks
    c.stop_executor()
    lat = np.asarray(lat_ms)
    return {
        "chunks": len(lat_ms),
        "gc_calls": gc_calls,
        "ingest_wall_s": round(ingest_wall, 4),
        "ingest_krps": round(len(trace) / ingest_wall / 1e3, 1),
        "p50_ms": round(float(np.percentile(lat, 50)), 3),
        "p99_ms": round(float(np.percentile(lat, 99)), 3),
        "freed_blocks": freed,
        "relocated_blocks": moved,
        "final_sweep": final_stats,
        "final_sweep_s": round(gc_wall, 4) if gc_every else None,
        "report": rep,
        "digest": digest,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="CI-sized quick run")
    ap.add_argument("--requests", type=int, default=120_000)
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--cache-entries", type=int, default=2048)
    ap.add_argument("--chunk", type=int, default=8192)
    ap.add_argument("--gc-every", type=int, default=4, help="chunks between GC steps")
    ap.add_argument("--max-moves", type=int, default=512, help="per-shard compaction budget")
    ap.add_argument("--out", default="BENCH_gc.json")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.smoke:
        args.requests = min(args.requests, 12_000)
        args.chunk = min(args.chunk, 1024)

    trace = overwrite_trace(args.requests, seed=17)
    base = run_ingest(trace, args, gc_every=0)
    gc = run_ingest(trace, args, gc_every=args.gc_every)

    exact = gc["report"] == base["report"] and gc["digest"] == base["digest"]
    p99_bound = round(base["p99_ms"] * P99_DEGRADATION_X + P99_ABS_GRACE_MS, 3)
    reclaim_rate = (
        round(gc["relocated_blocks"] / gc["final_sweep_s"], 1)
        if gc["final_sweep_s"] and gc["final_sweep_s"] > 0
        else None
    )

    def row(name, r):
        out = {k: v for k, v in r.items() if k not in ("report", "digest")}
        out["mode"] = name
        return out

    rows = [row("baseline", base), row("gc_under_load", gc)]
    payload = {
        "meta": {
            "requests": len(trace),
            "shards": args.shards,
            "cache_entries": args.cache_entries,
            "chunk": args.chunk,
            "gc_every_chunks": args.gc_every,
            "max_moves_per_shard": args.max_moves,
            "cpus": os.cpu_count() or 1,
            "smoke": args.smoke,
            "gates": "bit-exact report+digest vs no-GC; freed>0; relocated>0; "
            f"p99 <= {P99_DEGRADATION_X}x baseline + {P99_ABS_GRACE_MS}ms",
        },
        "rows": rows,
        "derived": {
            "exact_vs_baseline": bool(exact),
            "p99_bound_ms": p99_bound,
            "p99_under_gc_ms": gc["p99_ms"],
            "relocations_per_s_final_sweep": reclaim_rate,
        },
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    for r in rows:
        print(
            f"{r['mode']:14s} {r['chunks']:>4d} chunks  p50 {r['p50_ms']:7.2f} ms  "
            f"p99 {r['p99_ms']:7.2f} ms  freed {r['freed_blocks']:>6,d}  "
            f"relocated {r['relocated_blocks']:>6,d}  gc_calls {r['gc_calls']}"
        )
    print(f"wrote {args.out}")

    if not exact:
        print("ERROR: GC-under-load run diverged from the no-GC baseline")
        return 1
    if gc["freed_blocks"] <= 0:
        print("ERROR: GC run reclaimed no blocks")
        return 1
    if gc["relocated_blocks"] <= 0:
        print("ERROR: GC run closed no PBA holes (0 relocations)")
        return 1
    if gc["p99_ms"] > p99_bound:
        print(
            f"ERROR: ingest p99 under GC ({gc['p99_ms']} ms) exceeded the "
            f"bound ({p99_bound} ms = {P99_DEGRADATION_X}x baseline "
            f"{base['p99_ms']} ms + {P99_ABS_GRACE_MS} ms)"
        )
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
