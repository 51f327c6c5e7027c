"""Replication benchmark: shard-loss recovery + effective dedup ratio vs R.

Sweeps a (shards x replication-factor) grid over a FASTEN-style overwrite
trace (arXiv 2312.08309: dedup concentrates failure blast radius, so the
interesting curve is how much dedup ratio you trade for R-way copies).
Per cell it runs:

* **oracle** — the trace through an uninterrupted cluster at that R, as two
  parallel ``replay_batched`` calls;
* **kill-recover** (R >= 2 only) — the *same* two calls, but the last shard
  is ``fail_shard``-ed between them and rebuilt with ``recover_shard``
  (checkpoint restore + chunk-aligned oplog roll-forward + mirror rebuild)
  before the second call; recovery wall time is the headline number.

Emits ``BENCH_replication.json``.  Gates (all runs):

* **recovery exactness** — every kill-recover cell's aggregate
  ``HybridReport`` and live-block digest are bit-identical to its oracle;
* **replica accounting** — every cell holds exactly
  ``(R_eff - 1) * final_disk_blocks`` mirror copies at the final barrier;
* **ratio curve** — the effective dedup ratio (logical writes per physical
  block, mirrors included) equals ``ratio_R1 / R_eff`` per shard count —
  replication divides capacity savings, it must never change decisions.

Usage:
    python benchmarks/replication.py            # default scale
    python benchmarks/replication.py --smoke    # CI-sized
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src"))

import numpy as np

from repro.core import ShardedCluster, generate_workload

SHARD_COUNTS = [2, 4, 8]
FACTORS = [1, 2, 3]
RATIO_REL_TOL = 1e-9


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


def make_cluster(shards: int, factor: int, args) -> ShardedCluster:
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)  # R > shards clamps
        return ShardedCluster(
            num_shards=shards,
            cache_entries=args.cache_entries,
            routing="fingerprint",
            replication_factor=factor,
        )


def run_cell(trace, shards: int, factor: int, args) -> dict:
    """One grid cell: oracle run, then (at R >= 2) the kill-recover run."""
    half = len(trace) // 2

    oracle = make_cluster(shards, factor, args)
    oracle.start_executor()
    oracle.replay_batched(trace[:half], batch_size=args.batch, parallel=True)
    oracle.replay_batched(trace[half:], batch_size=args.batch, parallel=True)
    rep = oracle.finish()
    digest = live_digest(oracle)
    replica_blocks = oracle.replica_blocks
    r_eff = oracle.effective_replication
    oracle.stop_executor()

    ratio = rep.total_writes / rep.final_disk_blocks
    physical = rep.final_disk_blocks + replica_blocks
    row = {
        "shards": shards,
        "replication_factor": factor,
        "effective_replication": r_eff,
        "final_disk_blocks": rep.final_disk_blocks,
        "replica_blocks": replica_blocks,
        "dedup_ratio": round(ratio, 4),
        "effective_dedup_ratio": round(rep.total_writes / physical, 4),
        "replica_invariant_ok": replica_blocks == (r_eff - 1) * rep.final_disk_blocks,
    }

    if r_eff >= 2:
        victim = shards - 1
        c = make_cluster(shards, factor, args)
        c.start_executor()
        c.replay_batched(trace[:half], batch_size=args.batch, parallel=True)
        c.fail_shard(victim)
        t0 = time.perf_counter()
        stats = c.recover_shard(victim)
        recovery_s = time.perf_counter() - t0
        c.replay_batched(trace[half:], batch_size=args.batch, parallel=True)
        got = c.finish()
        row.update(
            {
                "victim_shard": victim,
                "recovery_ms": round(recovery_s * 1e3, 2),
                "recovery_replayed_ops": stats["replayed"],
                "recovery_ops_per_s": round(stats["replayed"] / recovery_s, 1)
                if recovery_s > 0
                else None,
                "recovered_mirror_copies": stats["mirror_copies"],
                "recovery_exact": got == rep and live_digest(c) == digest,
            }
        )
        c.stop_executor()
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="CI-sized quick run")
    ap.add_argument("--requests", type=int, default=60_000)
    ap.add_argument("--cache-entries", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=2048)
    ap.add_argument("--out", default="BENCH_replication.json")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.smoke:
        args.requests = min(args.requests, 8_000)
        args.batch = min(args.batch, 512)

    trace = overwrite_trace(args.requests, seed=29)
    rows = [
        run_cell(trace, shards, factor, args)
        for shards in SHARD_COUNTS
        for factor in FACTORS
    ]

    bad_recovery = [r for r in rows if "recovery_exact" in r and not r["recovery_exact"]]
    bad_invariant = [r for r in rows if not r["replica_invariant_ok"]]
    bad_curve = []
    for shards in SHARD_COUNTS:
        cells = {r["effective_replication"]: r for r in rows if r["shards"] == shards}
        base_ratio = cells[1]["dedup_ratio"]
        for r_eff, cell in cells.items():
            want = base_ratio / r_eff
            if abs(cell["effective_dedup_ratio"] - want) > max(
                RATIO_REL_TOL * want, 1e-4
            ):
                bad_curve.append((shards, r_eff))

    payload = {
        "meta": {
            "requests": len(trace),
            "cache_entries": args.cache_entries,
            "batch": args.batch,
            "grid": {"shards": SHARD_COUNTS, "replication_factor": FACTORS},
            "cpus": os.cpu_count() or 1,
            "smoke": args.smoke,
            "gates": "kill-recover bit-exact report+digest vs oracle at every "
            "R>=2 cell; replica_blocks == (R_eff-1)*final_disk_blocks; "
            "effective ratio == ratio_R1 / R_eff",
        },
        "rows": rows,
        "derived": {
            "recovery_cells": sum(1 for r in rows if "recovery_exact" in r),
            "all_recoveries_exact": not bad_recovery,
            "max_recovery_ms": max(
                (r["recovery_ms"] for r in rows if "recovery_ms" in r), default=None
            ),
            "ratio_curve": {
                str(s): {
                    str(r["effective_replication"]): r["effective_dedup_ratio"]
                    for r in rows
                    if r["shards"] == s
                }
                for s in SHARD_COUNTS
            },
        },
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    for r in rows:
        rec = (
            f"recover {r['recovery_ms']:8.2f} ms ({r['recovery_replayed_ops']:>6,d} ops)"
            f"  exact {r['recovery_exact']}"
            if "recovery_ms" in r
            else "no kill (R_eff = 1)"
        )
        print(
            f"shards {r['shards']:>2d}  R {r['replication_factor']} "
            f"(eff {r['effective_replication']})  "
            f"ratio {r['dedup_ratio']:7.3f} -> effective {r['effective_dedup_ratio']:7.3f}  "
            f"{rec}"
        )
    print(f"wrote {args.out}")

    if bad_recovery:
        cells = [(r["shards"], r["replication_factor"]) for r in bad_recovery]
        print(f"ERROR: kill-recover diverged from the oracle at cells {cells}")
        return 1
    if bad_invariant:
        cells = [(r["shards"], r["replication_factor"]) for r in bad_invariant]
        print(f"ERROR: replica accounting broke (R_eff-1)*blocks at cells {cells}")
        return 1
    if bad_curve:
        print(f"ERROR: effective dedup ratio off the ratio_R1/R curve at {bad_curve}")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
