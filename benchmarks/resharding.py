"""Elastic-resharding benchmark: resize cost and keys-moved fraction.

Ingests part of a synthetic workload into a ``ShardedCluster``, calls
``resize(N -> M)`` mid-stream, ingests the rest, and measures:

* **moved fraction** — the share of the key population (ground-truth seen
  fingerprints) that changed shards, against the consistent-hash minimal
  bound: ``(M - N) / M`` on grow, ``(N - M) / N`` on shrink.  Exceeding the
  bound by more than the ring-imbalance tolerance means the remap is no
  longer minimal — that is the benchmark's failure gate.
* **resize cost** — wall time of the migration, alongside migrated
  blocks/cache entries and resize throughput (moved keys / second).
* **exactness** — after finishing the interrupted-and-resized replay, the
  cluster's aggregate dedup counts must equal the uninterrupted
  single-engine oracle's, and conservation (inline dups + post reclaims ==
  duplicate writes) must hold.

Emits ``BENCH_resharding.json``::

    {"meta": {...}, "rows": [
        {"workload": "A", "from": 2, "to": 4, "moved_fraction": ...,
         "minimal_bound": ..., "resize_s": ..., "moved_keys_per_s": ...,
         "counts_equal": true}, ...]}

Usage:
    python benchmarks/resharding.py            # default scale
    python benchmarks/resharding.py --smoke    # CI-sized
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import List, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src"))

from repro.core import HPDedup, ShardedCluster, generate_workload

# ring-imbalance tolerance over the theoretical minimal fraction: with 64
# vnodes/shard, per-shard ownership shares fluctuate a few percent
SLACK = 0.08


def minimal_bound(n_from: int, n_to: int) -> float:
    if n_to >= n_from:
        return (n_to - n_from) / n_to
    return (n_from - n_to) / n_from


def counts_equal(cluster_rep, oracle_rep) -> bool:
    return (
        cluster_rep.total_writes == oracle_rep.total_writes
        and cluster_rep.total_dup_writes == oracle_rep.total_dup_writes
        and cluster_rep.unique_fingerprints == oracle_rep.unique_fingerprints
        and cluster_rep.final_disk_blocks == oracle_rep.final_disk_blocks
        and cluster_rep.inline.inline_dups + cluster_rep.post.blocks_reclaimed
        == cluster_rep.total_dup_writes
    )


def bench(
    workloads: List[str],
    n_requests: int,
    cache_entries: int,
    batch_size: int,
    transitions: List[Tuple[int, int]],
) -> List[dict]:
    rows = []
    for wl in workloads:
        trace, _ = generate_workload(wl, total_requests=n_requests, seed=0)
        oracle = HPDedup(cache_entries=cache_entries)
        oracle.replay_batched(trace, batch_size=batch_size)
        oracle_rep = oracle.finish()

        for n_from, n_to in transitions:
            cluster = ShardedCluster(num_shards=n_from, cache_entries=cache_entries)
            cut = (len(trace) // (2 * batch_size * n_from)) * batch_size * n_from
            cluster.ingest_batched(trace[:cut], batch_size)
            t0 = time.perf_counter()
            stats = cluster.resize(n_to)
            resize_s = time.perf_counter() - t0
            cluster.ingest_batched(trace[cut:], batch_size)
            rep = cluster.finish()
            cluster.check_consistency()
            bound = minimal_bound(n_from, n_to)
            row = {
                "workload": wl,
                "from": n_from,
                "to": n_to,
                "requests": len(trace),
                "key_population": stats["key_population"],
                "moved_fps": stats["moved_fps"],
                "moved_blocks": stats["moved_blocks"],
                "moved_cache_entries": stats["moved_cache_entries"],
                "moved_fraction": round(stats["moved_fraction"], 4),
                "minimal_bound": round(bound, 4),
                "within_bound": stats["moved_fraction"] <= bound + SLACK,
                "resize_s": round(resize_s, 4),
                "moved_keys_per_s": round(stats["moved_fps"] / resize_s) if resize_s else 0,
                "counts_equal": counts_equal(rep, oracle_rep),
            }
            rows.append(row)
            print(
                f"{wl} {n_from}->{n_to}: moved {row['moved_fps']:>7,d}/{row['key_population']:,d} "
                f"({row['moved_fraction']:.3f}, bound {row['minimal_bound']:.3f}"
                f"{'+slack OK' if row['within_bound'] else ' EXCEEDED'})   "
                f"resize {row['resize_s']:.3f}s ({row['moved_keys_per_s']:,d} keys/s)   "
                f"counts_equal={row['counts_equal']}"
            )
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="CI-sized quick run")
    ap.add_argument("--requests", type=int, default=200_000)
    ap.add_argument("--cache-entries", type=int, default=8_192)
    ap.add_argument("--batch-size", type=int, default=2_048)
    ap.add_argument("--workloads", nargs="+", default=["A", "B", "C"])
    ap.add_argument(
        "--transitions",
        type=int,
        nargs="+",
        default=[2, 4, 4, 8, 8, 4, 4, 2, 1, 8],
        help="flat from/to pairs, e.g. --transitions 2 4 4 2",
    )
    ap.add_argument("--out", default="BENCH_resharding.json")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    if len(args.transitions) % 2:
        ap.error("--transitions takes from/to pairs")
    transitions = list(zip(args.transitions[::2], args.transitions[1::2]))
    if args.smoke:
        args.requests = min(args.requests, 30_000)
        args.workloads = args.workloads[:1]
        transitions = [(2, 4), (4, 2)]

    rows = bench(
        args.workloads, args.requests, args.cache_entries, args.batch_size, transitions
    )
    payload = {
        "meta": {
            "requests": args.requests,
            "cache_entries": args.cache_entries,
            "batch_size": args.batch_size,
            "workloads": args.workloads,
            "transitions": transitions,
            "moved_fraction_slack": SLACK,
        },
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {args.out}")
    if not all(r["counts_equal"] for r in rows):
        print("ERROR: post-resize aggregate dedup counts diverged from the oracle")
        return 1
    if not all(r["within_bound"] for r in rows):
        print("ERROR: resize moved more keys than the minimal-remap bound allows")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
