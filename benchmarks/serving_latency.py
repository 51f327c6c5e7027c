"""Multi-tenant serving front-end benchmark: p50/p99 write latency + rps.

Drives ``AsyncDedupFrontend`` (serving/frontend.py) with hundreds of
concurrent client connections over a skewed 16-tenant mix — request volume
follows a Zipf-like tenant skew and per-tenant duplicate locality spans
high (mail-like) to low (web-like), mirroring the paper's observation that
streams differ wildly in temporal locality.  Per scenario it measures:

* per-tenant and aggregate **p50/p99 write latency** (submit -> inline
  flag resolved, i.e. including batching delay and queueing) and
  aggregate **rps** over the wall of the run;
* **exactness**: with ``record_trace=True`` the frontend captures the
  exact batch interleaving it executed; replaying that interleaving
  through a fresh identically-configured engine must reproduce a
  **bit-exact** ``HybridReport`` — the serving layer adds concurrency,
  never a different answer;
* **admission control**: the ``contended`` scenario shrinks the inline
  cache so occupancy crosses the contention threshold and low-locality
  tenants get throttled at the door (``throttled`` counts recorded).

Emits ``BENCH_serving.json``::

    {"meta": {...}, "rows": [
        {"scenario": "skewed16", "requests": ..., "rps": ...,
         "p50_ms": ..., "p99_ms": ..., "throttled": ...,
         "deterministic": true, "tenants": {...}}, ...]}

Gates: exactness (``deterministic``) always; full runs additionally gate
aggregate throughput (rps >= RPS_FLOOR) and tail latency
(p99 <= P99_CEILING_MS) on the ``skewed16`` scenario.  ``--smoke`` gates
exactness only — latency numbers from 1-rep runs on shared CI runners
are noise.

Usage:
    python benchmarks/serving_latency.py            # default scale
    python benchmarks/serving_latency.py --smoke    # CI-sized
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
from typing import Dict, List, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src"))

import numpy as np

from repro.core import HPDedup, ShardedCluster
from repro.serving.frontend import AsyncDedupFrontend

N_TENANTS = 16
# Full-run QoS bars for the skewed16 scenario, calibrated against the
# 1-CPU reference runner (measured ~2.3k rps / p99 ~97 ms at default
# scale) with ~1.5-2.5x margin for scheduler noise.  The front end is a
# pure-Python asyncio layer, so per-write loop overhead — not the engine —
# sets the ceiling; multi-core hosts clear these bars by a wide margin.
RPS_FLOOR = 1_500
P99_CEILING_MS = 250.0


def make_tenant_workload(
    n_requests: int, seed: int
) -> Dict[int, Tuple[np.ndarray, np.ndarray]]:
    """Per-tenant (lba, fp) columns with Zipf volume skew + mixed locality.

    Tenant t's request share ~ 1/(t+1) (heaviest tenant ~6x the lightest
    over 16 tenants); duplicate ratio ramps from 0.7 (high temporal
    locality, mail-like) down to 0.05 (low, web-like).  Fingerprint spaces
    are tenant-disjoint except a small shared slice so cross-tenant
    duplicates exist too.
    """
    rng = np.random.default_rng(seed)
    weights = 1.0 / (np.arange(N_TENANTS) + 1.0)
    weights /= weights.sum()
    shared_pool = rng.integers(1, 2**62, size=256, dtype=np.uint64)
    out: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
    for t in range(N_TENANTS):
        n = max(64, int(n_requests * weights[t]))
        dup_ratio = 0.7 - 0.65 * t / (N_TENANTS - 1)
        n_unique = max(8, int(n * (1.0 - dup_ratio)))
        pool = rng.integers(1, 2**62, size=n_unique, dtype=np.uint64)
        # ~4% of requests hit the cross-tenant shared pool
        take_shared = rng.random(n) < 0.04
        fps = np.where(
            take_shared,
            shared_pool[rng.integers(0, len(shared_pool), size=n)],
            pool[rng.integers(0, n_unique, size=n)],
        ).astype(np.uint64)
        # mostly sequential LBAs with occasional overwrite jumps back
        lbas = np.arange(n, dtype=np.int64)
        jump = rng.random(n) < 0.1
        lbas[jump] = rng.integers(0, n, size=int(jump.sum()))
        out[t] = (lbas, fps)
    return out


def make_engine(num_shards: int, cache_entries: int, seed: int = 0):
    if num_shards <= 1:
        return HPDedup(cache_entries=cache_entries, seed=seed)
    return ShardedCluster(num_shards=num_shards, cache_entries=cache_entries)


async def run_scenario(
    workload: Dict[int, Tuple[np.ndarray, np.ndarray]],
    engine,
    conns_per_tenant: int,
    max_batch: int,
    max_delay: float,
    max_pending: int,
    resize_to: int = 0,
    admission_budget: int = 0,
) -> Tuple[dict, AsyncDedupFrontend]:
    fe = AsyncDedupFrontend(
        engine,
        max_batch=max_batch,
        max_delay=max_delay,
        max_pending=max_pending,
        admission_budget=admission_budget or None,
        record_trace=True,
    )

    async def connection(tenant: int, lbas: np.ndarray, fps: np.ndarray) -> None:
        for lba, fp in zip(lbas.tolist(), fps.tolist()):
            await fe.write(tenant, fp, lba=lba)

    # hundreds of concurrent client streams: each tenant's columns are
    # strided across ``conns_per_tenant`` connections (disjoint LBA slices,
    # so concurrent same-tenant connections never race on a block)
    clients = []
    for t, (lbas, fps) in workload.items():
        for c in range(conns_per_tenant):
            clients.append(connection(t, lbas[c::conns_per_tenant], fps[c::conns_per_tenant]))
    t0 = time.perf_counter()
    if resize_to:
        async def resize_midway():
            await asyncio.sleep(0.01)
            await fe.resize(resize_to)
        clients.append(resize_midway())
    await asyncio.gather(*clients)
    await fe.drain()
    wall = time.perf_counter() - t0
    stats = fe.stats()
    stats["wall_s"] = round(wall, 4)
    stats["rps"] = round(stats["completed"] / wall) if wall > 0 else 0
    stats["connections"] = len(workload) * conns_per_tenant
    await fe.close()
    return stats, fe


def check_deterministic(fe: AsyncDedupFrontend, engine_report, fresh_engine) -> bool:
    """Bit-exact differential: the executed interleaving through a fresh
    engine must reproduce the served engine's HybridReport exactly."""
    tenants, lbas, fps = fe.executed_trace()
    fresh_engine.write_batch(tenants, lbas, fps)
    return fresh_engine.finish() == engine_report


def bench(args) -> List[dict]:
    rows = []
    scenarios = [
        # name, shards, cache_entries, resize_to
        ("skewed16", args.shards, args.cache_entries, 0),
        ("contended", args.shards, 192, 0),  # tiny cache -> admission control
        ("resize_under_load", max(args.shards, 2), args.cache_entries, max(args.shards, 2) + 2),
    ]
    for name, shards, cache_entries, resize_to in scenarios:
        workload = make_tenant_workload(args.requests, seed=11)
        engine = make_engine(shards, cache_entries)
        stats, fe = asyncio.run(
            run_scenario(
                workload,
                engine,
                conns_per_tenant=args.conns_per_tenant,
                max_batch=args.max_batch,
                max_delay=args.max_delay,
                max_pending=args.max_pending,
                resize_to=resize_to,
                # caps must bind against real client concurrency for the
                # contended-cache policy to throttle anyone
                admission_budget=(N_TENANTS * args.conns_per_tenant) // 2,
            )
        )
        rep = engine.finish()
        if resize_to:
            # resize migrates state mid-stream: the fixed-layout oracle
            # checks aggregate exact-dedup counts instead of bit-exactness
            tenants, lbas, fps = fe.executed_trace()
            oracle = make_engine(shards, cache_entries)
            oracle.write_batch(tenants, lbas, fps)
            orep = oracle.finish()
            deterministic = (
                rep.total_writes == orep.total_writes
                and rep.unique_fingerprints == orep.unique_fingerprints
                and rep.final_disk_blocks == orep.final_disk_blocks
            )
        else:
            deterministic = check_deterministic(fe, rep, make_engine(shards, cache_entries))
        row = {
            "scenario": name,
            "shards": shards,
            "cache_entries": cache_entries,
            "requests": stats["completed"],
            "connections": stats["connections"],
            "rps": stats["rps"],
            "wall_s": stats["wall_s"],
            "p50_ms": stats["p50_ms"],
            "p99_ms": stats["p99_ms"],
            "mean_batch": stats["mean_batch"],
            "batches": stats["batches"],
            "throttled": stats["throttled"],
            "deduped": stats["deduped"],
            "deterministic": bool(deterministic),
            "tenants": stats["tenants"],
        }
        rows.append(row)
        print(
            f"{name:18s} {row['requests']:>7,d} req / {row['connections']:>3d} conns   "
            f"{row['rps']:>9,d} rps   p50 {row['p50_ms']:6.2f} ms   p99 {row['p99_ms']:6.2f} ms   "
            f"throttled {row['throttled']:>6,d}   deterministic={row['deterministic']}"
        )
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="CI-sized quick run")
    ap.add_argument("--requests", type=int, default=120_000)
    ap.add_argument("--conns-per-tenant", type=int, default=16)
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--cache-entries", type=int, default=8192)
    ap.add_argument("--max-batch", type=int, default=1024)
    ap.add_argument("--max-delay", type=float, default=0.002)
    ap.add_argument("--max-pending", type=int, default=16384)
    ap.add_argument("--out", default="BENCH_serving.json")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.smoke:
        args.requests = min(args.requests, 12_000)
        args.conns_per_tenant = 8

    rows = bench(args)
    payload = {
        "meta": {
            "tenants": N_TENANTS,
            "conns_per_tenant": args.conns_per_tenant,
            "requests": args.requests,
            "shards": args.shards,
            "cache_entries": args.cache_entries,
            "max_batch": args.max_batch,
            "max_delay_s": args.max_delay,
            "max_pending": args.max_pending,
            "cpus": os.cpu_count() or 1,
            "latency": "submit -> inline flag resolved (includes batching delay)",
            "gates": "deterministic always; full runs: "
            f"rps >= {RPS_FLOOR} and p99 <= {P99_CEILING_MS} ms on skewed16",
        },
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {args.out}")

    bad = [r["scenario"] for r in rows if not r["deterministic"]]
    if bad:
        print(f"ERROR: serving differential diverged from the serial oracle: {bad}")
        return 1
    contended = next(r for r in rows if r["scenario"] == "contended")
    if contended["throttled"] == 0:
        print("ERROR: contended scenario produced no admission throttling")
        return 1
    if not args.smoke:
        main_row = next(r for r in rows if r["scenario"] == "skewed16")
        if main_row["rps"] < RPS_FLOOR:
            print(f"ERROR: aggregate throughput bar (>= {RPS_FLOOR} rps) missed: {main_row['rps']}")
            return 1
        if main_row["p99_ms"] > P99_CEILING_MS:
            print(f"ERROR: tail latency bar (p99 <= {P99_CEILING_MS} ms) missed: {main_row['p99_ms']}")
            return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
