"""Traffic family ``hybrid_block_writes``: ``block_writes`` with HPDedup's
exact post-processing phase running on the served path.

The generator, the disks' depths and the closed loop are ``block_writes``'
own.  The cluster is built as ``common.make_cluster`` builds it, plus
``postprocess_period`` (the configuration's
``cluster.postprocess_period_writes``): every shard runs a pass each time it
has taken that many writes, while the state ages and inside the window.

A run fails at once, before any trace is generated, on a program without
the counters the cell's metrics read (the reverse index's
``reverse_keys_walked``).  After the window closes, with the chip's memory
peak read, it reports ``block_writes``' four membership checks and four of
its own against ``bench/reference_post.py``:

* ``post_pass_gap``: per shard, passes run against the passes its writes
  made due (one per ``postprocess_period`` writes), read before anything
  else runs a pass;
* ``post_backlog_gap``: per shard, then, duplicate rows beyond the writes
  since its last pass (a pass that ran short leaves more);
* ``exact_gap``: after one cluster-wide ``run_postprocess(to_exact=True)``,
  |live blocks − distinct fingerprints the written keys hold|;
* ``readback_gap``: after that pass, acknowledged writes, those that aged
  the state and those since, whose (disk, LBA) does not read back, through
  the owning shard's store, a live block holding the fingerprint its last
  write wrote (the passes have merged blocks under them).

Traffic file keys: ``in_flight``, ``supply_writes``, ``warmup_batches``.
"""

from __future__ import annotations

import asyncio
import time
from typing import Dict, Optional

import numpy as np

from bench import common, reference, reference_post
from bench.families import block_writes as bw

CONTROL_BITS = bw.CONTROL_BITS


class _Counted:
    """The front end as the closed loop sees it, counting each disk's
    writes: a disk serves its supply in order, so its acknowledged writes
    are the first ``issued[d]`` of it."""

    def __init__(self, fe, disks: int):
        self.fe = fe
        self.issued = [0] * disks

    @property
    def batches_executed(self) -> int:
        return self.fe.batches_executed

    def write(self, d, fp, lba):
        self.issued[d] += 1
        return self.fe.write(d, fp, lba=lba)

    def close(self):
        return self.fe.close()


def make_cluster(cfg: dict):
    """``common.make_cluster``'s cluster with the configuration's period."""
    from repro.core import ShardedCluster

    c = cfg["cluster"]
    return ShardedCluster(num_shards=c["shards"], routing=c["routing"], seed=c["seed"],
                          replication_factor=c["replication_factor"],
                          cache_entries=c["cache_entries_per_shard"],
                          postprocess_period=c["postprocess_period_writes"])


def require_counters(cluster) -> None:
    """Fail where the program lacks what the cell's metrics read."""
    if not all(hasattr(e.store, "reverse_keys_walked") for e in cluster.shards):
        raise RuntimeError("the program keeps no reverse-index counter "
                           "(BlockStore.reverse_keys_walked): this cell cannot be measured")


def counters(cluster, fe=None, spans=()) -> Dict[str, float]:
    """``common.counters`` plus the exact phase's, summed over shards."""
    out = common.counters(cluster, fe, spans)
    out.update(post_passes=0, post_merges=0, post_reclaimed=0, reverse_keys=0)
    for e in cluster.shards:
        out["post_passes"] += e.post.metrics.passes
        out["post_merges"] += e.post.metrics.merges
        out["post_reclaimed"] += e.post.metrics.blocks_reclaimed
        out["reverse_keys"] += e.store.reverse_keys_walked
    return out


def post_state(cluster) -> Dict[str, list]:
    """Per shard: passes run, writes taken and duplicate rows held."""
    return {"passes": [e.post.metrics.passes for e in cluster.shards],
            "writes": [e._total_writes for e in cluster.shards],
            "rows": [len(e.store.duplicate_fingerprints()) for e in cluster.shards]}


def read_back(cluster, keys: np.ndarray, fps: np.ndarray) -> np.ndarray:
    """Per block key (``reference_post.block_keys``): the fingerprint of the
    live block its (disk, LBA) maps to in the store of the shard that owns
    ``fps`` (what the key should hold), 0 where it maps to no live block
    there."""
    owners = cluster.ring.shard_of_many(np.asarray(fps, dtype=np.uint64)).tolist()
    keys = np.asarray(keys, dtype=np.uint64)
    disks = (keys >> np.uint64(reference_post.LBA_BITS)).astype(np.int64)
    lbas = (keys & np.uint64((1 << reference_post.LBA_BITS) - 1)).astype(np.int64)
    stores = [e.store for e in cluster.shards]
    out = np.zeros(len(owners), dtype=np.uint64)
    for i, (s, d, lba) in enumerate(zip(owners, disks.tolist(), lbas.tolist())):
        st = stores[s]
        pba = st.lba_map.get((d, lba))
        fp = st.fp_of_pba.get(pba) if pba is not None else None
        if fp is not None:
            out[i] = fp
    return out


def run(cfg: dict, traffic: dict, seed: int, seconds: float, trace_dir: Optional[str], log,
        t_start: float) -> common.Outcome:
    from repro.serving.frontend import AsyncDedupFrontend

    clock = common.CompileClock()
    cluster = make_cluster(cfg)
    require_counters(cluster)
    t = time.perf_counter()
    aged = bw.aged_trace(cfg["tenants"], int(cfg["aged_distinct_fingerprints"]),
                         int(traffic["supply_writes"]), seed, float(cfg["requests_per_distinct"]),
                         tuple(cfg["overlap_range"]))
    aged_fps = aged.aged_fps
    t_gen = time.perf_counter() - t
    t = time.perf_counter()
    cluster.ingest_batched(aged.aged)
    t_ingest = time.perf_counter() - t
    aged_post = counters(cluster)
    log(f"set-up: generated {aged.aged.size} aged requests ({aged_fps.size} distinct "
        f"fingerprints) in {t_gen} s, ingested in {t_ingest} s with {aged_post['post_passes']} "
        f"post-processing passes ({aged_post['post_merges']} merges); index capacities "
        f"{common.index_shapes(cluster)}")

    f = cfg["frontend"]
    fe = AsyncDedupFrontend(cluster, max_batch=f["max_batch"], max_delay=f["max_delay_s"],
                            max_pending=f["max_pending"], admission_control=f["admission_control"])
    span = common.Span("write_batch", cluster.write_batch)
    cluster.write_batch = span  # the front end calls it on its engine thread
    base = counters(cluster, fe, [span])
    total = sum(s.size for s in aged.supply_fp)
    rec = {"t_call": np.zeros(total), "t_ack": np.zeros(total),
           "flag": np.zeros(total, dtype=bool), "fp": np.zeros(total, dtype=np.uint64),
           "batch": np.full(total, -1, dtype=np.int64)}
    prof = common.Profiler(trace_dir)
    snaps = {}

    # the window's edges are first acknowledgements of a batch: the engine
    # thread has applied that batch and holds no other, so the counters read
    # there cover whole batches
    def on_open(t0):
        snaps["open"] = counters(cluster, fe, [span])
        prof.open()

    def on_close(t1):
        prof.close()
        snaps["close"] = counters(cluster, fe, [span])

    window = common.Window(seconds, int(traffic["warmup_batches"]), on_open, on_close)
    disk_depths = bw.depths(cfg, aged.templates, int(traffic["in_flight"]))
    counted = _Counted(fe, len(disk_depths))
    n, errors = asyncio.run(bw._closed_loop(counted, aged.supply_lba, aged.supply_fp,
                                            disk_depths, window, rec))
    planes = prof.planes()
    peak = common.memory_peak()
    if errors:
        raise errors[0]
    if not window.closed:
        raise RuntimeError("the window never closed")
    t0, t1 = window.t0, window.t1
    log(f"window: {t1 - t0} s, compiles in window: {clock.between(t0, t1)}, index capacities "
        f"{common.index_shapes(cluster)}")

    # the front end is closed: each shard's periodic passes are all in
    period = int(cfg["cluster"]["postprocess_period_writes"])
    post = post_state(cluster)
    # references: every acknowledged write since the aged state, in call order
    after = counters(cluster, fe, [span])
    done = rec["t_ack"][:n] > 0
    fps, flags = rec["fp"][:n][done], rec["flag"][:n][done]
    d = common.delta(after, base)
    checks = reference.membership_checks(aged_fps, fps, flags, d["engine_writes"],
                                         d["engine_dups"], d["engine_hits"])
    # each disk's acknowledged writes, disk by disk (keys of two disks never meet)
    disks = np.repeat(np.arange(len(counted.issued)), counted.issued)
    w_lba = np.concatenate([lb[:k] for lb, k in zip(aged.supply_lba, counted.issued)])
    w_fp = np.concatenate([fp[:k] for fp, k in zip(aged.supply_fp, counted.issued)])
    aged_w = aged.aged[aged.aged["op"] == bw.OP_WRITE]
    all_keys = np.concatenate([reference_post.block_keys(aged_w["stream"], aged_w["lba"]),
                               reference_post.block_keys(disks, w_lba)])
    all_fps = np.concatenate([aged_w["fp"], w_fp])
    checks.append(reference_post.post_pass_gap(post["passes"], post["writes"], period))
    checks.append(reference_post.post_backlog_gap(post["rows"], post["writes"], post["passes"],
                                                  period))
    # the exact pass also applies the writes that still wait in a pending
    # duplicate run, so every acknowledged write is in the store after it
    t = time.perf_counter()
    cluster.run_postprocess(to_exact=True)
    live = sum(e.store.live_blocks for e in cluster.shards)
    log(f"exact pass after the window: {time.perf_counter() - t} s, {live} live blocks; "
        f"before it, per shard: {post}")
    uniq, content = reference_post.last_writes(all_keys, all_fps)
    checks.append(reference_post.readback_gap(all_keys, all_fps,
                                              read_back(cluster, uniq, content)))
    checks.append(reference_post.exact_gap(live, all_keys, all_fps))

    dup = reference.truly_duplicate(aged_fps, fps)
    t_ack, batch = rec["t_ack"][:n][done], rec["batch"][:n][done]
    in_win = (t_ack >= t0) & (t_ack < t1)
    lat = (t_ack - rec["t_call"][:n][done])[in_win]
    win = common.delta(snaps["close"], snaps["open"])
    # the batches whose engine work falls between the two counter readings
    applied = (batch > snaps["open"]["frontend_batches"]) & \
        (batch <= snaps["close"]["frontend_batches"])
    ref_dups = int(dup[applied].sum())
    log(f"window: batches {win['frontend_batches']}, duplicates {ref_dups}, removed inline "
        f"{win['engine_inline_dups']}, post-processing passes {win['post_passes']}, merges "
        f"{win['post_merges']}, blocks reclaimed {win['post_reclaimed']}, reverse-index keys "
        f"walked {win['reverse_keys']}")
    e2e = {
        "writes_per_s": float(in_win.sum()) / (t1 - t0),
        # duplicate writes the engines removed inline over those plain
        # membership finds in the same batches (HPDedup's Fig. 6 quantity)
        "inline_dedup_pct": 100.0 * win["engine_inline_dups"] / ref_dups,
        "setup_s": t0 - t_start,
    }
    ctx = {"window_s": t1 - t0, "counters": win,
           "writes": {"latency_s": lat, "duplicates": ref_dups,
                      "cache_hits": int(flags[applied].sum())}}
    return common.Outcome(e2e, ctx, checks, attempted=n, failed=int((~done).sum()),
                          device=common.device(peak), planes=planes,
                          info={"aged_fps": aged_fps, "fps": fps,
                                "live_fps": reference_post.distinct_live(all_keys, all_fps)})


def control(cfg: dict, info: dict, seed: int, bits: int = CONTROL_BITS) -> list:
    """The control: ``block_writes``' membership keyed by the low ``bits`` of
    each fingerprint, read as ``dup_count_gap``, and an exact phase keyed the
    same way, which collapses distinct contents that agree there, read as
    ``exact_gap``."""
    live = info["live_fps"]
    return bw.control(cfg, info, seed, bits) + [
        reference.Check("exact_gap", live.size - reference_post.truncated_distinct(live, bits),
                        0)]


def shrink(cfg: dict, traffic: dict) -> None:
    """Sizes a CPU test run can hold: ``block_writes``' sizes, and a period
    that keeps a pass every fourth batch per shard, as at the cell's size."""
    bw.shrink(cfg, traffic)
    cfg["cluster"]["postprocess_period_writes"] = 2048
