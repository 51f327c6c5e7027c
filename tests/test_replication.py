"""Replication-aware cluster: R-way placement, read failover, shard-loss
recovery (ISSUE 9).

The contract under test (core/cluster.py, replication overlay): writes place
R copies of each fingerprint's content on distinct *physical* ring
successors, every routed record is appended to a roll-forward oplog on the
primary's live successors, reads against a failed primary are served from
the surviving mirrors, and ``fail_shard``/``recover_shard`` rebuild a dead
shard **bit-exactly** — the recovered cluster's aggregate ``HybridReport``
and live-block digests equal the uninterrupted oracle's, at every tested
R x shard-count point, under both the serial and the parallel executor.
The recovery sweep also covers the satellite bugfixes: a poisoned worker
lane must leave the cluster cleanly stoppable/restartable, and the failure
path must compose with online GC's deferred reclaim.
"""

from __future__ import annotations

import json
import warnings

import numpy as np
import pytest

from repro.core import ShardedCluster, ShardWorkerError, generate_workload
from repro.core.fingerprint import OP_WRITE

REPLICATION = [2, 3]
SHARD_COUNTS = [2, 4, 8]


def _trace(total=6_000, seed=5, workload="A"):
    return generate_workload(workload, total_requests=total, seed=seed)[0]


def _overwrite_trace(total=4_000, seed=13):
    base = _trace(total, seed)
    over = base.copy()
    over["ts"] = over["ts"] + int(base["ts"].max()) + 1
    over["fp"] = over["fp"] ^ np.uint64(0x9E3779B97F4A7C15)
    both = np.concatenate([base, over])
    both.sort(order="ts", kind="stable")
    return both


def _cluster(num_shards, replication_factor=1):
    return ShardedCluster(
        num_shards=num_shards,
        cache_entries=512,
        routing="fingerprint",
        replication_factor=replication_factor,
    )


def _live_digest(cluster):
    """PBA-value-independent digest of every live (stream, lba) -> fp."""
    out = []
    for engine in cluster.shards:
        store = engine.store
        out.append(sorted((k, int(store.fp_of_pba[p])) for k, p in store.lba_map.items()))
    return out


# ---------------------------------------------------------------------------
# tentpole: kill mid-parallel-replay -> recover -> bit-exact vs the oracle
# ---------------------------------------------------------------------------


@pytest.mark.filterwarnings("ignore::RuntimeWarning")  # R=3 x 2 shards clamps
@pytest.mark.parametrize("num_shards", SHARD_COUNTS)
@pytest.mark.parametrize("factor", REPLICATION)
def test_kill_mid_parallel_replay_recover_bit_exact(num_shards, factor):
    trace = _overwrite_trace()
    half = len(trace) // 2
    victim = num_shards - 1

    oracle = _cluster(num_shards, factor)
    oracle.replay_batched(trace[:half], batch_size=256, parallel=True)
    oracle.replay_batched(trace[half:], batch_size=256, parallel=True)
    expected = oracle.finish()

    c = _cluster(num_shards, factor)
    c.replay_batched(trace[:half], batch_size=256, parallel=True)
    c.fail_shard(victim)
    # traffic keeps flowing while the shard is down
    c.replay_batched(trace[half:], batch_size=256, parallel=True)
    stats = c.recover_shard(victim)
    assert stats["replayed"] > 0
    got = c.finish()

    assert got == expected
    assert _live_digest(c) == _live_digest(oracle)
    assert c.replica_blocks == oracle.replica_blocks


@pytest.mark.parametrize("parallel", [False, True], ids=["serial", "parallel"])
def test_served_writes_while_a_shard_is_down_recover_bit_exact(parallel):
    """``write_batch`` with shard 1 of 4 failed: records routed to it read
    False and are logged, the other shards' flags land in call order as a
    healthy cluster's do, and recovery rolls the logged served writes
    forward to the oracle's report and live blocks."""
    trace = _trace(8_000, seed=17)
    writes = trace[trace["op"] == OP_WRITE]
    victim, step = 1, 1_024
    chunks = [writes[lo : lo + step] for lo in range(0, len(writes), step)]
    half = len(chunks) // 2

    def serve(c, part):
        return np.concatenate([c.write_batch(w["stream"], w["lba"], w["fp"]) for w in part])

    oracle = _cluster(4, 2)
    c = _cluster(4, 2)
    if parallel:
        c.min_parallel_batch = 0
        c.start_executor()
    try:
        assert np.array_equal(serve(c, chunks[:half]), serve(oracle, chunks[:half]))
        c.fail_shard(victim)
        got, want = serve(c, chunks[half:]), serve(oracle, chunks[half:])
        rest = np.concatenate(chunks[half:])
        down = c.ring.shard_of_many(rest["fp"]) == victim
        assert down.any() and not got[down].any()
        assert want[~down].any() and np.array_equal(got[~down], want[~down])
        assert c.recover_shard(victim)["replayed"] > 0
        assert c.finish() == oracle.finish()
    finally:
        c.stop_executor()
    assert _live_digest(c) == _live_digest(oracle)


def test_r1_oracle_equals_unreplicated_cluster():
    """R == 1 is the identity overlay: reports equal the plain cluster's."""
    trace = _trace()
    plain = _cluster(4).replay_batched(trace, batch_size=256).finish()
    r1 = _cluster(4, 1).replay_batched(trace, batch_size=256).finish()
    assert plain == r1


def test_replication_decision_neutral():
    """R never changes dedup decisions: reports are identical across R."""
    trace = _overwrite_trace()
    reports = [
        _cluster(4, factor).replay_batched(trace, batch_size=256).finish()
        for factor in (1, 2, 3)
    ]
    assert reports[0] == reports[1] == reports[2]


def test_replica_copies_track_live_content():
    """At a finished barrier the mirrors hold exactly (R_eff - 1) copies of
    every live fingerprint — the FASTEN storage-overhead denominator."""
    for factor in (2, 3):
        c = _cluster(4, factor)
        rep = c.replay_batched(_overwrite_trace(), batch_size=256).finish()
        assert c.replica_blocks == (factor - 1) * rep.final_disk_blocks


# ---------------------------------------------------------------------------
# failure-mode traffic: failover reads, writes while down, R=1 hard stop
# ---------------------------------------------------------------------------


def test_read_failover_counters():
    trace = _trace(4_000, seed=11)
    c = _cluster(4, 2)
    c.replay_batched(trace, batch_size=256)
    c.fail_shard(1)
    c.ingest_batched(trace)  # re-reads routed to shard 1 must fail over
    assert c.failover_reads > 0
    # every re-read key's content has a surviving mirror at R=2
    assert c.failover_misses == 0


def test_r1_shard_loss_is_unrecoverable():
    c = _cluster(2, 1)
    c.ingest_batched(_trace(2_000))
    c.fail_shard(1)
    with pytest.raises(RuntimeError, match="unrecoverable"):
        c.recover_shard(1)


def test_failed_shard_blocks_finish_and_snapshot():
    c = _cluster(2, 2)
    c.ingest_batched(_trace(2_000))
    c.fail_shard(0)
    with pytest.raises(RuntimeError, match="recover_shard"):
        c.finish()
    with pytest.raises(RuntimeError, match="recover_shard"):
        c.snapshot()
    c.recover_shard(0)
    c.finish()


def test_fail_shard_rejects_bad_args():
    c = _cluster(2, 2)
    with pytest.raises(IndexError):
        c.fail_shard(5)
    c.fail_shard(1)
    with pytest.raises(ValueError):
        c.fail_shard(1)
    with pytest.raises(ValueError):
        c.recover_shard(0)  # not failed


# ---------------------------------------------------------------------------
# composition: snapshot/restore, resize, unmap fan-out, online GC
# ---------------------------------------------------------------------------


def test_snapshot_roundtrip_preserves_replication_state():
    trace = _trace()
    half = len(trace) // 2
    c = _cluster(4, 2)
    c.ingest_batched(trace[:half], batch_size=256)
    snap = json.loads(json.dumps(c.snapshot()))
    restored = ShardedCluster.restore(snap)
    assert restored.replication_factor == 2
    c.ingest_batched(trace[half:], batch_size=256)
    restored.ingest_batched(trace[half:], batch_size=256)
    # the restored cluster can still lose and recover a shard: ckpt + oplog
    # survived serialization
    restored.fail_shard(0)
    restored.recover_shard(0)
    assert restored.finish() == c.finish()
    assert _live_digest(restored) == _live_digest(c)


def test_pre_replication_snapshot_loads_as_r1():
    """Snapshots written before the replication overlay carry no subtree;
    they must load as plain R == 1 clusters."""
    c = _cluster(2, 1)
    c.ingest_batched(_trace(2_000))
    snap = json.loads(json.dumps(c.snapshot()))
    assert snap["replication"] is None
    snap.pop("replication")
    restored = ShardedCluster.restore(snap)
    assert restored.replication_factor == 1


def test_resize_rebuilds_mirrors_on_new_ring():
    trace = _trace()
    half = len(trace) // 2
    c = _cluster(2, 2)
    c.ingest_batched(trace[:half], batch_size=256)
    c.resize(4)
    c.ingest_batched(trace[half:], batch_size=256)
    # post-resize failure recovers against the resized ring + fresh ckpt
    c.fail_shard(2)
    c.recover_shard(2)
    rep = c.finish()
    assert c.replica_blocks == rep.final_disk_blocks  # (R_eff-1) == 1


def test_resize_refuses_with_failed_shard():
    c = _cluster(4, 2)
    c.ingest_batched(_trace(2_000))
    c.fail_shard(1)
    with pytest.raises(RuntimeError, match="recover_shard"):
        c.resize(2)


def test_unmap_fans_out_to_replicas():
    c = _cluster(4, 2)
    c.replay_batched(_trace(4_000), batch_size=256)
    before = c.replica_blocks
    packed = next(iter(c._rep_keys))
    stream, lba = packed >> 40, packed & ((1 << 40) - 1)
    assert c.unmap(stream, lba) is not None
    assert packed not in c._rep_keys
    assert c.replica_blocks <= before  # eager fan-out (equal iff fp shared)
    # an unmap during a failure window rolls forward at recovery
    c2 = _cluster(4, 2)
    c2.replay_batched(_trace(4_000), batch_size=256)
    key2 = next(k for k, v in c2._directory.items() if v == 1)
    c2.fail_shard(1)
    c2.unmap(key2 >> 40, key2 & ((1 << 40) - 1))
    c2.recover_shard(1)
    assert (key2 >> 40, key2 & ((1 << 40) - 1)) not in c2.shards[1].store.lba_map


def test_recovery_composes_with_online_gc():
    """Shard loss while online GC has armed deferred reclaim: replica-side
    grace periods hold frees in limbo, recovery still lands bit-exact."""
    trace = _overwrite_trace()
    half = len(trace) // 2

    oracle = _cluster(4, 2)
    oracle.ingest_batched(trace[:half], batch_size=256)
    oracle.run_gc()
    oracle.ingest_batched(trace[half:], batch_size=256)
    expected = oracle.finish()

    c = _cluster(4, 2)
    c.ingest_batched(trace[:half], batch_size=256)
    c.run_gc()  # wait=True barrier: checkpoints refresh here
    c.fail_shard(3)
    c.ingest_batched(trace[half:], batch_size=256)
    c.run_gc()  # GC with a failed shard skips the dead lane
    oracle2 = _cluster(4, 2)  # oracle for the second GC barrier
    oracle2.ingest_batched(trace[:half], batch_size=256)
    oracle2.run_gc()
    oracle2.ingest_batched(trace[half:], batch_size=256)
    c.recover_shard(3)
    got = c.finish()
    assert got == expected
    assert _live_digest(c) == _live_digest(oracle)


def test_checkpoint_truncates_oplogs():
    c = _cluster(4, 2)
    c.ingest_batched(_trace(4_000), batch_size=256)
    assert sum(c._since_ckpt) > 0
    c.checkpoint()
    assert c._since_ckpt == [0] * 4
    assert all(not rs.oplog for rs in c._replicas if rs is not None)
    # recovery right after a checkpoint replays nothing but is exact
    c.fail_shard(0)
    assert c.recover_shard(0)["replayed"] == 0


# ---------------------------------------------------------------------------
# satellite 1 regression: injected worker fault -> clean stop/restart
# ---------------------------------------------------------------------------


def test_worker_fault_cluster_cleanly_restartable():
    """A sticky ``ShardWorkerError`` used to survive ``stop_executor()`` /
    ``start_executor()``: teardown re-raised, and a fresh executor was
    poisoned by nothing at all while the coordinator state was undefined.
    Now: the fault surfaces once at an engine call, ``stop_executor()``
    never raises, the cluster reports the poisoned lane with a clear
    recovery hint, and fail/recover restores bit-exactness."""
    trace = _trace()
    third = len(trace) // 3
    c = _cluster(4, 2)
    c.min_parallel_batch = 1  # force the true worker path, no coalescing
    ex = c.start_executor()
    c.ingest_batched(trace[:third], parallel=True, batch_size=256)

    def boom():
        raise ValueError("injected lane fault")

    ex.submit(2, boom)
    # the faulted call still routes + logs every record; healthy lanes
    # execute theirs, the poisoned lane's land in the oplog, and the fault
    # surfaces at the call-end barrier
    with pytest.raises(ShardWorkerError):
        c.ingest_batched(trace[third : 2 * third], parallel=True, batch_size=256)

    c.stop_executor()  # regression: used to re-raise the sticky error
    c.start_executor()
    # restarted but still poisoned: engine state on lane 2 is undefined and
    # every entry point says so (no silent half-applied batches)
    with pytest.raises(ShardWorkerError, match="recover"):
        c.ingest_batched(trace[2 * third :], parallel=True, batch_size=256)

    c.fail_shard(2)  # absorbs the poison; shard 2 transitions to failed
    c.recover_shard(2)  # rolls the poisoned lane's oplog forward
    c.ingest_batched(trace[2 * third :], parallel=True, batch_size=256)
    got = c.finish()
    c.stop_executor()

    oracle = _cluster(4, 2)
    oracle.ingest_batched(trace[:third], batch_size=256)
    oracle.ingest_batched(trace[third : 2 * third], batch_size=256)
    oracle.ingest_batched(trace[2 * third :], batch_size=256)
    assert got == oracle.finish()


def test_worker_fault_snapshot_reload_also_heals():
    """The documented alternative recovery path: reload a known-good
    snapshot in place; poisoned lanes are healed by the reload."""
    trace = _trace(4_000, seed=3)
    half = len(trace) // 2
    c = _cluster(2, 2)
    c.ingest_batched(trace[:half], batch_size=256)
    snap = json.loads(json.dumps(c.snapshot()))
    ex = c.start_executor()

    def boom():
        raise ValueError("injected")

    ex.submit(0, boom)
    with pytest.raises(ShardWorkerError):
        c.ingest_batched(trace[half:], parallel=True, batch_size=256)
    c.load_snapshot(snap)
    c.ingest_batched(trace[half:], parallel=True, batch_size=256)
    got = c.finish()
    c.stop_executor()

    oracle = _cluster(2, 2)
    oracle.ingest_batched(trace[:half], batch_size=256)
    oracle.ingest_batched(trace[half:], batch_size=256)
    assert got == oracle.finish()


# ---------------------------------------------------------------------------
# graceful degradation: R > live shards clamps loudly, never silently
# ---------------------------------------------------------------------------


def test_replication_clamp_warns():
    with pytest.warns(RuntimeWarning, match="exceeds"):
        c = ShardedCluster(
            num_shards=2, cache_entries=512, routing="fingerprint", replication_factor=4
        )
    assert c.effective_replication == 2
    rep = c.replay_batched(_trace(2_000), batch_size=256).finish()
    assert c.replica_blocks == rep.final_disk_blocks  # one mirror copy, not 3


def test_replication_requires_fingerprint_routing():
    """Fingerprint routing is the only rule: construction rejects any other
    value, replicated or not."""
    for factor in (1, 2):
        for routing in ("stream", "lba", ""):
            with pytest.raises(ValueError, match="fingerprint"):
                ShardedCluster(
                    num_shards=2, cache_entries=512, routing=routing,
                    replication_factor=factor,
                )


def test_grow_unclamps_replication():
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        c = ShardedCluster(
            num_shards=2, cache_entries=512, routing="fingerprint", replication_factor=3
        )
    trace = _trace(3_000, seed=9)
    c.ingest_batched(trace, batch_size=256)
    assert c.effective_replication == 2
    c.resize(4)
    assert c.effective_replication == 3
    rep = c.finish()
    # after the resize resync the mirrors carry R_eff-1 = 2 copies per fp
    assert c.replica_blocks == 2 * rep.final_disk_blocks
