"""Elastic resharding: differential tests for ``ShardedCluster.resize``.

Contract (ISSUE 4): growing or shrinking the cluster mid-replay migrates
*only* the fingerprints the consistent-hash ring actually remaps (exactly
the ring-diff, asserted key for key), carries their cache entries, directory
rows and store mappings to the new owner, and leaves aggregate dedup counts
equal to the single-engine oracle at finish — with cross-shard duplicate
blocks reconciled by post-processing.
"""

import json

import numpy as np
import pytest

from repro.core import (
    ConsistentHashRing,
    HPDedup,
    ShardedCluster,
    generate_workload,
    restore_engine,
    snapshot_engine,
)

BATCH = 256


@pytest.fixture(scope="module")
def trace():
    return generate_workload("B", total_requests=8_000, seed=7)[0]


@pytest.fixture(scope="module")
def oracle_report(trace):
    oracle = HPDedup(cache_entries=512)
    oracle.replay(trace)
    return oracle.finish()


def assert_counts_match(rep, oracle_rep):
    assert rep.total_writes == oracle_rep.total_writes
    assert rep.total_dup_writes == oracle_rep.total_dup_writes
    assert rep.unique_fingerprints == oracle_rep.unique_fingerprints
    assert rep.final_disk_blocks == oracle_rep.final_disk_blocks
    # conservation: both dedup phases together find every duplicate write
    assert rep.inline.inline_dups + rep.post.blocks_reclaimed == rep.total_dup_writes


def seen_population(cluster):
    out = set()
    for engine in cluster.shards:
        out |= engine._seen_fps
    return out


@pytest.mark.parametrize(
    "n_from,n_to", [(2, 4), (4, 2), (1, 8), (8, 1), (8, 3), (3, 8)]
)
def test_resize_keeps_aggregate_counts_exact(trace, oracle_report, n_from, n_to):
    cluster = ShardedCluster(num_shards=n_from, cache_entries=512)
    cut = BATCH * n_from * 4
    cluster.ingest_batched(trace[:cut], BATCH)

    # predicted minimal remap: exactly the keys whose ring owner changes
    keys = np.asarray(sorted(seen_population(cluster)), dtype=np.uint64)
    before = cluster.ring.shard_of_many(keys)
    after = ConsistentHashRing(n_to, vnodes=64, seed=0).shard_of_many(keys)
    predicted_moves = int((before != after).sum())

    stats = cluster.resize(n_to)
    assert stats["moved_fps"] == predicted_moves  # minimal remap, key-exact
    assert stats["key_population"] == keys.size
    assert cluster.num_shards == n_to == len(cluster.shards)

    cluster.ingest_batched(trace[cut:], BATCH)
    rep = cluster.finish()
    cluster.check_consistency()  # incl. fingerprint-partition disjointness
    assert_counts_match(rep, oracle_report)


def test_resize_grow_only_moves_to_new_shards(trace):
    """Consistent hashing's defining property at the cluster level: growing
    N -> N+1 strands no key between surviving shards."""
    cluster = ShardedCluster(num_shards=4, cache_entries=512)
    cluster.ingest_batched(trace[: BATCH * 4 * 4], BATCH)
    keys = np.asarray(sorted(seen_population(cluster)), dtype=np.uint64)
    before = cluster.ring.shard_of_many(keys)
    cluster.resize(5)
    after = cluster.ring.shard_of_many(keys)
    moved = before != after
    assert bool((after[moved] == 4).all())
    assert 0 < int(moved.sum()) < keys.size // 2


# ring-imbalance tolerance over the theoretical minimal fraction: with 64
# vnodes per shard, per-shard ownership shares fluctuate a few percent
FRACTION_SLACK = 0.08


@pytest.mark.parametrize("n_from,n_to", [(2, 4), (4, 8), (8, 4), (4, 2), (1, 8)])
def test_resize_moved_fraction_within_minimal_bound(trace, n_from, n_to):
    """A mid-stream resize moves no larger a share of the key population
    than consistent hashing's minimal remap, ``(M - N) / M`` on a grow and
    ``(N - M) / N`` on a shrink, within the ring's imbalance."""
    cluster = ShardedCluster(num_shards=n_from, cache_entries=512)
    cut = (len(trace) // (2 * BATCH * n_from)) * BATCH * n_from
    cluster.ingest_batched(trace[:cut], BATCH)
    stats = cluster.resize(n_to)
    bound = (n_to - n_from) / n_to if n_to > n_from else (n_from - n_to) / n_from
    assert stats["key_population"] > 1_000
    assert 0 < stats["moved_fraction"] <= bound + FRACTION_SLACK


def test_resize_migrates_cache_entries_and_directory(trace):
    cluster = ShardedCluster(num_shards=2, cache_entries=4096)
    cluster.ingest_batched(trace[: BATCH * 2 * 6], BATCH)
    stats = cluster.resize(4)
    assert stats["moved_cache_entries"] > 0
    # no shard caches (or stores) fingerprints it does not own anymore
    for s, engine in enumerate(cluster.shards):
        cached = list(engine.inline.cache.owner)
        if cached:
            owners = cluster.ring.shard_of_many(np.asarray(cached, dtype=np.uint64))
            assert bool((owners == s).all())
    # directory rows point at each live key's owning shard
    for s, engine in enumerate(cluster.shards):
        for stream, lba in engine.store.lba_map:
            assert cluster._directory[(stream << 40) + lba] == s
    # reads still resolve after the move (routing directory migrated)
    hits = 0
    for s, engine in enumerate(cluster.shards):
        for (stream, lba), pba in list(engine.store.lba_map.items())[:50]:
            assert engine.store.read(stream, lba) == pba
            hits += 1
    assert hits > 0


def test_resize_shrink_retires_shards_without_losing_counters(trace, oracle_report):
    cluster = ShardedCluster(num_shards=8, cache_entries=512)
    cut = BATCH * 8 * 2
    cluster.ingest_batched(trace[:cut], BATCH)
    writes_before = sum(e._total_writes for e in cluster.shards)
    cluster.resize(2)
    assert len(cluster.shards) == 2
    assert len(cluster._retired_reports) == 6
    # retired shards are fully drained but their counters persist
    for r in cluster._retired_reports:
        assert r.final_disk_blocks == 0
    retired_writes = sum(r.total_writes for r in cluster._retired_reports)
    live_writes = sum(e._total_writes for e in cluster.shards)
    assert retired_writes + live_writes == writes_before
    cluster.ingest_batched(trace[cut:], BATCH)
    rep = cluster.finish()
    assert_counts_match(rep, oracle_report)


def test_resize_reconciles_cross_boundary_duplicates(trace):
    """A migrated fingerprint can arrive with several PBAs (inline misses on
    its old shard); reconcile=True merges them immediately, reconcile=False
    leaves them for the next idle pass."""
    # tiny caches force inline misses -> multi-PBA fingerprints to migrate
    cluster = ShardedCluster(num_shards=2, cache_entries=8)
    cluster.ingest_batched(trace[: BATCH * 2 * 8], BATCH)
    lazy = ShardedCluster(num_shards=2, cache_entries=8)
    lazy.ingest_batched(trace[: BATCH * 2 * 8], BATCH)

    stats = cluster.resize(4, reconcile=True)
    assert stats["reconciled_shards"]
    lazy_stats = lazy.resize(4, reconcile=False)
    assert lazy_stats["reconciled_shards"] == []
    assert sum(len(e.store.duplicate_fingerprints()) for e in lazy.shards) >= sum(
        len(e.store.duplicate_fingerprints()) for e in cluster.shards
    )
    # either way the exact phase at finish restores one block per fingerprint
    for c in (cluster, lazy):
        c.run_postprocess(to_exact=True)
        for e in c.shards:
            assert e.store.duplicate_fingerprints() == []
        c.check_consistency()


def test_shrink_then_grow_never_reuses_pba_namespaces(trace, oracle_report):
    """Shrink retires a shard slot whose live blocks migrate out with their
    PBAs intact; a later grow that recreated the slot's old PBA namespace
    would allocate colliding ids (clobbering ``fp_of_pba``/refcounts when
    those blocks migrate back).  Namespace slots must be lifetime-unique."""
    cluster = ShardedCluster(num_shards=4, cache_entries=512)
    cut1 = BATCH * 4 * 2
    cut2 = cut1 + BATCH * 2 * 2
    cluster.ingest_batched(trace[:cut1], BATCH)
    cluster.resize(2)
    cluster.ingest_batched(trace[cut1:cut2], BATCH)
    cluster.resize(4)
    # recreated slots 2 and 3 allocate from fresh namespaces, past every
    # slot the cluster has ever handed out
    assert cluster._next_namespace == 6
    for engine in cluster.shards[2:]:
        assert engine.store._next_pba >= 4 * cluster._pba_stride
    cluster.ingest_batched(trace[cut2:], BATCH)
    # global PBA uniqueness across all shard stores
    pbas = []
    for engine in cluster.shards:
        engine.store.flush_staged()
        pbas.extend(engine.store.fp_of_pba)
    assert len(pbas) == len(set(pbas))
    rep = cluster.finish()
    cluster.check_consistency()
    assert_counts_match(rep, oracle_report)


def test_shrink_grow_chain_with_snapshot_is_bit_exact(trace):
    """The namespace counter persists through snapshots: a restored cluster
    growing after a shrink must continue from fresh namespace slots, and the
    whole shrink -> snapshot -> restore -> grow chain stays bit-exact."""
    def run(crash: bool):
        cluster = ShardedCluster(num_shards=4, cache_entries=512)
        cut1 = BATCH * 4 * 2
        cluster.ingest_batched(trace[:cut1], BATCH)
        cluster.resize(2)
        if crash:
            payload = json.dumps(snapshot_engine(cluster))
            cluster = restore_engine(json.loads(payload))
            assert cluster._next_namespace == 4
        cut2 = cut1 + BATCH * 2 * 2
        cluster.ingest_batched(trace[cut1:cut2], BATCH)
        cluster.resize(4)
        cluster.ingest_batched(trace[cut2:], BATCH)
        return cluster.finish()

    assert run(crash=True) == run(crash=False)


def test_resize_then_snapshot_then_restore_chain(trace):
    """The PR's two tentpole halves compose: resize mid-replay, snapshot the
    resized cluster, crash, restore, finish — bit-exact against the same
    sequence without the crash."""
    def run(crash: bool):
        cluster = ShardedCluster(num_shards=2, cache_entries=512)
        cut1 = BATCH * 2 * 4
        cluster.ingest_batched(trace[:cut1], BATCH)
        cluster.resize(4)
        cut2 = cut1 + BATCH * 4 * 2
        cluster.ingest_batched(trace[cut1:cut2], BATCH)
        if crash:
            payload = json.dumps(snapshot_engine(cluster))
            cluster = restore_engine(json.loads(payload))
        cluster.ingest_batched(trace[cut2:], BATCH)
        return cluster.finish()

    assert run(crash=True) == run(crash=False)


def test_resize_validation_errors(trace):
    cluster = ShardedCluster(num_shards=2, cache_entries=64)
    with pytest.raises(ValueError, match=">= 1"):
        cluster.resize(0)
    # no-op resize moves nothing
    stats = cluster.resize(2)
    assert stats["moved_fps"] == 0 and stats["moved_blocks"] == 0


def test_resize_rejects_unsupported_engines_before_mutating(trace):
    """An engine without a ground-truth seen set fails validation *before*
    any migration: the cluster must not be left half-migrated."""

    class OpaqueEngine:
        def __init__(self, seed):
            self._inner = HPDedup(cache_entries=64, seed=seed)
            self.store = self._inner.store  # store visible, seen set not

        def write_batch(self, streams, lbas, fps):
            return self._inner.write_batch(streams, lbas, fps)

        def replay(self, t):
            self._inner.replay(t)
            return self

        def finish(self):
            return self._inner.finish()

    cluster = ShardedCluster(num_shards=2, engine_factory=OpaqueEngine)
    cluster.replay_batched(trace[: BATCH * 4], batch_size=BATCH)
    fps_before = [sorted(e.store.fp_table) for e in cluster.shards]
    with pytest.raises(TypeError, match="seen set"):
        cluster.resize(4)
    assert cluster.num_shards == 2 and len(cluster.shards) == 2
    assert [sorted(e.store.fp_table) for e in cluster.shards] == fps_before
