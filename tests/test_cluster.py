"""Sharded dedup cluster: differential tests against the single-engine oracle.

``ShardedCluster`` partitions the fingerprint space across N independent
engines by consistent hashing, so its *aggregate* dedup counts must match a
single monolithic engine on the same trace:

* ``total_writes`` / ``total_dup_writes`` — a fingerprint always routes to
  the same shard, so per-shard ground-truth seen-sets partition exactly;
* ``unique_fingerprints`` / ``final_disk_blocks`` (= bytes resident) — the
  shard-local exact phase leaves one block per live fingerprint partition;
* conservation — duplicates found inline + reclaimed by post-processing
  equal the trace's duplicate writes on both sides.

A 1-shard cluster must be *bit-exact* on the full ``HybridReport``, and the
cluster's batched columnar path must be bit-exact against the cluster's own
scalar path at every shard count (per-shard record sequences are identical,
so PR 1's batched-vs-scalar contract applies shard-wise).
"""

import numpy as np
import pytest

from repro.core import (
    DIODE,
    ConsistentHashRing,
    Engine,
    HPDedup,
    PurePostProcessing,
    ShardedCluster,
    aggregate_reports,
    generate_workload,
    make_idedup,
)
from repro.core.fingerprint import OP_WRITE, TRACE_DTYPE

SHARD_COUNTS = [1, 2, 4, 8]
TEMPLATES = ["mail", "ftp", "web", "home"]


def assert_aggregate_counts_match(cluster_rep, oracle_rep):
    """The differential contract for fingerprint-partitioned clusters."""
    assert cluster_rep.total_writes == oracle_rep.total_writes
    assert cluster_rep.total_dup_writes == oracle_rep.total_dup_writes
    assert cluster_rep.unique_fingerprints == oracle_rep.unique_fingerprints
    assert cluster_rep.final_disk_blocks == oracle_rep.final_disk_blocks
    # inline + post-process together find every duplicate write (exactness)
    assert (
        cluster_rep.inline.inline_dups + cluster_rep.post.blocks_reclaimed
        == cluster_rep.total_dup_writes
    )
    assert (
        oracle_rep.inline.inline_dups + oracle_rep.post.blocks_reclaimed
        == oracle_rep.total_dup_writes
    )


@pytest.fixture(scope="module")
def template_traces():
    return {
        tpl: generate_workload("A", total_requests=4_000, seed=11, mix={tpl: 3})[0]
        for tpl in TEMPLATES
    }


@pytest.fixture(scope="module")
def template_oracles(template_traces):
    out = {}
    for tpl, trace in template_traces.items():
        engine = HPDedup(cache_entries=512)
        engine.replay(trace)
        out[tpl] = engine.finish()
    return out


@pytest.mark.parametrize("tpl", TEMPLATES)
@pytest.mark.parametrize("num_shards", SHARD_COUNTS)
def test_differential_vs_single_engine_oracle(
    template_traces, template_oracles, tpl, num_shards
):
    """Every workload template x shard count: aggregate counts equal the
    scalar single-engine oracle; one shard is bit-exact end to end."""
    trace = template_traces[tpl]
    oracle_rep = template_oracles[tpl]
    cluster = ShardedCluster(num_shards=num_shards, cache_entries=512)
    cluster.replay_batched(trace, batch_size=512)
    rep = cluster.finish()
    cluster.check_consistency()
    assert_aggregate_counts_match(rep, oracle_rep)
    if num_shards == 1:
        assert rep == oracle_rep  # bit-exact on the full HybridReport


@pytest.mark.parametrize("num_shards", [2, 4])
def test_cluster_batched_matches_cluster_scalar(num_shards):
    """The cluster's columnar path is bit-exact vs its own scalar path:
    routing is record-identical, so each shard sees the same sequence and
    PR 1's batched contract applies per shard."""
    trace, _ = generate_workload("B", total_requests=8_000, seed=5)
    scalar = ShardedCluster(num_shards=num_shards, cache_entries=512)
    scalar.replay(trace)
    batched = ShardedCluster(num_shards=num_shards, cache_entries=512)
    batched.replay_batched(trace, batch_size=256)
    rs, rb = scalar.finish(), batched.finish()
    assert rs == rb
    for a, b in zip(scalar.shard_reports, batched.shard_reports):
        assert a == b


@pytest.mark.parametrize(
    "factory",
    [
        lambda i: make_idedup(cache_entries=256, seed=i),
        lambda i: DIODE(cache_entries=256, seed=i),
        lambda i: PurePostProcessing(),
    ],
    ids=["idedup", "diode", "postproc"],
)
def test_cluster_wraps_every_engine_type(factory):
    """Any Engine works as the shard engine; aggregate invariants hold."""
    trace, _ = generate_workload("B", total_requests=5_000, seed=2)
    oracle = factory(0)
    oracle.replay(trace)
    oracle_rep = oracle.finish()
    cluster = ShardedCluster(num_shards=4, engine_factory=factory)
    cluster.replay_batched(trace, batch_size=512)
    rep = cluster.finish()
    assert isinstance(cluster, Engine)
    assert rep.total_writes == oracle_rep.total_writes
    assert rep.total_dup_writes == oracle_rep.total_dup_writes
    assert rep.unique_fingerprints == oracle_rep.unique_fingerprints
    assert rep.final_disk_blocks == oracle_rep.final_disk_blocks


def test_cluster_accepts_custom_protocol_engine():
    """A shard engine only needs the Engine protocol: engines without a
    registered columnar driver fall back to their own write_batch/replay."""

    class WrappedEngine:
        """Protocol-conformant engine that is none of the built-in types."""

        def __init__(self, seed: int):
            self._inner = HPDedup(cache_entries=256, seed=seed)
            self.store = self._inner.store

        def write_batch(self, streams, lbas, fps):
            return self._inner.write_batch(streams, lbas, fps)

        def replay(self, trace):
            self._inner.replay(trace)
            return self

        def finish(self):
            return self._inner.finish()

    trace, _ = generate_workload("B", total_requests=4_000, seed=6)
    oracle = HPDedup(cache_entries=256)
    oracle.replay(trace)
    oracle_rep = oracle.finish()
    for replay_fn in ("replay", "replay_batched"):
        cluster = ShardedCluster(num_shards=2, engine_factory=WrappedEngine)
        getattr(cluster, replay_fn)(trace)
        rep = cluster.finish()
        assert rep.total_writes == oracle_rep.total_writes
        assert rep.total_dup_writes == oracle_rep.total_dup_writes
        assert rep.unique_fingerprints == oracle_rep.unique_fingerprints
        assert rep.final_disk_blocks == oracle_rep.final_disk_blocks


def test_write_batch_flags_match_single_engine():
    """With no cache pressure and threshold 1, inline decisions depend only
    on whether the fingerprint was seen — which fingerprint routing
    preserves — so per-record flags equal the single engine's, and the
    scatter/gather realignment is exercised end to end."""
    trace, _ = generate_workload("B", total_requests=6_000, seed=7)
    writes = trace[trace["op"] == OP_WRITE]
    single = make_idedup(cache_entries=1 << 20, threshold=1)
    cluster = ShardedCluster(
        num_shards=4, engine_factory=lambda i: make_idedup(cache_entries=1 << 20, threshold=1)
    )
    single_flags, cluster_flags = [], []
    for a in range(0, len(writes), 500):
        chunk = writes[a : a + 500]
        single_flags.extend(single.write_batch(chunk["stream"], chunk["lba"], chunk["fp"]).tolist())
        cluster_flags.extend(
            cluster.write_batch(chunk["stream"], chunk["lba"], chunk["fp"]).tolist()
        )
    assert single_flags == cluster_flags
    assert single.finish().total_dup_writes == cluster.finish().total_dup_writes


def test_reads_route_to_the_writing_shard():
    """The routing directory sends a read to the shard holding its key, so
    cluster reads resolve like single-engine reads."""
    n = 64
    recs = np.zeros(n, dtype=TRACE_DTYPE)
    recs["ts"] = np.arange(n)
    recs["op"] = np.where(np.arange(n) % 2 == 0, 0, 1)  # write, then read it
    recs["stream"] = 0
    recs["lba"] = np.arange(n) // 2
    recs["fp"] = np.arange(1, n + 1) * 7  # all-unique content
    recs["fp"][recs["op"] == 1] = 0
    cluster = ShardedCluster(num_shards=4, cache_entries=64)
    cluster.replay(recs)
    # every written key resolves on some shard (reads found their mapping)
    for lba in range(n // 2):
        hits = [e.store.read(0, lba) for e in cluster.shards]
        assert sum(h is not None for h in hits) == 1


def test_shard_local_cleanup_window():
    """CASStor-style idle reclamation: budgeted shard-local passes reclaim
    duplicate blocks without finishing the replay."""
    trace, _ = generate_workload("B", total_requests=6_000, seed=9)
    # tiny caches -> inline misses -> on-disk duplicates for cleanup to find
    cluster = ShardedCluster(num_shards=4, cache_entries=8)
    cluster.replay_batched(trace)
    dup_fps_before = sum(len(e.store.duplicate_fingerprints()) for e in cluster.shards)
    assert dup_fps_before > 0
    reclaimed = cluster.run_postprocess(max_merges_per_shard=5)
    assert reclaimed > 0
    assert cluster.reclaimed_blocks == reclaimed
    assert sum(e.post.metrics.merges for e in cluster.shards) <= 5 * 4
    # the budget is per window, not lifetime: a second window keeps merging
    reclaimed2 = cluster.run_postprocess(max_merges_per_shard=5)
    assert reclaimed2 > 0
    # a full window restores per-shard exactness
    cluster.run_postprocess(to_exact=True)
    for e in cluster.shards:
        assert e.store.duplicate_fingerprints() == []
    cluster.check_consistency()


def test_pba_namespaces_disjoint():
    trace, _ = generate_workload("B", total_requests=4_000, seed=1)
    cluster = ShardedCluster(num_shards=4, cache_entries=256)
    cluster.replay_batched(trace)
    cluster.finish()
    seen = {}
    for s, e in enumerate(cluster.shards):
        for pba in e.store.fp_of_pba:
            assert pba not in seen, f"PBA {pba} allocated by shards {seen[pba]} and {s}"
            seen[pba] = s


def test_ring_lookup_vectorized_matches_scalar_and_is_deterministic():
    ring = ConsistentHashRing(8, vnodes=32, seed=3)
    keys = np.random.default_rng(0).integers(0, 1 << 62, 2_000, dtype=np.uint64)
    vec = ring.shard_of_many(keys)
    assert [ring.shard_of(int(k)) for k in keys.tolist()] == vec.tolist()
    ring2 = ConsistentHashRing(8, vnodes=32, seed=3)
    np.testing.assert_array_equal(vec, ring2.shard_of_many(keys))
    assert set(np.unique(vec).tolist()) <= set(range(8))
    # every shard owns a share of a large keyspace
    assert len(np.unique(vec)) == 8


def test_ring_minimal_remap_on_grow():
    """Consistent hashing's defining property: growing N -> N+1 only moves
    keys onto the new shard; no key moves between surviving shards."""
    keys = np.random.default_rng(1).integers(0, 1 << 62, 5_000, dtype=np.uint64)
    before = ConsistentHashRing(4, vnodes=64, seed=0).shard_of_many(keys)
    after = ConsistentHashRing(5, vnodes=64, seed=0).shard_of_many(keys)
    moved = before != after
    assert bool((after[moved] == 4).all())
    # and a nontrivial-but-minority share moves (~1/5 in expectation)
    assert 0 < int(moved.sum()) < keys.size // 2


def test_aggregate_reports_identity_and_sum():
    trace, _ = generate_workload("B", total_requests=3_000, seed=4)
    engine = HPDedup(cache_entries=256)
    engine.replay(trace)
    rep = engine.finish()
    assert aggregate_reports([rep]) == rep
    double = aggregate_reports([rep, rep])
    assert double.total_writes == 2 * rep.total_writes
    assert double.inline.per_stream_writes == {
        s: 2 * v for s, v in rep.inline.per_stream_writes.items()
    }
