"""Fault-injection differential harness for snapshot/restore (ISSUE 4).

The crash model: replay a trace through the batched ingest path, stop at a
*randomized batch boundary*, serialize the whole engine to a state tree,
round-trip that tree through **JSON** (proving serializability — the live
object graph is dropped, exactly like a process crash after its last
checkpoint write), restore a fresh engine from the parsed bytes, and finish
the trace.  The final ``HybridReport`` must equal the uninterrupted run's
**bit for bit** — for every engine kind (HPDedup, iDedup, DIODE,
PurePostProcessing) and every shard count in {1, 2, 4, 8}.

That equality forces every piece of hidden state to survive: fingerprint
caches with exact LRU/LFU/ARC ordering, LDSS reservoirs *including their RNG
bit-generator state*, the prioritized cache's eviction RNG and Fenwick slot
layout, spatial-threshold histograms, pending duplicate runs, block-store
tables and the cluster routing directory.
"""

import json

import numpy as np
import pytest

from repro.core import (
    DIODE,
    HPDedup,
    PurePostProcessing,
    ShardedCluster,
    engine_finish_replay,
    engine_ingest,
    generate_workload,
    load_engine_state,
    make_idedup,
    restore_engine,
    snapshot_engine,
)

BATCH = 256
SHARD_COUNTS = [1, 2, 4, 8]

ENGINE_FACTORIES = {
    "hpdedup": lambda seed: HPDedup(cache_entries=256, seed=seed),
    "idedup": lambda seed: make_idedup(cache_entries=256, seed=seed),
    "diode": lambda seed: DIODE(cache_entries=256, seed=seed),
    "postproc": lambda seed: PurePostProcessing(),
}


@pytest.fixture(scope="module")
def trace():
    return generate_workload("B", total_requests=6_000, seed=13)[0]


def crash_restart_report(make_cluster, trace, chunk, cut_chunk):
    """Ingest -> snapshot at a batch boundary -> 'crash' -> restore from the
    JSON round trip -> finish.  Returns (report, restored_cluster)."""
    cut = chunk * cut_chunk
    live = make_cluster()
    live.ingest_batched(trace[:cut], BATCH)
    tree = snapshot_engine(live)
    payload = json.dumps(tree)  # serializability is part of the contract
    del live, tree  # the crash: nothing survives but the serialized bytes
    restored = restore_engine(json.loads(payload))
    restored.ingest_batched(trace[cut:], BATCH)
    return restored.finish(), restored


@pytest.mark.parametrize("kind", list(ENGINE_FACTORIES))
@pytest.mark.parametrize("num_shards", SHARD_COUNTS)
def test_crash_restore_is_bit_exact(trace, kind, num_shards):
    factory = ENGINE_FACTORIES[kind]

    def make_cluster():
        return ShardedCluster(num_shards=num_shards, engine_factory=factory)

    baseline = make_cluster()
    baseline.replay_batched(trace, batch_size=BATCH)
    expected = baseline.finish()

    chunk = BATCH * num_shards
    n_chunks = len(trace) // chunk
    # randomized (but reproducible) mid-replay batch boundary per combo
    rng = np.random.default_rng(abs(hash((kind, num_shards))) % (1 << 32))
    cut_chunk = int(rng.integers(1, n_chunks))
    report, restored = crash_restart_report(make_cluster, trace, chunk, cut_chunk)

    assert report == expected  # full HybridReport, field for field
    for a, b in zip(restored.shard_reports, baseline.shard_reports):
        assert a == b
    restored.check_consistency()


def test_single_engine_crash_restore_bit_exact(trace):
    """The engines also snapshot outside a cluster (the pipeline's 1-shard
    configuration embeds them directly)."""
    for kind, factory in ENGINE_FACTORIES.items():
        baseline = factory(0)
        baseline.replay_batched(trace, batch_size=BATCH)
        expected = baseline.finish()

        live = factory(0)
        engine_ingest(live, trace[: BATCH * 9], BATCH)
        payload = json.dumps(snapshot_engine(live))
        del live
        restored = restore_engine(json.loads(payload))
        engine_ingest(restored, trace[BATCH * 9 :], BATCH)
        engine_finish_replay(restored)
        assert restored.finish() == expected, kind


def test_snapshot_tree_is_stable_and_idempotent(trace):
    """snapshot -> restore -> snapshot reproduces the identical tree: the
    restore is lossless and the serializer is deterministic."""
    cluster = ShardedCluster(num_shards=2, cache_entries=128)
    cluster.ingest_batched(trace[: BATCH * 2 * 5], BATCH)
    tree = json.loads(json.dumps(snapshot_engine(cluster)))
    again = json.loads(json.dumps(snapshot_engine(restore_engine(tree))))
    assert again == tree


def test_snapshot_mid_pending_run_state(trace):
    """The randomized cuts usually leave pending duplicate runs open; pin it
    explicitly: a snapshot with non-empty pending state restores them."""
    engine = HPDedup(cache_entries=512, adaptive_threshold=False, fixed_threshold=4)
    engine.write(0, 0, 42)
    engine.write(0, 1, 43)
    engine.write(1, 0, 42)  # cache hit -> pending run on stream 1
    tree = snapshot_engine(engine)
    assert tree["state"]["inline"]["pending"]
    restored = restore_engine(json.loads(json.dumps(tree)))
    assert restored.inline._pending.keys() == engine.inline._pending.keys()
    assert restored.finish() == engine.finish()


def test_load_engine_state_preserves_identity_and_hooks(trace):
    """In-place restore keeps object identity, so process-local wiring
    (e.g. the serving layer's on_free reclaim hook) survives."""
    engine = HPDedup(cache_entries=128)
    engine_ingest(engine, trace[: BATCH * 4], BATCH)
    tree = json.loads(json.dumps(snapshot_engine(engine)))

    target = HPDedup(cache_entries=128)
    freed = []
    target.store.on_free = freed.append
    store_id, cache_id = id(target.store), id(target.inline.cache)
    load_engine_state(target, tree)
    assert id(target.store) == store_id and id(target.inline.cache) == cache_id
    assert target.store.on_free is not None
    engine_ingest(target, trace[BATCH * 4 :], BATCH)
    engine_finish_replay(target)

    ref = HPDedup(cache_entries=128)
    ref.replay_batched(trace, batch_size=BATCH)
    assert target.finish() == ref.finish()


def test_envelope_version_and_kind_guards():
    engine = HPDedup(cache_entries=16)
    tree = snapshot_engine(engine)
    future = dict(tree, version=tree["version"] + 1)
    with pytest.raises(ValueError, match="version"):
        restore_engine(future)
    # older trees are rejected too: version-1 snapshots lack state the
    # bit-exact guarantee needs (raw Fenwick nodes, namespace counter)
    stale = dict(tree, version=1)
    with pytest.raises(ValueError, match="version"):
        restore_engine(stale)
    with pytest.raises(ValueError, match="not a"):
        restore_engine({"bogus": True})
    with pytest.raises(ValueError, match="kind"):
        load_engine_state(PurePostProcessing(), tree)


def test_load_engine_state_rejects_mismatched_config():
    """An in-place load into a differently-parameterized engine restores
    state under the wrong live capacities/policies and silently diverges —
    it must be rejected loudly, like the version gate."""
    tree = json.loads(json.dumps(snapshot_engine(HPDedup(cache_entries=8192))))
    with pytest.raises(ValueError, match="config"):
        load_engine_state(HPDedup(cache_entries=1024), tree)
    diode_tree = json.loads(json.dumps(snapshot_engine(DIODE(cache_entries=256))))
    with pytest.raises(ValueError, match="config"):
        load_engine_state(DIODE(cache_entries=256, policy="lfu"), diode_tree)


def test_cluster_load_snapshot_shape_guard():
    cluster = ShardedCluster(num_shards=2, cache_entries=16)
    tree = snapshot_engine(cluster)
    other = ShardedCluster(num_shards=4, cache_entries=16)
    with pytest.raises(ValueError, match="shards"):
        load_engine_state(other, tree)
    # mismatched PBA stride must be rejected too: a grow on the loaded
    # cluster would compute namespace offsets that overlap the restored
    # shards' allocated ranges
    narrow = ShardedCluster(num_shards=2, cache_entries=16, pba_stride=1 << 20)
    with pytest.raises(ValueError, match="pba_stride"):
        load_engine_state(narrow, tree)


def test_cluster_snapshot_rejects_any_routing_but_fingerprint():
    """Fingerprint routing is the one rule: the snapshot keeps its
    ``routing`` key, and both the in-place load and the from-scratch
    restore refuse a tree that names any other value."""
    cluster = ShardedCluster(num_shards=2, cache_entries=16)
    tree = json.loads(json.dumps(snapshot_engine(cluster)))
    assert tree["state"]["config"]["routing"] == "fingerprint"
    tree["state"]["config"]["routing"] = "stream"
    before = json.dumps(snapshot_engine(cluster))
    with pytest.raises(ValueError, match="routing"):
        load_engine_state(cluster, tree)
    assert json.dumps(snapshot_engine(cluster)) == before  # untouched
    with pytest.raises(ValueError, match="routing"):
        restore_engine(tree)


def test_cluster_load_snapshot_rejects_without_mutating(trace):
    """A per-shard config mismatch must reject BEFORE any shard loads: a
    mid-loop failure would leave earlier shards on snapshot state and later
    ones live — a silently inconsistent mix if the caller catches the error
    and keeps going."""
    donor = ShardedCluster(num_shards=2, cache_entries=32)
    donor.ingest_batched(trace[: BATCH * 4], BATCH)
    tree = json.loads(json.dumps(snapshot_engine(donor)))

    target = ShardedCluster(num_shards=2, cache_entries=16)  # same ring params
    target.ingest_batched(trace[BATCH * 4 : BATCH * 8], BATCH)
    before = json.dumps(snapshot_engine(target))
    with pytest.raises(ValueError, match="config"):
        load_engine_state(target, tree)
    assert json.dumps(snapshot_engine(target)) == before  # untouched

    # a truncated shards list (corrupt/tampered file) passes the num_shards
    # config check but must still reject before any shard loads
    truncated = json.loads(json.dumps(snapshot_engine(donor)))
    truncated["state"]["shards"] = truncated["state"]["shards"][:1]
    matching = ShardedCluster(num_shards=2, cache_entries=32)
    matching.ingest_batched(trace[BATCH * 4 : BATCH * 8], BATCH)
    before = json.dumps(snapshot_engine(matching))
    with pytest.raises(ValueError, match="corrupt"):
        load_engine_state(matching, truncated)
    assert json.dumps(snapshot_engine(matching)) == before  # untouched
    # the from-scratch path must reject it too, not build a 2-shard cluster
    # with a 1-engine shards list
    with pytest.raises(ValueError, match="corrupt"):
        restore_engine(truncated)


def test_pipeline_crash_restore_continues_bit_exact():
    """Full-engine pipeline checkpoints: a fresh pipeline restored from a
    JSON-round-tripped state dict continues the *uninterrupted* run's batch
    stream bit-exactly — with NO pre-replay (the old estimator-only
    checkpoints needed the restoring pipeline to re-ingest the prefix; the
    engine state tree makes cold restores exact)."""
    from repro.data.pipeline import DedupIngestPipeline, TenantSpec

    def mk(num_shards):
        return DedupIngestPipeline(
            [TenantSpec(0, dup_ratio=0.6), TenantSpec(1, dup_ratio=0.2)],
            block_tokens=16,
            vocab=500,
            cache_entries=256,
            fingerprint_batch=8,
            num_shards=num_shards,
            seed=5,
        )

    for num_shards in (1, 4):
        ref = mk(num_shards)
        it_ref = ref.batches(2, 32)
        for _ in range(5):
            next(it_ref)
        expected = [next(it_ref) for _ in range(3)]  # uninterrupted batches 6-8

        live = mk(num_shards)
        it_live = live.batches(2, 32)
        for _ in range(5):
            next(it_live)
        payload = json.dumps(live.state_dict())  # checkpoints are serializable
        del live, it_live  # the crash

        cold = mk(num_shards)
        cold.load_state(json.loads(payload))
        it_cold = cold.batches(2, 32)
        for exp in expected:
            got = next(it_cold)
            np.testing.assert_array_equal(exp["inputs"], got["inputs"])
            np.testing.assert_array_equal(exp["targets"], got["targets"])
        assert cold.metrics.blocks_in == ref.metrics.blocks_in
        assert cold.metrics.blocks_deduped_inline == ref.metrics.blocks_deduped_inline


def test_pipeline_periodic_snapshots_flow():
    """``snapshot_every_blocks`` keeps ``last_snapshot`` fresh during ingest
    and the snapshot loads into a cold pipeline."""
    from repro.data.pipeline import DedupIngestPipeline, TenantSpec

    def mk():
        return DedupIngestPipeline(
            [TenantSpec(0, dup_ratio=0.5)],
            block_tokens=16,
            vocab=300,
            cache_entries=128,
            fingerprint_batch=8,
            snapshot_every_blocks=16,
            seed=2,
        )

    pipe = mk()
    it = pipe.batches(2, 32)
    while pipe.last_snapshot is None:
        next(it)
    first_at = pipe.last_snapshot["metrics"]["blocks_in"]
    for _ in range(6):
        next(it)
    assert pipe.last_snapshot["metrics"]["blocks_in"] > first_at  # refreshed
    cold = mk()
    cold.load_state(pipe.last_snapshot)
    next(cold.batches(2, 32))  # resumes without error
    assert cold.metrics.blocks_in > first_at


def test_serving_snapshot_resumes_bit_exact():
    """Crash-restore the KV-dedup server: the restored server's dedup engine
    and page table continue exactly (same prefill hits, same metrics)."""
    import jax

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving.dedup_kv import DedupKVServer

    cfg = get_config("tinyllama-1.1b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def mk():
        return DedupKVServer(model, params, page_tokens=16, max_slots=128,
                             cache_entries=128, num_shards=2)

    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, 48)
    requests = [np.concatenate([prompt, rng.integers(0, cfg.vocab_size, 8)]) for _ in range(6)]

    s1 = mk()
    for toks in requests[:3]:
        s1.prefill_request(0, toks)
    snap = s1.snapshot()
    for toks in requests[3:]:
        s1.prefill_request(0, toks)

    s2 = mk()
    s2.load_state(snap)
    for toks in requests[3:]:
        s2.prefill_request(0, toks)
    assert s2.metrics == s1.metrics
    assert json.dumps(snapshot_engine(s2.dedup)) == json.dumps(snapshot_engine(s1.dedup))
    # reclaim hooks were re-attached: a post pass still drops merged pages
    s2.run_postprocess()
