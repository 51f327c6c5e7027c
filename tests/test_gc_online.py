"""Online GC differential harness + epoch/compaction unit coverage.

The contract (core/gc.py, ARCHITECTURE.md "Online reclaim + compaction"):
GC-under-live-ingest, quiesced-GC, and no-GC runs of the same trace
converge to **identical live-block sets** (PBA-value-independent digests —
compaction renames PBAs on purpose) and **bit-exact aggregate
``HybridReport``s**, at shard counts {1, 2, 4, 8}, across snapshot/restore
taken mid-GC (limbo non-empty) and across a ``resize()`` whose quiesce
point force-drains orphaned blocks.  The store-level epoch protocol
(pin -> free parks in limbo -> drain reclaims) is covered deterministically
here rather than by racing threads.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from repro.core import (
    BlockStore,
    HPDedup,
    ShardedCluster,
    generate_workload,
    restore_engine,
    snapshot_engine,
)
from conftest import feed_cluster

SHARD_COUNTS = [1, 2, 4, 8]


def _overwrite_trace(total=3_000, seed=13, workload="A"):
    """A trace whose second half overwrites the first half's keys with new
    content — every original block's refcount hits zero, feeding the GC."""
    base = generate_workload(workload, total_requests=total, seed=seed)[0]
    over = base.copy()
    over["ts"] = over["ts"] + int(base["ts"].max()) + 1
    over["fp"] = over["fp"] ^ np.uint64(0x9E3779B97F4A7C15)
    both = np.concatenate([base, over])
    both.sort(order="ts", kind="stable")
    return both


def _cluster(num_shards, **kw):
    kw.setdefault("cache_entries", 512)
    return ShardedCluster(num_shards=num_shards, **kw)


def _live_digest(cluster):
    """PBA-value-independent view of live content: every key's fingerprint,
    plus how many physical blocks back each fingerprint (inline misses)."""
    keys = sorted(
        (k[0], k[1], e.store.fp_of_pba[p])
        for e in cluster.shards
        for k, p in e.store.lba_map.items()
    )
    copies = sorted(
        (fp, len(pbas)) for e in cluster.shards for fp, pbas in e.store.fp_table.items()
    )
    return keys, copies


# ---------------------------------------------------------------------------
# Store-level epoch protocol (deterministic, no threads).
# ---------------------------------------------------------------------------


def test_epoch_pin_parks_free_in_limbo_until_drain():
    store = BlockStore()
    store.deferred_reclaim = True
    events = []
    store.on_free = lambda pba: events.append((pba, store.freed_blocks))
    p1 = store.write_new_block(0, 1, 0xF1)
    tag = store.pin_epoch()  # a write is in flight
    store.unmap(0, 1)  # refcount 0: logical free NOW...
    assert store.live_blocks == 0
    assert not store.has_fp(0xF1)  # fingerprint purged immediately
    assert store.freed_blocks == 0 and events == []  # ...physical reclaim deferred
    assert store._limbo == [(0, p1)]
    store.advance_epoch()
    assert store.collect_limbo() == 0  # epoch 0 still pinned
    store.unpin_epoch(tag)
    assert store.collect_limbo() == 1  # grace period drained
    assert events == [(p1, 1)]  # hook fires at reclaim, after the counter
    assert store._free_pbas == [p1]
    store.check_consistency()


def test_collect_limbo_force_ignores_pins():
    store = BlockStore()
    store.deferred_reclaim = True
    store.write_new_block(0, 1, 0xF1)
    store.pin_epoch()
    store.unmap(0, 1)
    assert store.collect_limbo() == 0
    assert store.collect_limbo(force=True) == 1  # full barrier: caller's call
    assert store.freed_blocks == 1


def test_no_pins_means_immediate_reclaim_even_when_deferred():
    store = BlockStore()
    store.deferred_reclaim = True
    store.write_new_block(0, 1, 0xF1)
    store.unmap(0, 1)
    assert store.freed_blocks == 1 and store._limbo == []


# ---------------------------------------------------------------------------
# Compaction unit coverage.
# ---------------------------------------------------------------------------


def test_compact_closes_holes_and_trims_tail():
    store = BlockStore()
    pbas = [store.write_new_block(0, i, 0xA0 + i) for i in range(6)]
    store.unmap(0, 1)  # hole at 1
    store.unmap(0, 3)  # hole at 3
    store.unmap(0, 5)  # trailing hole at 5
    moves = []
    store.on_relocate = lambda old, new: moves.append((old, new))
    relocs = store.compact()
    # block 4 (highest live) fills hole 1; holes {3, 4, 5} then trail off
    assert relocs == {pbas[4]: pbas[1]} and moves == [(pbas[4], pbas[1])]
    assert store._next_pba == 3 and store._free_pbas == []
    assert store.relocated_blocks == 1
    assert store.lba_map[(0, 4)] == pbas[1]  # LBA followed the block
    store.check_consistency()
    # the freed tail is genuinely reusable
    assert store.write_new_block(0, 9, 0xFF) == 3


def test_compact_budget_and_canonical_order():
    store = BlockStore()
    store.write_new_block(0, 0, 0xA)
    store.write_new_block(0, 1, 0xB)
    store.write_new_block(0, 2, 0xB)  # duplicate row: [1, 2]
    store.write_new_block(0, 3, 0xB)  # row [1, 2, 3]
    store.unmap(0, 0)  # hole at 0
    store.unmap(0, 1)  # hole at 1 — 0xB's canonical PBA dies, row [2, 3]
    assert store.compact(max_moves=1) == {3: 0}
    # in-place row update preserves canonical (positional) order: [2, 0]
    assert store.fp_table[0xB] == [2, 0]
    assert store.lookup_fp(0xB) == 2
    store.check_consistency()
    assert store.compact() == {2: 1}  # second call finishes the job
    assert store.fp_table[0xB] == [1, 0]
    store.check_consistency()


def test_compact_requires_flushed_staged_writes():
    store = BlockStore()
    store.write_new_block(0, 0, 0xA)
    store.write_new_block(0, 1, 0xB)
    store.unmap(0, 0)
    store.stage_new_block(0, 2, 0xC)
    with pytest.raises(AssertionError):
        store.compact()
    store.flush_staged()
    assert store.compact() == {2: 0}


def test_gc_never_resurrects_stale_cache_pair_on_reused_slot():
    """The resurrect-pin: compaction refills a freed slot with *matching*
    content; a cache pair still referencing the slot must stay stale (a
    no-GC run never reuses slots, so its pair stays stale forever)."""
    eng = HPDedup(cache_entries=64, adaptive_threshold=False, fixed_threshold=1)
    eng.write(0, 0, 0xAA)  # pba 0, cached (0xAA -> 0)
    eng.write(0, 1, 0xBB)  # pba 1
    eng.write(0, 0, 0xCC)  # overwrite: pba 0 freed; cache pair (0xAA -> 0) now stale
    eng.inline.flush()
    stats = eng.run_gc()  # compaction: block 2 (0xCC)... holes [0]
    assert stats["moved"] == 1
    # whatever now lives at slot 0, a fresh write of 0xAA must NOT dedup
    # against the stale pair — it allocates a new block, like no-GC would
    before = eng.inline.metrics.inline_dups
    eng.write(0, 5, 0xAA)
    eng.inline.flush()
    assert eng.inline.metrics.inline_dups == before
    eng.store.check_consistency()
    assert eng.store.fp_of_pba[eng.store.read(0, 5)] == 0xAA


# ---------------------------------------------------------------------------
# The differential harness: no-GC vs quiesced-GC vs GC-under-live-ingest.
# ---------------------------------------------------------------------------


def _run_no_gc(trace, num_shards):
    c = _cluster(num_shards)
    c.ingest_batched(trace, batch_size=256)
    rep = c.finish()
    return c, rep


def _run_quiesced_gc(trace, num_shards):
    """GC only at quiet points: serial ingest in slices, full GC between."""
    c = _cluster(num_shards)
    n = len(trace)
    for lo in range(0, n, n // 3 + 1):
        c.ingest_batched(trace[lo : lo + n // 3 + 1], batch_size=256)
        c.run_gc(max_moves_per_shard=64)
    c.run_gc()
    rep = c.finish()
    return c, rep


def _run_gc_under_load(trace, num_shards):
    """GC steps queued on the shard worker lanes between in-flight chunks —
    no quiesce: the epoch pins of queued chunks gate physical reclaim."""
    c = _cluster(num_shards)
    c.min_parallel_batch = 0  # force the worker path even for tiny chunks
    c.start_executor()
    try:
        c.ingest_batched(
            trace,
            batch_size=256,
            parallel=True,
            on_chunk=lambda i: c.run_gc(wait=False) if i % 2 == 1 else None,
        )
        c.run_gc(wait=True)
        rep = c.finish()
    finally:
        c.stop_executor()
    return c, rep


@pytest.mark.parametrize("num_shards", SHARD_COUNTS)
def test_gc_differential_three_modes(num_shards):
    trace = _overwrite_trace()
    base, rep0 = _run_no_gc(trace, num_shards)
    quiesced, rep1 = _run_quiesced_gc(trace, num_shards)
    live, rep2 = _run_gc_under_load(trace, num_shards)
    assert rep1 == rep0, "quiesced-GC report diverged from no-GC"
    assert rep2 == rep0, "GC-under-load report diverged from no-GC"
    d0, d1, d2 = _live_digest(base), _live_digest(quiesced), _live_digest(live)
    assert d1 == d0 and d2 == d0, "live-block sets diverged"
    for c in (base, quiesced, live):
        c.check_consistency()
    # the GC runs actually reclaimed and compacted (overwrite-heavy trace)
    assert quiesced.reclaimed_blocks == base.reclaimed_blocks
    assert quiesced.relocated_blocks > 0
    assert live.relocated_blocks > 0
    for c in (quiesced, live):  # every grace period drained at finish
        for e in c.shards:
            assert e.store._limbo == []


@pytest.mark.parametrize("num_shards", [2, 4])
def test_gc_snapshot_restore_mid_gc(num_shards):
    """Snapshot with limbo non-empty (mid-grace-period), restore, continue:
    bit-exact against both no-GC and an uninterrupted quiesced-GC run."""
    trace = _overwrite_trace()
    n = 3 * len(trace) // 4  # past the midpoint: overwrites are freeing blocks
    _, rep0 = _run_no_gc(trace, num_shards)

    c = _cluster(num_shards)
    c.run_gc()  # arm deferred reclaim before any traffic
    tags = [e.store.pin_epoch() for e in c.shards]  # writes "in flight"
    c.ingest_batched(trace[:n], batch_size=256)
    limbo_total = sum(len(e.store._limbo) for e in c.shards)
    assert limbo_total > 0, "pinned epochs should park frees in limbo"
    for e, tag in zip(c.shards, tags):
        e.store.unpin_epoch(tag)
    # snapshot taken mid-GC: limbo entries (with epoch tags) are serialized
    payload = json.dumps(snapshot_engine(c))
    restored = restore_engine(json.loads(payload))
    assert sum(len(e.store._limbo) for e in restored.shards) == limbo_total
    for cc in (c, restored):
        cc.ingest_batched(trace[n:], batch_size=256)
        cc.run_gc(max_moves_per_shard=64)
        assert cc.finish() == rep0
        cc.check_consistency()
    assert _live_digest(c) == _live_digest(restored)


def test_gc_resize_with_orphan_reclaim():
    """A shrink's quiesce point force-drains limbo (cross-shard orphan
    blocks freed by the stale-key sweep included) before migration, and the
    resized cluster still converges to the no-GC oracle."""
    trace = _overwrite_trace(total=2_000, seed=5)
    n = len(trace) // 2

    def run(with_gc):
        c = _cluster(4)
        if with_gc:
            c.run_gc()
        c.ingest_batched(trace[:n], batch_size=256)
        if with_gc:
            # leave limbo non-empty going into resize: pin, free, unpin
            tags = [e.store.pin_epoch() for e in c.shards]
            c.ingest_batched(trace[n : n + n // 2], batch_size=256)
            for e, tag in zip(c.shards, tags):
                e.store.unpin_epoch(tag)
            rest = trace[n + n // 2 :]
        else:
            c.ingest_batched(trace[n : n + n // 2], batch_size=256)
            rest = trace[n + n // 2 :]
        stats = c.resize(2)
        if with_gc:
            # resize quiesced: every orphan physically reclaimed, no limbo
            for e in c.shards:
                assert e.store._limbo == []
            c.run_gc(max_moves_per_shard=128)
        c.ingest_batched(rest, batch_size=256)
        rep = c.finish()
        c.check_consistency()
        return c, rep, stats

    base, rep0, stats0 = run(False)
    gced, rep1, stats1 = run(True)
    assert rep1 == rep0
    assert _live_digest(gced) == _live_digest(base)
    assert stats1["moved_fps"] == stats0["moved_fps"]
    assert gced.relocated_blocks > 0


# ---------------------------------------------------------------------------
# Satellite: sub-batch coalescing keeps both entry paths bit-exact.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("entry", ["ingest_batched", "write_batch"])
def test_coalescing_floor_is_bit_exact(entry):
    """Tiny sub-batches coalesced onto the coordinator (floor = huge) and
    fully scattered to workers (floor = 0) produce identical reports on
    either entry path; the serial path is the oracle."""
    trace = _overwrite_trace(total=2_000, seed=11)
    serial = _cluster(4)
    feed_cluster(serial, trace, entry, 128)
    rep0 = serial.finish()
    for floor in (0, 1 << 30):
        c = _cluster(4)
        c.min_parallel_batch = floor
        c.start_executor()
        try:
            feed_cluster(c, trace, entry, 128)
            rep = c.finish()
        finally:
            c.stop_executor()
        assert rep == rep0, f"floor={floor} diverged through {entry}"


def test_coalesced_write_batch_flags_match_workers():
    trace = _overwrite_trace(total=1_200, seed=3)
    cols = (trace["stream"], trace["lba"].astype(np.int64), trace["fp"])
    flags = {}
    for floor in (0, 1 << 30):
        c = _cluster(4)
        c.min_parallel_batch = floor
        c.start_executor()
        try:
            out = []
            for lo in range(0, len(trace), 100):  # sub-batches of ~25/shard
                out.append(c.write_batch(*(col[lo : lo + 100] for col in cols)))
            flags[floor] = np.concatenate(out)
            c.finish()
        finally:
            c.stop_executor()
    assert (flags[0] == flags[1 << 30]).all()


# ---------------------------------------------------------------------------
# Serving composition: AsyncDedupFrontend traffic + run_gc, page relocation.
# ---------------------------------------------------------------------------


def test_gc_under_frontend_traffic_matches_executed_interleaving():
    """run_gc steps interleaved with live async traffic: the executed
    interleaving replayed through a fresh GC-free cluster is bit-exact."""
    import asyncio

    from repro.serving.frontend import AsyncDedupFrontend

    trace = _overwrite_trace(total=2_000, seed=21)
    per_tenant = {}
    for t in np.unique(trace["stream"]):
        recs = trace[trace["stream"] == t]
        per_tenant[int(t)] = (recs["lba"].astype(np.int64), recs["fp"].astype(np.uint64))

    async def run():
        engine = _cluster(4)
        engine.min_parallel_batch = 0
        fe = AsyncDedupFrontend(
            engine, max_batch=128, max_delay=0.001, max_pending=256, record_trace=True
        )

        async def conn(t, lbas, fps):
            for i, (lba, fp) in enumerate(zip(lbas.tolist(), fps.tolist())):
                await fe.write(t, fp, lba=lba)
                if i % 400 == 399:
                    await fe.run_gc(max_moves_per_shard=64)

        await asyncio.gather(*(conn(t, c[0], c[1]) for t, c in per_tenant.items()))
        stats = await fe.run_gc()
        await fe.close()
        return engine.finish(), fe, stats, engine

    rep, fe, gc_stats, engine = asyncio.run(run())
    assert gc_stats is not None and "moved" in gc_stats
    t_col, l_col, f_col = fe.executed_trace()
    oracle = _cluster(4)
    oracle.write_batch(t_col, l_col, f_col)
    assert oracle.finish() == rep
    engine.check_consistency()


def test_serving_pages_follow_compaction():
    """KV pages move with their blocks: after run_gc relocates PBAs, every
    live mapping still finds its page and decode output is unchanged."""
    import jax

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving.dedup_kv import DedupKVServer

    cfg = get_config("tinyllama-1.1b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    srv = DedupKVServer(model, params, page_tokens=16, max_slots=64, cache_entries=1)
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab_size, 32)
    for _ in range(3):
        srv.prefill_request(0, np.concatenate([prompt, rng.integers(0, cfg.vocab_size, 16)]))
        srv.prefill_request(1, rng.integers(0, cfg.vocab_size, 48))
    srv.run_postprocess()  # merges free PBAs -> pages drop eagerly, holes open
    store = srv.dedup.store
    assert store._free_pbas, "postprocess should have opened PBA holes"
    stats = srv.run_gc()
    assert stats["moved"] > 0
    # every page key is a live PBA and every live PBA's page is reachable
    assert set(srv.pages) <= set(store.fp_of_pba)
    store.check_consistency()
    toks = np.concatenate([prompt, rng.integers(0, cfg.vocab_size, 16)])
    c1, p1, _ = srv.prefill_request(0, toks)
    nodedup = DedupKVServer(model, params, page_tokens=16, max_slots=64, cache_entries=0)
    c2, p2, _ = nodedup.prefill_request(0, toks)
    o1, _ = srv.decode(c1, p1, steps=3)
    o2, _ = nodedup.decode(c2, p2, steps=3)
    assert o1 == o2
