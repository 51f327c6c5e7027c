"""The served path's profiler spans and launch counters, on the CPU.

A profiler trace over a tiny 4-shard ``ShardedCluster`` behind
``AsyncDedupFrontend`` (shard executor on, indexes on the Pallas backend in
interpret mode) must hold every span ``repro.obs`` names, with the shard
spans on the worker threads carrying the cluster's batch number, and a
bounded number of spans per batch, and a forced full garbage collection as a
``gc.collect`` span.  ``FingerprintIndex.table_stats()``'s launch counters
are checked against the launches themselves.
"""

from __future__ import annotations

import asyncio
import gc
import glob
import os
from collections import Counter

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import ShardedCluster
from repro.core.fingerprint import OP_WRITE
from repro.core.fp_index import FingerprintIndex
from repro.core.traces import generate_workload
from repro.kernels.fp_index import TILE_KEYS, tile_shape
from repro.kernels.ops import _route_keys
from repro.serving.frontend import AsyncDedupFrontend

BATCH = 1024
BATCHES = 6
POST_PERIOD = 512


@pytest.fixture
def device_indexes(monkeypatch):
    """Every index on the Pallas backend (interpreted here), with a host-set
    cutoff small enough for tiny per-shard sub-batches to reach the table."""
    monkeypatch.setitem(FingerprintIndex.__init__.__kwdefaults__, "backend", "pallas")
    monkeypatch.setitem(FingerprintIndex.__init__.__kwdefaults__, "small_batch", 32)


def _threads(trace_dir):
    """Host thread lines of the trace: [(line name, [(name, start, end, stats)])]."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = [(e.name[len(obs.PREFIX):], e.start_ns, e.start_ns + e.duration_ns,
                          dict(e.stats)) for e in line.events if e.name.startswith(obs.PREFIX)]
                if spans:
                    out.append((line.name, spans))
    return out


async def _serve(cluster, writes):
    # batches close by size only: exactly BATCHES of them
    fe = AsyncDedupFrontend(cluster, max_batch=BATCH, max_delay=60.0, max_pending=len(writes))
    try:
        await asyncio.gather(*(fe.write(int(r["stream"]), int(r["fp"]), lba=int(r["lba"]))
                               for r in writes))
    finally:
        await fe.close()
    return fe


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    trace, _ = generate_workload("A", total_requests=40_000, seed=3)
    writes = trace[trace["op"] == OP_WRITE]
    mp = pytest.MonkeyPatch()
    mp.setitem(FingerprintIndex.__init__.__kwdefaults__, "backend", "pallas")
    mp.setitem(FingerprintIndex.__init__.__kwdefaults__, "small_batch", 32)
    try:
        # a small cache: evictions tombstone the cache index, the
        # estimator's interval (256 writes) ends inside the sub-batches, and
        # each shard runs a post-processing pass every 512 of its writes
        cluster = ShardedCluster(num_shards=4, seed=0, cache_entries=256,
                                 postprocess_period=POST_PERIOD)
        cluster.min_parallel_batch = 64
        cluster.ingest_batched(writes[:4096])
        served = writes[4096:4096 + BATCH * BATCHES]
        trace_dir = str(tmp_path_factory.mktemp("obs_trace"))
        jax.profiler.start_trace(trace_dir)
        try:
            fe = asyncio.run(_serve(cluster, served))
            gc.collect(2)  # a full collection inside the trace, whatever serving allocated
        finally:
            jax.profiler.stop_trace()
    finally:
        mp.undo()
    return cluster, fe, _threads(trace_dir)


def test_every_span_appears_and_none_outside_the_table(served):
    _, _, threads = served
    seen = {name for _, spans in threads for name, *_ in spans}
    assert seen == set(obs.SPANS)


def test_forced_full_collection_lands_as_a_gc_span(served):
    _, _, threads = served
    assert obs.SPANS["gc.collect"] == "cluster and engines"
    gcs = [st for _, spans in threads for name, _, _, st in spans if name == "gc.collect"]
    full = [st for st in gcs if st["generation"] == 2]
    assert full and all(st["collected"] >= 0 for st in full)
    assert {st["generation"] for st in gcs} <= {0, 1, 2}


def test_building_clusters_installs_the_gc_hook_once():
    ShardedCluster(num_shards=2, seed=0)
    ShardedCluster(num_shards=2, seed=1)
    assert gc.callbacks.count(obs._on_gc) == 1
    obs.trace_gc()
    assert gc.callbacks.count(obs._on_gc) == 1


def test_gc_hook_without_a_trace_records_nothing_and_closes_its_span():
    obs.trace_gc()
    gc.collect(2)
    assert obs._gc_span is None  # every start met its stop


def test_shard_spans_run_on_worker_threads_with_the_cluster_batch(served):
    cluster, fe, threads = served
    coord = [i for i, (_, spans) in enumerate(threads)
             if any(name == "cluster.write_batch" for name, *_ in spans)]
    assert len(coord) == 1  # the front end's engine thread
    calls = {st["batch"]: (a, b) for name, a, b, st in threads[coord[0]][1]
             if name == "cluster.write_batch"}
    assert sorted(calls) == list(range(cluster.write_batches)) == list(range(BATCHES))
    shard = [(i, a, b, st) for i, (_, spans) in enumerate(threads) for name, a, b, st in spans
             if name == "shard.write_batch"]
    # the executor's four workers, each on its own line
    assert {i for i, *_ in shard}.isdisjoint(coord) and len({i for i, *_ in shard}) == 4
    for i, a, b, st in shard:
        lo, hi = calls[st["batch"]]
        assert lo <= a and b <= hi  # inside the cluster call it belongs to
    assert max(Counter(st["batch"] for *_, st in shard).values()) == 4
    assert sum(st["keys"] for *_, st in shard) == BATCH * BATCHES
    # the front end's own spans: one fill, close, execute and ack per batch
    fe_spans = Counter(name for _, spans in threads for name, *_ in spans
                       if name.startswith("frontend."))
    assert fe_spans == {"frontend.fill": BATCHES, "frontend.close": BATCHES,
                        "frontend.execute": BATCHES, "frontend.ack": BATCHES}
    assert fe.batches_executed == BATCHES


def test_spans_per_batch_are_bounded_not_per_write(served):
    _, _, threads = served
    total = sum(len(spans) for _, spans in threads)
    # launch-granularity spans only: a few per shard index launch, never one
    # per write (BATCH writes per batch)
    assert total / BATCHES < BATCH / 4
    boundary = [st for _, spans in threads for name, _, _, st in spans
                if name == "engine.boundary"]
    assert boundary and all(st["kind"] in (obs.BOUNDARY_INTERVAL, obs.BOUNDARY_POST,
                                           obs.BOUNDARY_INTERVAL | obs.BOUNDARY_POST)
                            for st in boundary)


def test_post_processing_passes_land_as_spans_inside_the_shard_calls(served):
    cluster, _, threads = served
    assert obs.SPANS["post.run"] == obs.SPANS["store.reverse"] == "post-processing"
    for _, spans in threads:
        shard_calls = [(a, b) for name, a, b, _ in spans if name == "shard.write_batch"]
        for name, a, b, st in spans:
            if name == "post.run":
                # a pass runs at the scalar record that reaches the period
                assert any(lo <= a and b <= hi for lo, hi in shard_calls)
                assert 0 <= st["merges"] <= st["backlog"] and st["reclaimed"] >= 0
    runs = [st for _, spans in threads for name, _, _, st in spans if name == "post.run"]
    # the served batches give each shard about BATCH * BATCHES / 4 writes
    assert len(runs) >= 4 * (BATCH * BATCHES // 4 // POST_PERIOD - 1)
    assert sum(st["merges"] for st in runs) > 0


def test_reverse_index_span_walks_the_keys_staged_since_the_last_pass(served):
    cluster, _, threads = served
    walks = [st["keys"] for _, spans in threads for name, _, _, st in spans
             if name == "store.reverse"]
    assert walks and all(0 < k <= 2 * POST_PERIOD for k in walks)
    # the counter adds up every walk, the ingest's included
    assert sum(e.store.reverse_keys_walked for e in cluster.shards) >= sum(walks)


def test_reverse_keys_counter_stays_out_of_the_snapshot():
    cluster = ShardedCluster(num_shards=1, seed=0, cache_entries=64, postprocess_period=64)
    trace, _ = generate_workload("C", total_requests=2_000, seed=4)
    cluster.ingest_batched(trace)
    store = cluster.shards[0].store
    assert store.reverse_keys_walked > 0
    tree = store.snapshot()
    assert "reverse_keys_walked" not in str(tree)
    again = type(store)()
    again.load_snapshot(tree)
    assert again.reverse_keys_walked == 0


def test_frontend_counters_add_up(served):
    _, fe, threads = served
    assert fe.fill_s > 0 and fe.queue_wait_s >= 0 and fe.ack_s > 0
    st = fe.stats()
    assert (st["fill_s"], st["queue_wait_s"], st["ack_s"]) == (fe.fill_s, fe.queue_wait_s,
                                                               fe.ack_s)
    fills = [(b - a) * 1e-9 for _, spans in threads for name, a, b, _ in spans
             if name == "frontend.fill"]
    # the span and the counter time the same stretch on two clocks
    assert len(fills) == BATCHES
    assert sum(fills) == pytest.approx(fe.fill_s, rel=0.05, abs=1e-3)


@pytest.mark.parametrize("max_pending,waited", [(64, 0), (4, 60)])
def test_close_span_counts_the_writes_that_waited(tmp_path, max_pending, waited):
    """``frontend.close``'s ``slow`` stat is the closed batch's writes that
    took the slow path: none while the front end has room, and every write
    beyond the first ``max_pending`` when 64 arrive at once."""

    class Standin:
        def write_batch(self, streams, lbas, fps):
            return np.zeros(len(fps), dtype=bool)

    async def serve():
        fe = AsyncDedupFrontend(Standin(), max_batch=8, max_delay=0.001, max_pending=max_pending)
        try:
            await asyncio.gather(*(fe.write(i % 4, i + 1) for i in range(64)))
        finally:
            await fe.close()
        return fe

    jax.profiler.start_trace(str(tmp_path))
    try:
        fe = asyncio.run(serve())
    finally:
        jax.profiler.stop_trace()
    closes = [st for _, spans in _threads(str(tmp_path)) for name, _, _, st in spans
              if name == "frontend.close"]
    assert len(closes) == fe.batches_executed
    assert sum(st["keys"] for st in closes) == 64
    assert sum(st["slow"] for st in closes) == fe.stats()["slow_writes"] == waited


def test_served_slow_writes_are_the_admission_caps_waits(served):
    """The served front end has room for every write, so only the contended
    cache's admission caps send writes down the slow path."""
    _, fe, threads = served
    closes = [st for _, spans in threads for name, _, _, st in spans if name == "frontend.close"]
    stats = fe.stats()
    assert len(closes) == BATCHES
    assert sum(st["slow"] for st in closes) == stats["slow_writes"] == stats["throttled"] > 0


def test_launch_spans_carry_the_index_counters(served):
    cluster, _, threads = served
    puts = [st for _, spans in threads for name, _, _, st in spans if name == "fp_index.put"]
    stats = [idx.table_stats() for e in cluster.shards
             for idx in (e._seen_fps, e.store.fp_index, e.inline.cache.index)]
    # the trace covers the served batches only; the counters include the ingest
    assert 0 < sum(st["keys"] for st in puts) <= sum(s["launch_keys"] for s in stats)
    assert all(st["keys"] <= st["slots"] for st in puts)
    assert sum(s["removed_device"] for s in stats) > 0


def test_table_stats_launch_counters_on_the_device_backend(device_indexes):
    rng = np.random.default_rng(5)
    keys = rng.integers(1, 1 << 62, size=3000, dtype=np.uint64)
    idx = FingerprintIndex(capacity=1 << 14)
    cap = idx.table_stats()["capacity"]
    st0 = idx.table_stats()
    assert (st0["launch_keys"], st0["launch_key_slots"], st0["inserted_device"],
            st0["flush_probe_keys"]) == (0, 0, 0, 0)
    # add_many journals; the next batched probe folds it: one probe of the
    # journal (filter), one insert of the fresh keys, then the probe itself
    idx.add_many(keys[:2000])
    flags = idx.contains_many(keys)
    assert flags[:2000].all() and not flags[2000:].any()
    st = idx.table_stats()
    assert st["flush_probe_keys"] == 2000
    assert st["launches_device"] == {"probe": 2, "insert": 1, "remove": 0}
    assert st["inserted_device"] == 2000 == st["live"]
    assert st["launch_keys"] == 2000 + 2000 + 3000

    def slots(n_keys):
        from repro.core.fp_index import _split

        lo, hi = _split(n_keys)
        counts, klo, _, _ = _route_keys(lo, hi, cap)
        tiles = tile_shape(cap)[0]
        k = max(TILE_KEYS, -(-int(counts.max()) // TILE_KEYS) * TILE_KEYS)
        assert klo.size == tiles * k
        return tiles * k

    assert st["launch_key_slots"] == 2 * slots(keys[:2000]) + slots(keys)
    # inserts through probe_and_add count the keys the launch placed
    more = rng.integers(1, 1 << 62, size=500, dtype=np.uint64)
    idx.probe_and_add(np.unique(more))
    st2 = idx.table_stats()
    assert st2["inserted_device"] - st["inserted_device"] == np.unique(more).size
    # removals count the slots a launch tombstoned
    idx.remove_many(keys[:100])
    st3 = idx.table_stats()
    assert st3["removed_device"] == 100 and st3["launches_device"]["remove"] == 1
    idx.check_consistency()
