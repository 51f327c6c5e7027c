"""Async serving front end: determinism, backpressure, admission, resize.

The determinism contract (serving/frontend.py): the front end multiplexes
concurrent per-tenant client streams into columnar batches, and the exact
interleaving it executed — ``executed_trace()`` — replayed through a fresh
identically-configured engine single-stream reproduces a bit-exact
``HybridReport`` and identical per-tenant dedup counts.  Concurrency
changes *which* interleaving runs, never the answer for that interleaving.
"""

from __future__ import annotations

import asyncio

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import HPDedup, ShardedCluster, generate_workload
from repro.models import build_model
from repro.serving.dedup_kv import DedupKVServer
from repro.serving.frontend import AsyncDedupFrontend


def _tenant_columns(total=4_000, seed=3, workload="A"):
    trace, _ = generate_workload(workload, total_requests=total, seed=seed)
    out = {}
    for t in np.unique(trace["stream"]):
        recs = trace[trace["stream"] == t]
        out[int(t)] = (recs["lba"].astype(np.int64), recs["fp"].astype(np.uint64))
    return out


async def _drive(fe, tenants, conns_per_tenant=4):
    async def conn(t, lbas, fps):
        for lba, fp in zip(lbas.tolist(), fps.tolist()):
            await fe.write(t, fp, lba=lba)

    jobs = []
    for t, (lbas, fps) in tenants.items():
        for c in range(conns_per_tenant):
            jobs.append(conn(t, lbas[c::conns_per_tenant], fps[c::conns_per_tenant]))
    await asyncio.gather(*jobs)


def _make_cluster(n=4, cache_entries=512):
    return ShardedCluster(num_shards=n, cache_entries=cache_entries)


def test_per_tenant_counts_match_single_stream_replay():
    tenants = _tenant_columns()

    async def run():
        engine = _make_cluster()
        fe = AsyncDedupFrontend(
            engine, max_batch=128, max_delay=0.001, max_pending=256, record_trace=True
        )
        await _drive(fe, tenants)
        await fe.close()
        return engine.finish(), fe

    rep, fe = asyncio.run(run())

    # single-stream replay of the interleaved trace the frontend executed
    t_col, l_col, f_col = fe.executed_trace()
    oracle = _make_cluster()
    flags = oracle.write_batch(t_col, l_col, f_col)
    assert oracle.finish() == rep  # bit-exact HybridReport

    stats = fe.stats()
    for t, (lbas, _) in tenants.items():
        mask = t_col == t
        assert stats["tenants"][t]["completed"] == int(mask.sum()) == len(lbas)
        assert stats["tenants"][t]["deduped"] == int(flags[mask].sum())


@pytest.mark.parametrize("num_shards", [1, 2, 4])
def test_postprocess_period_on_the_served_path_matches_the_scalar_oracle(num_shards):
    """Workload C with the exact phase running every 256 writes per shard:
    served through the front end, the cluster's report is bit-exact against
    the same interleaving replayed record by record through the scalar
    oracle, shard by shard (``HPDedup.replay``)."""
    from repro.core.fingerprint import OP_WRITE, TRACE_DTYPE

    tenants = _tenant_columns(total=4_000, seed=9, workload="C")

    def make():
        return ShardedCluster(num_shards=num_shards, cache_entries=256, postprocess_period=256)

    async def run():
        engine = make()
        fe = AsyncDedupFrontend(engine, max_batch=256, max_delay=0.001, max_pending=512,
                                record_trace=True)
        await _drive(fe, tenants)
        await fe.close()
        return engine, fe

    engine, fe = asyncio.run(run())
    rep = engine.finish()
    assert rep.post.passes > 3 * num_shards  # passes ran on the served path, not only at finish
    t_col, l_col, f_col = fe.executed_trace()
    trace = np.zeros(t_col.size, dtype=TRACE_DTYPE)
    trace["ts"] = np.arange(t_col.size)
    trace["stream"], trace["op"], trace["lba"], trace["fp"] = t_col, OP_WRITE, l_col, f_col
    oracle = make()
    oracle.replay(trace)
    assert oracle.finish() == rep
    for a, b in zip(oracle.shard_reports, engine.shard_reports):
        assert a == b
    engine.check_consistency()


def test_frontend_over_single_engine_and_kv_server():
    tenants = _tenant_columns(total=2_000, seed=8)

    async def run(engine):
        fe = AsyncDedupFrontend(engine, max_batch=64, max_delay=0.001, record_trace=True)
        await _drive(fe, tenants, conns_per_tenant=2)
        await fe.close()
        return fe

    fe1 = asyncio.run(run(HPDedup(cache_entries=512)))
    cfg = get_config("tinyllama-1.1b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    server = DedupKVServer(model, params, page_tokens=16, max_slots=64, cache_entries=512)
    fe2 = asyncio.run(run(server))
    assert fe2.engine is server.dedup  # unwraps the KV server's engine
    assert fe1.stats()["completed"] == fe2.stats()["completed"] == sum(
        len(l) for l, _ in tenants.values()
    )


def test_backpressure_bounds_pending_and_completes():
    tenants = _tenant_columns(total=3_000, seed=5)
    total = sum(len(l) for l, _ in tenants.values())

    async def run():
        engine = _make_cluster(2)
        fe = AsyncDedupFrontend(
            engine, max_batch=32, max_delay=0.0005, max_pending=48, record_trace=True
        )
        peak = 0

        orig = fe._schedule_flush

        def watch():
            nonlocal peak
            peak = max(peak, len(fe._buf_futs) + fe._inflight_batches * fe.max_batch)
            orig()

        fe._schedule_flush = watch
        await _drive(fe, tenants, conns_per_tenant=8)
        await fe.close()
        return engine.finish(), fe, peak

    rep, fe, peak = asyncio.run(run())
    assert fe.stats()["completed"] == total
    # buffered writes never exceed the backpressure bound
    assert peak <= 48 + fe.max_batch
    t_col, l_col, f_col = fe.executed_trace()
    oracle = _make_cluster(2)
    oracle.write_batch(t_col, l_col, f_col)
    assert oracle.finish() == rep


def test_admission_control_throttles_under_cache_contention():
    # tiny caches -> occupancy crosses contention_ratio early; the Zipf-ish
    # volume skew gives the estimator distinct per-tenant LDSS shares
    tenants = _tenant_columns(total=6_000, seed=2)

    async def run():
        engine = _make_cluster(2, cache_entries=64)
        fe = AsyncDedupFrontend(
            engine,
            max_batch=64,
            max_delay=0.0005,
            max_pending=512,
            admission_budget=8,
            contention_ratio=0.5,
            record_trace=True,
        )
        await _drive(fe, tenants, conns_per_tenant=6)
        await fe.close()
        return engine.finish(), fe

    rep, fe = asyncio.run(run())
    stats = fe.stats()
    assert stats["throttled"] > 0
    # throttled writes still complete: nothing is dropped
    assert stats["completed"] == sum(len(l) for l, _ in tenants.values())
    t_col, l_col, f_col = fe.executed_trace()
    oracle = _make_cluster(2, cache_entries=64)
    oracle.write_batch(t_col, l_col, f_col)
    assert oracle.finish() == rep


def test_live_resize_under_traffic():
    tenants = _tenant_columns(total=4_000, seed=7)
    total = sum(len(l) for l, _ in tenants.values())

    async def run():
        engine = _make_cluster(2)
        fe = AsyncDedupFrontend(engine, max_batch=128, max_delay=0.001, record_trace=True)
        traffic = asyncio.ensure_future(_drive(fe, tenants, conns_per_tenant=4))
        await asyncio.sleep(0.01)
        info = await fe.resize(4)
        await traffic
        await fe.close()
        return engine, fe, info

    engine, fe, info = asyncio.run(run())
    assert engine.num_shards == 4
    assert info["new_num_shards"] == 4
    rep = engine.finish()
    stats = fe.stats()
    assert stats["completed"] == total
    # resize preserves exactness: aggregate exact-dedup counts equal a
    # fixed-layout oracle's over the same executed interleaving
    t_col, l_col, f_col = fe.executed_trace()
    oracle = _make_cluster(2)
    oracle.write_batch(t_col, l_col, f_col)
    orep = oracle.finish()
    assert rep.total_writes == orep.total_writes == total
    assert rep.unique_fingerprints == orep.unique_fingerprints
    assert rep.final_disk_blocks == orep.final_disk_blocks


def test_engine_error_propagates_to_writers():
    class Exploding:
        def write_batch(self, streams, lbas, fps):
            raise RuntimeError("engine down")

    async def run():
        fe = AsyncDedupFrontend(Exploding(), max_batch=4, max_delay=0.0005)
        with pytest.raises(RuntimeError, match="engine down"):
            await fe.write(0, 12345)
        fe._engine_pool.shutdown(wait=False)

    asyncio.run(run())


def test_write_after_close_rejected():
    async def run():
        fe = AsyncDedupFrontend(HPDedup(cache_entries=64))
        await fe.write(0, 99)
        await fe.close()
        with pytest.raises(RuntimeError, match="closed"):
            await fe.write(0, 100)

    asyncio.run(run())
