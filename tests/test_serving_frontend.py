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


class _CheckedEngine:
    """An ``HPDedup`` behind a check, made on the engine thread, that the
    batch being applied plus the writes buffered behind it stay within the
    front end's backpressure bound."""

    def __init__(self, bound: int):
        self.inner = HPDedup(cache_entries=256)
        self.bound = bound
        self.fe = None
        self.violations = 0

    def write_batch(self, streams, lbas, fps):
        if len(streams) + len(self.fe._buf_futs) > self.bound:
            self.violations += 1
        return self.inner.write_batch(streams, lbas, fps)


@pytest.mark.parametrize("max_pending,writers", [(8, 64), (8, 8), (1, 16)])
def test_backpressure_bound_holds_and_waiters_are_served_in_arrival_order(max_pending, writers):
    """Buffered plus in-flight writes never pass ``max_pending``, counted at
    every submit and inside the engine; writes reach the engine in the order
    they were called (waiters in FIFO order, and no write on the fast path
    overtakes one), and the executed interleaving replays bit-exact."""
    per_writer = 40
    rng = np.random.default_rng(max_pending * 1000 + writers)
    # each writer is its own tenant, so the admission cap never binds
    cols = {w: rng.integers(1, 300, per_writer, dtype=np.uint64) for w in range(writers)}
    engine = _CheckedEngine(max_pending)

    async def run():
        fe = AsyncDedupFrontend(engine, max_batch=4, max_delay=0.0005, max_pending=max_pending,
                                record_trace=True)
        engine.fe = fe
        called, peak, submits = [], 0, 0
        orig = fe._schedule_flush

        def at_submit():  # runs as each write is buffered
            nonlocal peak, submits
            submits += 1
            peak = max(peak, submits - fe.records_executed)
            orig()

        fe._schedule_flush = at_submit

        async def writer(w):
            for fp in cols[w].tolist():
                called.append((w, fp))
                await fe.write(w, fp)

        await asyncio.gather(*(writer(w) for w in range(writers)))
        await fe.close()
        return fe, called, peak

    fe, called, peak = asyncio.run(run())
    assert peak <= max_pending and engine.violations == 0
    assert fe._pending == 0 and not fe._waiters and not fe._granted
    t_col, l_col, f_col = fe.executed_trace()
    assert list(zip(t_col.tolist(), f_col.tolist())) == called
    stats = fe.stats()
    assert stats["completed"] == writers * per_writer
    # one write in flight per writer: the bound binds only with more writers
    assert (stats["slow_writes"] > 0) == (writers > max_pending)
    oracle = HPDedup(cache_entries=256)
    oracle.write_batch(t_col, l_col, f_col)
    assert oracle.finish() == engine.inner.finish()


class _GatedEngine:
    """Applies a batch only once the test opens the gate."""

    def __init__(self):
        import threading

        self.gate = threading.Event()
        self.batches = []

    def write_batch(self, streams, lbas, fps):
        self.gate.wait(10)
        self.batches.append(fps.tolist())
        return fps % 2 == 0


@pytest.mark.parametrize("when", ["in_batch", "waiting", "granted"])
def test_cancelled_write_is_skipped_and_its_slot_released(when):
    """A write the client cancels is skipped at acknowledgement (it was
    applied) or never buffered (it was waiting on backpressure); either way
    its slot comes back and every other write completes."""
    engine = _GatedEngine()

    async def run():
        fe = AsyncDedupFrontend(engine, max_batch=4, max_delay=0.0005, max_pending=4)
        first = [asyncio.ensure_future(fe.write(0, fp)) for fp in (10, 11, 12, 13)]
        await asyncio.sleep(0.01)  # the batch closed; the engine holds it at the gate
        late = [asyncio.ensure_future(fe.write(1, fp)) for fp in (20, 21)]
        await asyncio.sleep(0)
        assert len(fe._waiters) == 2
        victim = {"in_batch": first[0], "waiting": late[0], "granted": late[0]}[when]
        if when == "granted":  # cancelled after its slot was handed over
            release = fe._release

            def release_then_cancel(n):
                release(n)
                if n:
                    victim.cancel()

            fe._release = release_then_cancel
        else:
            victim.cancel()
            await asyncio.sleep(0)
        engine.gate.set()
        results = await asyncio.gather(*first, *late, return_exceptions=True)
        await fe.close()
        return fe, results, victim

    fe, results, victim = asyncio.run(run())
    assert victim.cancelled()
    for fp, r in zip((10, 11, 12, 13, 20, 21), results):
        if not isinstance(r, asyncio.CancelledError):
            assert r is (fp % 2 == 0)
    assert sum(isinstance(r, asyncio.CancelledError) for r in results) == 1
    assert fe._pending == 0 and not fe._waiters and not fe._granted
    applied = [fp for b in engine.batches for fp in b]
    assert applied[:4] == [10, 11, 12, 13]
    assert applied[4:] == ([20, 21] if when == "in_batch" else [21])
    assert fe.stats()["completed"] == len(applied)
    assert fe.stats()["slow_writes"] == 2


def test_a_write_never_overtakes_a_waiter_handed_a_slot():
    """A write submitted after a waiter was handed a slot, but before the
    waiter resumed, queues behind it and is served as soon as it resumes:
    the two land in one batch, in arrival order."""
    engine = _GatedEngine()

    async def run():
        fe = AsyncDedupFrontend(engine, max_batch=4, max_delay=0.05, max_pending=4)
        first = [asyncio.ensure_future(fe.write(0, fp)) for fp in (10, 11, 12, 13)]
        await asyncio.sleep(0.01)  # the batch closed; the engine holds it at the gate
        waiter = asyncio.ensure_future(fe.write(1, 20))
        go = asyncio.get_running_loop().create_future()

        async def late():
            await go
            return await fe.write(2, 31)

        later = asyncio.ensure_future(late())
        await asyncio.sleep(0)
        assert len(fe._waiters) == 1
        release = fe._release

        def release_and_go(n):  # ``later`` resumes before the waiter it handed a slot
            if n and not go.done():
                go.set_result(None)
            release(n)

        fe._release = release_and_go
        engine.gate.set()
        results = await asyncio.gather(*first, waiter, later)
        await fe.close()
        return fe, results

    fe, results = asyncio.run(run())
    assert engine.batches == [[10, 11, 12, 13], [20, 31]]
    assert results == [True, False, True, False, True, False]
    assert fe.stats()["slow_writes"] == 2
    assert fe._pending == 0 and not fe._waiters and not fe._granted


@pytest.mark.parametrize("style", ["await", "gather", "ensure_future", "wait_for"])
@pytest.mark.parametrize("max_pending", [64, 3])
def test_write_result_works_under_every_await_style(style, max_pending):
    """The fast path's future and the slow path's coroutine both work under
    ``await``, ``asyncio.gather``, ``asyncio.ensure_future`` and
    ``asyncio.wait_for``, and resolve to the engine's flags."""
    fps = [5, 7, 5, 9, 7, 7, 11, 5, 13, 9]
    engine = HPDedup(cache_entries=64)

    async def run():
        fe = AsyncDedupFrontend(engine, max_batch=2, max_delay=0.0005, max_pending=max_pending,
                                record_trace=True)
        if style == "await":
            out = [await fe.write(0, fp) for fp in fps]
        elif style == "gather":
            out = await asyncio.gather(*(fe.write(0, fp) for fp in fps))
        elif style == "ensure_future":
            futs = [asyncio.ensure_future(fe.write(0, fp)) for fp in fps]
            out = [await f for f in futs]
        else:
            out = [await asyncio.wait_for(fe.write(0, fp), timeout=10) for fp in fps]
        await fe.close()
        return fe, out

    fe, out = asyncio.run(run())
    t_col, l_col, f_col = fe.executed_trace()
    oracle = HPDedup(cache_entries=64)
    flags = oracle.write_batch(t_col, l_col, f_col)
    assert sorted(f_col.tolist()) == sorted(fps)
    if style in ("await", "wait_for") or max_pending >= len(fps):
        assert f_col.tolist() == fps
        assert out == [bool(f) for f in flags]
    assert all(type(r) is bool for r in out)
    assert sum(out) == int(flags.sum()) == fe.stats()["deduped"]
    if max_pending >= len(fps):
        assert fe.stats()["slow_writes"] == 0


@pytest.mark.parametrize("admission_control", [True, False])
def test_stats_match_what_the_clients_received(admission_control):
    """Per tenant, ``completed`` and ``deduped`` equal the flags the clients
    got back; each p50/p99 is taken over exactly ``completed`` latencies."""
    tenants = _tenant_columns(total=3_000, seed=4)
    got = {t: [] for t in tenants}

    async def run():
        fe = AsyncDedupFrontend(_make_cluster(2), max_batch=64, max_delay=0.0005, max_pending=96,
                                admission_control=admission_control)

        async def conn(t, lbas, fps):
            for lba, fp in zip(lbas.tolist(), fps.tolist()):
                got[t].append(await fe.write(t, fp, lba=lba))

        await asyncio.gather(*(conn(t, l[c::3], f[c::3]) for t, (l, f) in tenants.items()
                               for c in range(3)))
        await fe.close()
        return fe

    fe = asyncio.run(run())
    stats = fe.stats()
    samples = []
    for t, flags in got.items():
        st, q = stats["tenants"][t], fe.tenants[t]
        lat = np.concatenate(q.latencies)
        samples.append(lat)
        assert st["submitted"] == st["completed"] == len(flags) == lat.size
        assert st["deduped"] == sum(flags)
        assert st["p50_ms"] == round(float(np.percentile(lat, 50)) * 1e3, 3)
        assert st["p99_ms"] == round(float(np.percentile(lat, 99)) * 1e3, 3)
        assert (lat > 0).all()
    lat = np.concatenate(samples)
    assert lat.size == stats["completed"] == sum(len(f) for f in got.values())
    assert stats["deduped"] == sum(sum(f) for f in got.values())
    assert stats["p50_ms"] == round(float(np.percentile(lat, 50)) * 1e3, 3)
    assert stats["p99_ms"] == round(float(np.percentile(lat, 99)) * 1e3, 3)
    assert set(stats) >= {"tenants", "completed", "deduped", "throttled", "batches", "mean_batch",
                          "fill_s", "queue_wait_s", "ack_s", "p50_ms", "p99_ms", "slow_writes"}


def test_stats_of_an_idle_frontend():
    fe = AsyncDedupFrontend(HPDedup(cache_entries=64))
    stats = fe.stats()
    assert stats["completed"] == stats["slow_writes"] == 0
    assert stats["p50_ms"] == stats["p99_ms"] == 0.0
    fe._engine_pool.shutdown(wait=False)
