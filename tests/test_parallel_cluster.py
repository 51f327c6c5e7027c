"""Concurrency determinism: the parallel shard executor vs the serial oracle.

The contract under test (core/cluster.py, ``ParallelShardExecutor``): every
shard worker consumes its own FIFO queue, the coordinator routes/scatters
chunk k+1 while shards drain chunk k, and a barrier-and-merge precedes any
coordinator read of shard state — so the parallel path produces a
**bit-exact** ``HybridReport`` (dataclass ``==``) against the serial path,
for any shard count and any thread interleaving the OS picks, including
across a crash/restore in the middle of a parallel replay.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from repro.core import (
    DEFAULT_BATCH_SIZE,
    ParallelShardExecutor,
    ShardedCluster,
    ShardWorkerError,
    generate_workload,
    restore_engine,
    run_replay,
    snapshot_engine,
)
from repro.core.fingerprint import OP_WRITE
from conftest import feed_cluster

SHARD_COUNTS = [1, 2, 4, 8]


def _trace(total=6_000, seed=5, workload="A"):
    return generate_workload(workload, total_requests=total, seed=seed)[0]


def _overwrite_trace(total=4_000, seed=13):
    """Overwrite-heavy: the second half rewrites the first half's LBAs with
    new content, exercising the store free/remap path under parallelism."""
    base = _trace(total, seed)
    over = base.copy()
    over["ts"] = over["ts"] + int(base["ts"].max()) + 1
    over["fp"] = over["fp"] ^ np.uint64(0x9E3779B97F4A7C15)
    both = np.concatenate([base, over])
    both.sort(order="ts", kind="stable")
    return both


def _cluster(num_shards):
    return ShardedCluster(num_shards=num_shards, cache_entries=512)


@pytest.mark.parametrize("num_shards", SHARD_COUNTS)
@pytest.mark.parametrize("make_trace", [_trace, _overwrite_trace], ids=["mixed", "overwrite"])
def test_parallel_replay_bit_exact_vs_serial(num_shards, make_trace):
    trace = make_trace()
    serial = _cluster(num_shards).replay_batched(trace, batch_size=256)
    parallel = _cluster(num_shards).replay_batched(trace, batch_size=256, parallel=True)
    assert parallel.finish() == serial.finish()


@pytest.mark.parametrize("entry", ["ingest_batched", "write_batch"])
def test_parallel_matches_serial_on_both_entry_paths(entry):
    """Both entry paths reach the shards through the one dispatch: on the
    workers (floor 0) they leave the serial path's report."""
    trace = _trace(5_000, seed=21)
    serial = _cluster(4)
    feed_cluster(serial, trace, entry, 512)
    parallel = _cluster(4)
    parallel.min_parallel_batch = 0
    parallel.start_executor()
    try:
        feed_cluster(parallel, trace, entry, 512)
        rep = parallel.finish()
    finally:
        parallel.stop_executor()
    assert rep == serial.finish()


@pytest.mark.parametrize("mode", ["serial", "parallel"])
def test_write_batch_and_ingest_share_state(mode):
    """A write-only trace fed through chunked ``write_batch`` and through
    ``ingest_batched`` with the same chunking leaves the same per-shard
    engine state and the same report: one dispatch, one shard driver."""
    trace = _trace(5_000, seed=23)
    trace = trace[trace["op"] == OP_WRITE]
    trees, reports = [], []
    for entry in ("write_batch", "ingest_batched"):
        c = _cluster(4)
        if mode == "parallel":
            c.min_parallel_batch = 0
            c.start_executor()
        try:
            feed_cluster(c, trace, entry, 256)
            trees.append(json.dumps(c.snapshot()["shards"]))
            reports.append(c.finish())
        finally:
            c.stop_executor()
    assert trees[0] == trees[1]
    assert reports[0] == reports[1]


def test_parallel_write_batch_flags_match_serial():
    trace = _trace(3_000, seed=7)
    serial = _cluster(4)
    parallel = _cluster(4)
    parallel.start_executor()
    try:
        for lo in range(0, len(trace), 512):
            chunk = trace[lo : lo + 512]
            fs = serial.write_batch(chunk["stream"], chunk["lba"], chunk["fp"])
            fp_ = parallel.write_batch(chunk["stream"], chunk["lba"], chunk["fp"])
            assert np.array_equal(fs, fp_)
    finally:
        parallel.stop_executor()
    assert parallel.finish() == serial.finish()


def test_crash_restore_mid_parallel_replay_bit_exact():
    """Snapshot taken mid-parallel-replay, JSON round-trip, resume in
    parallel: the stitched run must equal one uninterrupted serial run."""
    trace = _overwrite_trace(4_000, seed=3)
    cut = len(trace) // 2
    serial = _cluster(4).replay_batched(trace, batch_size=256)

    live = _cluster(4)
    live.start_executor()
    try:
        live.ingest_batched(trace[:cut], batch_size=256)
        payload = json.dumps(snapshot_engine(live))  # snapshot barriers first
    finally:
        live.stop_executor()
    restored = restore_engine(json.loads(payload))
    restored.ingest_batched(trace[cut:], batch_size=256, parallel=True)
    assert restored.finish() == serial.finish()


@pytest.mark.parametrize("num_shards", [2, 4])
def test_resize_restarts_executor_and_stays_exact(num_shards):
    trace = _trace(4_000, seed=9)
    cut = len(trace) // 2
    cluster = _cluster(num_shards)
    cluster.start_executor()
    try:
        cluster.ingest_batched(trace[:cut], batch_size=256)
        cluster.resize(num_shards + 2)
        assert cluster._executor is not None  # restarted at the new width
        cluster.ingest_batched(trace[cut:], batch_size=256)
        rep = cluster.finish()
    finally:
        cluster.stop_executor()
    oracle = _cluster(num_shards)
    oracle.ingest_batched(trace[:cut], batch_size=256)
    oracle.resize(num_shards + 2)
    oracle.ingest_batched(trace[cut:], batch_size=256)
    assert rep == oracle.finish()


def test_run_replay_parallel_dispatch():
    trace = _trace(3_000, seed=15)
    serial = run_replay(_cluster(4), trace, batch_size=DEFAULT_BATCH_SIZE)
    parallel = run_replay(_cluster(4), trace, batch_size=DEFAULT_BATCH_SIZE, parallel=True)
    assert parallel.finish() == serial.finish()


def test_executor_start_stop_idempotent():
    cluster = _cluster(2)
    ex = cluster.start_executor()
    assert cluster.start_executor() is ex  # get-or-create
    cluster.stop_executor()
    assert cluster._executor is None
    cluster.stop_executor()  # no-op when detached


def test_shard_worker_error_is_sticky_and_propagates():
    boom = RuntimeError("injected shard fault")

    def fail():
        raise boom

    with ParallelShardExecutor(num_shards=2) as ex:
        ex.submit(0, fail)
        with pytest.raises(ShardWorkerError, match="injected shard fault"):
            ex.barrier()
        # sticky and lane-local: the faulted lane refuses further work...
        with pytest.raises(ShardWorkerError):
            ex.submit(0, lambda: None)
        # ...while healthy lanes keep accepting (one poisoned shard must
        # not strand a half-scattered chunk) — the fault still re-raises
        # at every barrier
        done = []
        ex.submit(1, lambda: done.append(None))
        with pytest.raises(ShardWorkerError, match="injected shard fault"):
            ex.barrier()
        assert done == [None]


def test_barrier_waits_for_all_queued_work():
    done = []
    with ParallelShardExecutor(num_shards=4) as ex:
        for s in range(4):
            for i in range(8):
                ex.submit(s, lambda s=s, i=i: done.append((s, i)))
        ex.barrier()
        assert len(done) == 32
        # per-shard FIFO: each shard's submissions ran in order
        for s in range(4):
            seq = [i for sh, i in done if sh == s]
            assert seq == sorted(seq)


def test_snapshot_mid_queued_gc_converges():
    """``run_gc(wait=False)`` + immediate ``snapshot()``: the snapshot must
    barrier the queued GC steps (as ``resize`` quiesces) so it serializes a
    consistent post-GC barrier state, and the restored continuation
    converges with the uninterrupted run."""
    trace = _overwrite_trace(4_000, seed=31)
    half = len(trace) // 2

    c = _cluster(4)
    c.start_executor()
    c.ingest_batched(trace[:half], batch_size=256, parallel=True)
    c.run_gc(wait=False)  # queued on the worker lanes, not yet drained
    snap = json.loads(json.dumps(c.snapshot()))  # must barrier first
    c.ingest_batched(trace[half:], batch_size=256, parallel=True)
    original = c.finish()
    c.stop_executor()

    resumed = ShardedCluster.restore(snap)
    resumed.ingest_batched(trace[half:], batch_size=256)
    assert resumed.finish() == original


def test_snapshot_thread_races_parallel_ingest_with_queued_gc():
    """Regression (ISSUE 9): a ``snapshot()`` from another thread while the
    coordinator ran ``ingest_batched(parallel=True)`` with ``run_gc(
    wait=False)`` hooks used to serialize mid-mutation — the barrier
    answered even though queued closures were still being enqueued, so
    serialization raced worker-side dict mutation ("dictionary changed
    size during iteration") and could emit torn states.  The coordinator
    lock makes every entry point atomic: the snapshot thread either runs
    before or after a whole coordinator call, never inside one."""
    import threading

    trace = _overwrite_trace(4_000, seed=37)
    c = _cluster(4)
    c.start_executor()
    errors = []
    snaps = []
    stop = threading.Event()

    def snapper():
        while not stop.is_set():
            try:
                snaps.append(json.dumps(c.snapshot()))
            except BaseException as e:  # noqa: BLE001 - the regression signal
                errors.append(e)
                return

    th = threading.Thread(target=snapper)
    th.start()
    try:
        for _ in range(4):
            c.ingest_batched(
                trace, batch_size=256, parallel=True,
                on_chunk=lambda i: c.run_gc(wait=False),
            )
    finally:
        stop.set()
        th.join()
        c.stop_executor()
    assert not errors, f"snapshot raced ingest: {errors[0]!r}"
    # every captured snapshot is a loadable barrier state
    assert snaps
    ShardedCluster.restore(json.loads(snaps[-1]))
    # and the raced run still matches the oracle bit-for-bit
    oracle = _cluster(4)
    oracle.start_executor()
    for _ in range(4):
        oracle.ingest_batched(
            trace, batch_size=256, parallel=True,
            on_chunk=lambda i: oracle.run_gc(wait=False),
        )
    got, want = c.finish(), oracle.finish()
    oracle.stop_executor()
    assert got == want
