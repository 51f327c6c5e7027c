"""Chip smoke test: the dedup served path, end to end, on one TPU chip.

Run from the repository root, one process holding one chip:

    python chip_smoke.py

Phases, each checked against a plain reference:

* ``state`` — a 4-shard ``ShardedCluster`` of ``HPDedup`` engines
  (fingerprint routing) populated through ``ingest_batched`` from workload A
  until its all-time seen-set indexes hold >= 8M distinct fingerprints (the
  4 KiB blocks of a 32 GiB primary volume), then >= 256k more workload-A
  writes served through ``AsyncDedupFrontend``.  Reference: every
  ``FingerprintIndex``'s device table, read back, plus its spill equals the
  authoritative host set (``check_consistency``).
* ``exact`` — >= 1M workload-A writes through the front end over a fresh
  cluster.  Reference: the ``HybridReport`` equals (``==``) a fresh
  cluster's scalar ``replay`` of the executed interleaving, which answers
  membership from host sets only.
* ``bytes`` — a >= 256 MiB VM-image fleet through the Pallas
  ``ContentDefinedChunker``, ``batch_from_buffers`` and a cluster.
  Reference: boundaries from the numpy candidates + ``select_boundaries``
  on every buffer, fingerprints from the numpy golden hash on a >= 32 MiB
  sample of chunks, and the cluster report against scalar replay.

Earlier stdout lines give each phase's sizes and informational timings
(host clock, one run, not claims).  The script exits nonzero — printing no
result — if JAX finds no TPU, if a backend resolved to anything but Pallas,
if a phase's device answered no keys, or if any reference comparison
differs.  On success the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""

from __future__ import annotations

import asyncio
import gc
import json
import os
import resource
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

from repro.core import ShardedCluster, generate_workload  # noqa: E402
from repro.core.cdc import ContentDefinedChunker, _candidates_numpy, select_boundaries  # noqa: E402
from repro.core.fingerprint import OP_WRITE, TRACE_DTYPE  # noqa: E402
from repro.core.fp_index import SMALL_BATCH  # noqa: E402
from repro.data.byte_workloads import vm_image_workload  # noqa: E402
from repro.kernels.ops import chunk_fp64  # noqa: E402
from repro.kernels.ref import fingerprint_golden_numpy  # noqa: E402
from repro.serving.frontend import AsyncDedupFrontend  # noqa: E402

SHARDS = 4
BLOCK = 4096
SEEN_FPS = 8 << 20  # 4 KiB blocks of a 32 GiB primary volume
SERVED_WRITES = 256 << 10
EXACT_WRITES = 1 << 20
FLEET_BYTES = 256 << 20
SAMPLE_BYTES = 32 << 20
# Fingerprint-cache entries per shard: 1M in all, 1/8 of the volume's
# blocks.  The LDSS estimator closes an interval every 0.1-0.9 x cache
# entries writes and the batched engine splits its sub-batch there, so
# the cache must be large enough for one interval to hold a whole per-shard
# sub-batch (at the 32768-entry default, workload A's 3276-write intervals
# kept every probe under SMALL_BATCH and the device answered none).
CACHE_ENTRIES = 1 << 18
# Front-end batches whose per-shard sub-batches (~1/SHARDS of a batch)
# carry more unique fingerprints than the index's host-set cutoff, so the
# device answers the probes: workload A repeats fingerprints within a batch,
# ~0.3-0.4 unique per write.
MAX_BATCH = 64 * SMALL_BATCH
# workload A writes ~0.218 distinct fingerprints per request at 37M requests
# (seed 0); generate with a margin, ingest only the prefix that reaches the
# target, and regenerate larger if a trace still falls short
REQUESTS_PER_DISTINCT = 4.75


class SmokeFailure(AssertionError):
    pass


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class _CompileClock:
    """Sums JAX backend-compile seconds (a listener on JAX's own event)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def _indexes(cluster):
    for engine in cluster.shards:
        yield engine._seen_fps
        yield engine.store.fp_index
        yield engine.inline.cache.index


def _index_totals(cluster) -> dict:
    out = {"probed_device": 0, "probed_host": 0, "device_bytes": 0, "backends": set()}
    for idx in _indexes(cluster):
        st = idx.table_stats()
        out["probed_device"] += st["probed_device"]
        out["probed_host"] += st["probed_host"]
        out["device_bytes"] += st["device_bytes"]
        out["backends"].add(st["backend"])
    out["backends"] = sorted(out["backends"] - {"auto"})
    return out


def _writes(trace: np.ndarray) -> np.ndarray:
    return trace[trace["op"] == OP_WRITE]


def _as_trace(streams, lbas, fps) -> np.ndarray:
    trace = np.zeros(len(fps), dtype=TRACE_DTYPE)
    trace["ts"] = np.arange(len(fps))
    trace["stream"] = streams
    trace["op"] = OP_WRITE
    trace["lba"] = lbas
    trace["fp"] = fps
    return trace


async def _serve_async(engine, writes: np.ndarray, max_batch: int, record: bool):
    fe = AsyncDedupFrontend(engine, max_batch=max_batch, max_pending=4 * max_batch,
                            record_trace=record)

    async def conn(tenant, lbas, fps):
        for lba, fp in zip(lbas, fps):
            await fe.write(tenant, fp, lba=lba)

    # enough concurrent connections to fill a batch, split by tenant volume
    conns = 2 * max_batch
    jobs = []
    for tenant in np.unique(writes["stream"]).tolist():
        recs = writes[writes["stream"] == tenant]
        lbas, fps = recs["lba"].tolist(), recs["fp"].tolist()
        c = max(1, conns * len(fps) // len(writes))
        jobs.extend(conn(tenant, lbas[k::c], fps[k::c]) for k in range(c))
    try:
        await asyncio.gather(*jobs)
    finally:
        await fe.close()
    return fe


def _serve(engine, writes, max_batch, record=False):
    return asyncio.run(_serve_async(engine, writes, max_batch, record))


def _timed(clock, fn):
    c0, t0 = clock.seconds, time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0, clock.seconds - c0


def _rates(out: dict, records: int, nbytes: int, wall: float, compile_s: float) -> dict:
    out.update(records=records, bytes=nbytes, wall_s=wall, compile_s=compile_s,
               records_per_s=records / wall, bytes_per_s=nbytes / wall,
               host_peak_rss_gib=resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20)
    return out


def phase_state(clock, distinct: int = SEEN_FPS, served: int = SERVED_WRITES,
                max_batch: int = MAX_BATCH, seed: int = 0) -> dict:
    """Deployment-size state, then served traffic over it."""
    total = int(distinct * REQUESTS_PER_DISTINCT) + 2 * served
    t0 = time.perf_counter()
    while True:
        trace, _ = generate_workload("A", total_requests=total, seed=seed)
        write_pos = np.nonzero(trace["op"] == OP_WRITE)[0]
        _, first = np.unique(trace["fp"][write_pos], return_index=True)
        if first.size > distinct:
            # ingest the shortest prefix writing `distinct` fingerprints,
            # then serve the next `served` writes
            cut = int(write_pos[np.partition(first, distinct - 1)[distinct - 1]]) + 1
            ingest, tail = trace[:cut], _writes(trace[cut:])[:served]
            if tail.size == served:
                break
        total = int(total * 1.1)  # regenerate larger
    gen_wall = time.perf_counter() - t0

    cluster = ShardedCluster(num_shards=SHARDS, cache_entries=CACHE_ENTRIES)
    _, ingest_wall, ingest_compile = _timed(clock, lambda: cluster.ingest_batched(ingest))
    seen = sum(len(e._seen_fps) for e in cluster.shards)
    _check(seen >= distinct, f"seen sets hold {seen} distinct fingerprints, want >= {distinct}")
    before = _index_totals(cluster)
    fe, wall, compile_s = _timed(clock, lambda: _serve(cluster, tail, max_batch))
    after = _index_totals(cluster)
    for idx in _indexes(cluster):
        idx.check_consistency()  # device table read back + spill == host set
    out = {
        "phase": "state", "shards": SHARDS, "cache_entries_per_shard": CACHE_ENTRIES,
        "max_batch": max_batch, "batches": fe.batches_executed, "generate_wall_s": gen_wall,
        "ingested_requests": int(ingest.size),
        "distinct_resident_fps": seen,
        # after serving: table-resident + spilled keys per shard's seen set
        "resident_after_serve_by_shard": [
            e._seen_fps.table_stats()["live"] + e._seen_fps.spilled() for e in cluster.shards],
        "ingest_wall_s": ingest_wall, "ingest_compile_s": ingest_compile,
        "device_table_bytes": after["device_bytes"], "backends": after["backends"],
        "device_answered_keys": after["probed_device"] - before["probed_device"],
        "host_answered_keys": after["probed_host"] - before["probed_host"],
        "ingest_device_answered_keys": before["probed_device"],
        "ingest_host_answered_keys": before["probed_host"],
        "consistent": True,
    }
    return _rates(out, int(tail.size), int(tail.size) * BLOCK, wall, compile_s)


def phase_exact(clock, writes: int = EXACT_WRITES, max_batch: int = MAX_BATCH,
                seed: int = 1) -> dict:
    """Served path over a fresh cluster vs scalar replay of what it ran."""
    trace, _ = generate_workload("A", total_requests=int(writes * 1.15) + 1024, seed=seed)
    served = _writes(trace)[:writes]
    _check(served.size == writes, f"workload A gave {served.size} writes, want {writes}")
    cluster = ShardedCluster(num_shards=SHARDS, cache_entries=CACHE_ENTRIES)
    fe, wall, compile_s = _timed(clock, lambda: _serve(cluster, served, max_batch, record=True))
    report = cluster.finish()
    totals = _index_totals(cluster)
    oracle = ShardedCluster(num_shards=SHARDS, cache_entries=CACHE_ENTRIES)
    oracle.replay(_as_trace(*fe.executed_trace()))
    _check(oracle.finish() == report, "served HybridReport != scalar replay of its interleaving")
    out = {
        "phase": "exact", "shards": SHARDS, "max_batch": max_batch,
        "batches": fe.batches_executed,
        "device_table_bytes": totals["device_bytes"], "backends": totals["backends"],
        "device_answered_keys": totals["probed_device"],
        "host_answered_keys": totals["probed_host"],
        "report_equal": True,
    }
    return _rates(out, writes, writes * BLOCK, wall, compile_s)


def _split_ends(lens: np.ndarray, sizes) -> list:
    """Per-buffer chunk end offsets from the concatenated chunk lengths."""
    out, pos = [], 0
    for size in sizes:
        ends = np.cumsum(lens[pos:], dtype=np.int64)
        n = int(np.searchsorted(ends, size)) + 1 if size else 0
        _check(n == 0 or ends[n - 1] == size, "chunk lengths do not tile the buffer")
        out.append(ends[:n])
        pos += n
    _check(pos == lens.size, "chunks left over after the last buffer")
    return out


def phase_bytes(clock, fleet_bytes: int = FLEET_BYTES, sample_bytes: int = SAMPLE_BYTES,
                backend=None, seed: int = 2) -> dict:
    """VM-image bytes -> CDC (Pallas) -> ReplayBatch -> cluster."""
    streams = 8
    base = fleet_bytes // (4 * streams) + (64 << 10)  # + margin for deletes
    work = vm_image_workload(num_streams=streams, base_size=base, versions=3, seed=seed)
    _check(work.total_bytes >= fleet_bytes, f"fleet is {work.total_bytes} bytes")
    chunker = ContentDefinedChunker(backend=backend)
    cfg = chunker.config
    (batch, lens), wall, compile_s = _timed(
        clock, lambda: chunker.batch_from_buffers(work.stream_ids, work.buffers))

    # boundaries: every buffer against the numpy candidates
    ends_per = _split_ends(lens, [b.size for b in work.buffers])
    for buf, ends in zip(work.buffers, ends_per):
        want = select_boundaries(_candidates_numpy(buf, cfg.avg_size), buf.size,
                                 cfg.min_size, cfg.max_size)
        _check(np.array_equal(ends, want), "CDC boundaries differ from the numpy reference")
    # fingerprints: whole buffers, in order, until the sample holds sample_bytes
    sampled = checked = pos = 0
    for buf, ends in zip(work.buffers, ends_per):
        if sampled < sample_bytes:
            starts = np.concatenate([[0], ends[:-1]])
            mat = np.zeros((ends.size, cfg.max_size), dtype=np.uint8)
            for i, (a, b) in enumerate(zip(starts.tolist(), ends.tolist())):
                mat[i, : b - a] = buf[a:b]
            want = chunk_fp64(fingerprint_golden_numpy(mat.view("<u4")), ends - starts)
            _check(np.array_equal(batch.fp[pos:pos + ends.size], want),
                   "chunk fingerprints differ from the numpy golden hash")
            sampled += buf.size
            checked += ends.size
        pos += ends.size
    _check(sampled >= min(sample_bytes, work.total_bytes), "fingerprint sample too small")

    trace = _as_trace(batch.stream, batch.lba, batch.fp)
    cluster = ShardedCluster(num_shards=SHARDS, cache_entries=CACHE_ENTRIES)
    cluster.ingest_batched(trace)
    report = cluster.finish()
    oracle = ShardedCluster(num_shards=SHARDS, cache_entries=CACHE_ENTRIES)
    oracle.replay(trace)
    _check(oracle.finish() == report, "byte-trace HybridReport != scalar replay")
    totals = _index_totals(cluster)
    out = {
        "phase": "bytes", "buffers": len(work.buffers), "chunks": int(lens.size),
        "cdc_backend": chunker._resolve(), "boundaries_checked_bytes": work.total_bytes,
        "fingerprints_checked_bytes": sampled, "fingerprints_checked_chunks": checked,
        "device_table_bytes": totals["device_bytes"], "backends": totals["backends"],
        "device_answered_keys": totals["probed_device"],
        "host_answered_keys": totals["probed_host"],
        "report_equal": True,
    }
    return _rates(out, int(lens.size), work.total_bytes, wall, compile_s)


def _check_on_device(result: dict) -> None:
    """The phase ran on the chip: Pallas backends, device-answered keys."""
    _check(result["backends"] == ["pallas"],
           f"{result['phase']}: index backends {result['backends']}, want ['pallas']")
    _check(result["device_answered_keys"] > 0, f"{result['phase']}: device answered no keys")
    if "cdc_backend" in result:
        _check(result["cdc_backend"] == "pallas",
               f"bytes: chunker backend {result['cdc_backend']}, want pallas")


def main() -> int:
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is {platform!r})", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = _CompileClock()
    t0 = time.perf_counter()
    for phase in (phase_state, phase_exact, phase_bytes):
        result = phase(clock)
        print(json.dumps(result), flush=True)
        _check_on_device(result)
        gc.collect()  # free the phase's cluster (~28 GiB of host state after `state`)
    print(f"total wall s: {time.perf_counter() - t0}", flush=True)
    print(json.dumps({"ok": True, "device": {"platform": platform,
                                             "kind": devices[0].device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
