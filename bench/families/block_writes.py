"""Traffic family ``block_writes``: 4 KiB block writes from many VM disks
through ``AsyncDedupFrontend.write`` over a ``ShardedCluster`` whose state
was aged through ``ingest_batched``.

The generator draws multi-tenant block I/O in the shape of HPDedup
(arXiv:1702.08153) section V-A.  The per-template statistics and stream
mixes come from the configuration file (copied from the program's
``core/traces.py``); the generative model is the same (run-level
read/duplicate/fresh choices, geometric or uniform back-distance of a
duplicate run, per-template shared pools for cross-stream overlap,
exponential arrivals), drawn in bulk per stream instead of per request.
Block ids become 64-bit fingerprints through a seeded bijective mix, so keys
are spread like content hashes and never collide.

A run is a closed loop: each disk keeps a fixed number of writes
outstanding.  The window opens and closes on the completion of a front-end
batch, so it holds whole batches.  After it closes, with the chip's memory
peak read, every acknowledged write since the aged state is checked against
plain membership (``bench/reference.py``).

Traffic file keys: ``in_flight``, ``supply_writes``, ``warmup_batches``.
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import common, reference

# record layout of the program's core.fingerprint.TRACE_DTYPE
TRACE_DTYPE = np.dtype(
    [("ts", np.int64), ("stream", np.int32), ("op", np.int8), ("lba", np.int64), ("fp", np.uint64)]
)
OP_WRITE, OP_READ = 0, 1
_READ, _DUP, _FRESH = 0, 1, 2
CONTROL_BITS = 32


# ---------------------------------------------------------------------------
# generator
# ---------------------------------------------------------------------------


def _run_probs(t: dict) -> Tuple[float, float, float]:
    """Run-level probabilities hitting the template's per-block targets
    (the same algebra as ``core/traces.py``)."""
    wr, lr = t["write_ratio"], t["read_run_mean"]
    r, ld, lf = t["dup_ratio"], t["dup_run_mean"], t["write_run_mean"]
    q_dup = r * lf / (ld * (1.0 - r) + r * lf)
    lw = q_dup * ld + (1.0 - q_dup) * lf
    q_read = (1.0 - wr) * lw / (wr * lr + (1.0 - wr) * lw)
    return q_read, q_dup, q_read * lr + (1.0 - q_read) * lw


def _draw_runs(rng, t: dict, n: int):
    """Run kinds and lengths covering ``n`` requests; the first run writes
    fresh blocks (a stream with no history can neither read nor repeat)."""
    q_read, q_dup, mean_run = _run_probs(t)
    m = int(n / mean_run * 1.2) + 64
    while True:
        u = rng.random((3, m))
        kind = np.where(u[0] < q_read, _READ, np.where(u[1] < q_dup, _DUP, _FRESH))
        kind[0] = _FRESH
        mean = np.choose(kind, [t["read_run_mean"], t["dup_run_mean"], t["write_run_mean"]])
        length = rng.geometric(1.0 / mean)
        if int(length.sum()) >= n:
            return kind, length.astype(np.int64), u[2]
        m *= 2


def _stream(rng, t: dict, n: int, overlap: float, pool: np.ndarray, next_id: int):
    """One stream's requests: (ts, op, lba, ids), ids valid for writes.

    Fresh non-pool blocks take ids ``next_id, next_id + 1, ...``; returns
    the next free id too.
    """
    kind, length, u_pool = _draw_runs(rng, t, n)
    is_w = kind != _READ
    # a duplicate run never reaches past the history written before it
    while True:
        wlen = np.where(is_w, length, 0)
        hist = np.cumsum(wlen) - wlen
        over = (kind == _DUP) & (length > hist)
        if not over.any():
            break
        length[over] = np.maximum(hist[over], 1)
        kind[over & (hist == 0)] = _FRESH
    # cut the last run at n requests
    ends = np.cumsum(length)
    m = int(np.searchsorted(ends, n)) + 1
    kind, length, u_pool, hist = kind[:m], length[:m].copy(), u_pool[:m], hist[:m]
    length[-1] -= int(ends[m - 1]) - n
    is_w = kind != _READ

    # back-distance of each duplicate run (geometric, or uniform over history)
    geometric = t["locality"] == "geometric"
    back = rng.geometric(1.0 / max(t["locality_scale"], 1.0), m) if geometric \
        else np.zeros(m, dtype=np.int64)
    uni = length + np.floor(rng.random(m) * (hist - length + 1)).astype(np.int64)
    back = np.where((back + length > hist) | (not geometric), uni, back)
    src = np.maximum(hist - back, 0)
    read_start = np.floor(rng.random(m) * np.maximum(hist, 1)).astype(np.int64)
    use_pool = rng.random(m) < overlap
    pool_start = np.floor(u_pool * np.maximum(1, pool.size - length)).astype(np.int64)

    # per request
    run = np.repeat(np.arange(m), length)
    j = np.arange(n, dtype=np.int64) - np.repeat(np.cumsum(length) - length, length)
    req_w = is_w[run]
    op = np.where(req_w, OP_WRITE, OP_READ).astype(np.int8)
    lba = np.where(req_w, hist[run] + j, read_start[run] + j)
    t_run = np.cumsum(rng.exponential(1.0 / t["rate"], m))
    ts = (t_run[run] * 1e6).astype(np.int64) + np.arange(n, dtype=np.int64)

    # writes in order: resolve ids (duplicates point at an earlier write)
    wrun, wj = run[req_w], j[req_w]
    nw = wrun.size
    wkind = kind[wrun]
    dup = wkind == _DUP
    ptr = np.arange(nw, dtype=np.int64)
    ptr[dup] = np.minimum(src[wrun[dup]] + wj[dup], hist[wrun[dup]] - 1)
    while True:
        nxt = ptr[ptr]
        if np.array_equal(nxt, ptr):
            break
        ptr = nxt
    base = np.zeros(nw, dtype=np.uint64)
    pooled = (~dup) & use_pool[wrun]
    if pool.size:
        base[pooled] = pool[np.minimum(pool_start[wrun[pooled]] + wj[pooled], pool.size - 1)]
    fresh = (~dup) & ~pooled
    nf = int(fresh.sum())
    base[fresh] = np.arange(next_id, next_id + nf, dtype=np.uint64)
    ids = np.zeros(n, dtype=np.uint64)
    ids[req_w] = base[ptr]
    return ts, op, lba, ids, next_id + nf


def block_trace(tenants: Dict[str, dict], total_requests: int, seed: int,
                overlap_range=(0.0, 0.4)) -> Tuple[np.ndarray, List[str]]:
    """Merged multi-stream trace (``TRACE_DTYPE``, arrival order) and each
    stream's template name.  ``tenants`` maps template name to its
    statistics plus ``count`` (streams of that template)."""
    streams = [(name, t) for name, t in tenants.items() for _ in range(int(t["count"]))]
    total_rate = sum(t["rate"] for _, t in streams)
    rng = common.rng(seed, 0)
    key = int(rng.integers(0, 1 << 63))
    # each template's streams take evenly spaced content overlaps across the
    # range, in a seeded order: every seed gets the same set of overlaps, so
    # the seed moves the work only by its random draws, not by its shares
    lo, hi = overlap_range
    overlaps = []
    for t in tenants.values():
        c = int(t["count"])
        overlaps.extend(rng.permutation(lo + (hi - lo) * (np.arange(c) + 0.5) / c).tolist())
    pools: Dict[str, np.ndarray] = {}
    next_id = 1
    parts = []
    for sid, (name, t) in enumerate(streams):
        n = max(64, int(total_requests * t["rate"] / total_rate))
        overlap = overlaps[sid]
        if name not in pools:
            size = max(1024, n // 4)
            pools[name] = np.arange(next_id, next_id + size, dtype=np.uint64)
            next_id += size
        ts, op, lba, ids, next_id = _stream(common.rng(seed, 1, sid), t, n, overlap,
                                            pools[name], next_id)
        rec = np.zeros(n, dtype=TRACE_DTYPE)
        rec["ts"], rec["stream"], rec["op"], rec["lba"] = ts, sid, op, lba
        rec["fp"] = np.where(op == OP_WRITE, common.mix64(ids, key), np.uint64(0))
        parts.append(rec)
    trace = np.concatenate(parts)
    trace = trace[np.argsort(trace["ts"], kind="stable")]
    return trace, [name for name, _ in streams]



@dataclass
class AgedTrace:
    """A trace split at the point where its writes reach ``distinct``
    fingerprints: ``aged`` (all requests before) and, per stream, the
    writes after it that the window serves in order."""

    aged: np.ndarray
    supply_lba: List[np.ndarray]
    supply_fp: List[np.ndarray]
    templates: List[str]

    @property
    def aged_fps(self) -> np.ndarray:
        """Sorted distinct fingerprints the aged state holds."""
        return np.unique(self.aged["fp"][self.aged["op"] == OP_WRITE])


def aged_trace(tenants: Dict[str, dict], distinct: int, supply_writes: int, seed: int,
               requests_per_distinct: float, overlap_range=(0.0, 0.4)) -> AgedTrace:
    """Generate until the writes hold ``distinct`` fingerprints with at
    least ``supply_writes`` writes left after the cut (regenerating larger
    when a trace falls short)."""
    total = int(distinct * requests_per_distinct + supply_writes * 1.15)
    while True:
        trace, templates = block_trace(tenants, total, seed, overlap_range)
        write_pos = np.nonzero(trace["op"] == OP_WRITE)[0]
        _, first = np.unique(trace["fp"][write_pos], return_index=True)
        if first.size > distinct:
            cut = int(write_pos[np.partition(first, distinct - 1)[distinct - 1]]) + 1
            tail = trace[cut:]
            tail = tail[tail["op"] == OP_WRITE]
            if tail.size >= supply_writes:
                by_stream = [tail[tail["stream"] == s] for s in range(len(templates))]
                return AgedTrace(trace[:cut], [b["lba"].copy() for b in by_stream],
                                 [b["fp"].copy() for b in by_stream], templates)
        total = int(total * 1.15)


def depths(cfg: dict, templates: List[str], in_flight: int) -> List[int]:
    """Outstanding writes per disk, proportional to its stream's request
    rate, summing to ``in_flight`` (largest remainders), at least 1 each."""
    rates = np.array([cfg["tenants"][t]["rate"] for t in templates], dtype=float)
    share = in_flight * rates / rates.sum()
    d = np.maximum(np.floor(share).astype(int), 1)
    for i in np.argsort(-(share - np.floor(share)))[: max(0, in_flight - int(d.sum()))]:
        d[i] += 1
    return d.tolist()


# ---------------------------------------------------------------------------
# run
# ---------------------------------------------------------------------------


async def _closed_loop(fe, supply_lba, supply_fp, disk_depths, window: common.Window,
                       rec: dict):
    """Every disk keeps its depth of writes outstanding until the window
    closes; each write is timed from the call to its acknowledgement and
    tagged with the front-end batch that completed it."""
    from jax.profiler import TraceAnnotation

    loop = asyncio.get_running_loop()
    cursor = [0] * len(supply_fp)
    state = {"n": 0, "batch": -1, "exhausted": False}
    t_call, t_ack, flags, fps, batch = rec["t_call"], rec["t_ack"], rec["flag"], rec["fp"], \
        rec["batch"]

    def on_ack(t: float) -> int:
        b = fe.batches_executed  # the batch this acknowledgement completed
        if b != state["batch"]:
            state["batch"] = b
            window.edge(t)
            if window.t0 is not None and not window.closed:
                span = TraceAnnotation("bench.frontend")
                span.__enter__()
                loop.call_soon(span.__exit__, None, None, None)
        return b

    async def disk(d: int) -> None:
        lbas, fpd = supply_lba[d], supply_fp[d]
        while not window.closed:
            k = cursor[d]
            if k >= fpd.size:
                state["exhausted"] = True
                return
            cursor[d] = k + 1
            i = state["n"]
            state["n"] = i + 1
            fps[i] = fpd[k]
            t0 = time.perf_counter()
            flag = await fe.write(d, int(fpd[k]), lba=int(lbas[k]))
            t1 = time.perf_counter()
            t_call[i], t_ack[i], flags[i] = t0, t1, flag
            batch[i] = on_ack(t1)

    tasks = [asyncio.create_task(disk(d)) for d, n in enumerate(disk_depths) for _ in range(n)]
    try:
        results = await asyncio.gather(*tasks, return_exceptions=True)
    finally:
        await fe.close()
    errors = [r for r in results if isinstance(r, BaseException)]
    if state["exhausted"] and not errors:
        raise RuntimeError("a disk ran out of generated writes: raise the traffic's supply_writes")
    return state["n"], errors


def run(cfg: dict, traffic: dict, seed: int, seconds: float, trace_dir: Optional[str], log,
        t_start: float) -> common.Outcome:
    from repro.serving.frontend import AsyncDedupFrontend

    clock = common.CompileClock()
    t = time.perf_counter()
    aged = aged_trace(cfg["tenants"], int(cfg["aged_distinct_fingerprints"]),
                      int(traffic["supply_writes"]), seed, float(cfg["requests_per_distinct"]),
                      tuple(cfg["overlap_range"]))
    aged_fps = aged.aged_fps
    t_gen = time.perf_counter() - t
    cluster = common.make_cluster(cfg)
    t = time.perf_counter()
    cluster.ingest_batched(aged.aged)
    t_ingest = time.perf_counter() - t
    log(f"set-up: generated {aged.aged.size} aged requests ({aged_fps.size} distinct "
        f"fingerprints) in {t_gen} s, ingested in {t_ingest} s; index capacities "
        f"{common.index_shapes(cluster)}")

    f = cfg["frontend"]
    fe = AsyncDedupFrontend(cluster, max_batch=f["max_batch"], max_delay=f["max_delay_s"],
                            max_pending=f["max_pending"], admission_control=f["admission_control"])
    span = common.Span("write_batch", cluster.write_batch)
    cluster.write_batch = span  # the front end calls it on its engine thread
    base = common.counters(cluster, fe, [span])
    total = sum(s.size for s in aged.supply_fp)
    rec = {"t_call": np.zeros(total), "t_ack": np.zeros(total),
           "flag": np.zeros(total, dtype=bool), "fp": np.zeros(total, dtype=np.uint64),
           "batch": np.full(total, -1, dtype=np.int64)}
    prof = common.Profiler(trace_dir)
    snaps = {}

    # the window's edges are first acknowledgements of a batch: the engine
    # thread has applied that batch and holds no other, so the counters read
    # there cover whole batches
    def on_open(t0):
        snaps["open"] = common.counters(cluster, fe, [span])
        prof.open()

    def on_close(t1):
        prof.close()
        snaps["close"] = common.counters(cluster, fe, [span])

    window = common.Window(seconds, int(traffic["warmup_batches"]), on_open, on_close)
    disk_depths = depths(cfg, aged.templates, int(traffic["in_flight"]))
    n, errors = asyncio.run(_closed_loop(fe, aged.supply_lba, aged.supply_fp, disk_depths,
                                         window, rec))
    planes = prof.planes()
    peak = common.memory_peak()
    if errors:
        raise errors[0]
    if not window.closed:
        raise RuntimeError("the window never closed")
    t0, t1 = window.t0, window.t1
    log(f"window: {t1 - t0} s, compiles in window: {clock.between(t0, t1)}, index capacities "
        f"{common.index_shapes(cluster)}")

    # reference: every acknowledged write since the aged state, in call order
    after = common.counters(cluster, fe, [span])
    done = rec["t_ack"][:n] > 0
    fps, flags = rec["fp"][:n][done], rec["flag"][:n][done]
    d = common.delta(after, base)
    checks = reference.membership_checks(aged_fps, fps, flags, d["engine_writes"],
                                         d["engine_dups"], d["engine_hits"])
    dup = reference.truly_duplicate(aged_fps, fps)
    t_ack, batch = rec["t_ack"][:n][done], rec["batch"][:n][done]
    in_win = (t_ack >= t0) & (t_ack < t1)
    lat = (t_ack - rec["t_call"][:n][done])[in_win]
    win = common.delta(snaps["close"], snaps["open"])
    # the batches whose engine work falls between the two counter readings
    applied = (batch > snaps["open"]["frontend_batches"]) & \
        (batch <= snaps["close"]["frontend_batches"])
    ref_dups = int(dup[applied].sum())
    log(f"window write latency ms p50/p80/p90/p99/max: "
        f"{[float(np.percentile(lat, q)) * 1e3 for q in (50, 80, 90, 99, 100)]}, "
        f"batches {win['frontend_batches']}, duplicates {ref_dups}, removed inline "
        f"{win['engine_inline_dups']}, acknowledged as cache hits {int(flags[applied].sum())}")
    e2e = {
        "writes_per_s": float(in_win.sum()) / (t1 - t0),
        # duplicate writes the engines removed inline over those plain
        # membership finds in the same batches (HPDedup's Fig. 6 quantity)
        "inline_dedup_pct": 100.0 * win["engine_inline_dups"] / ref_dups,
        "setup_s": t0 - t_start,
    }
    ctx = {"window_s": t1 - t0, "counters": win,
           "writes": {"latency_s": lat, "duplicates": ref_dups,
                      "cache_hits": int(flags[applied].sum())}}
    return common.Outcome(e2e, ctx, checks, attempted=n, failed=int((~done).sum()),
                          device=common.device(peak), planes=planes,
                          info={"aged_fps": aged_fps, "fps": fps})


def control(cfg: dict, info: dict, seed: int, bits: int = CONTROL_BITS) -> list:
    """The control: membership keyed by the low ``bits`` of each fingerprint
    (an index that stores half-width keys) counts the duplicate writes; it
    reads as ``dup_count_gap`` against exact membership."""
    exact = int(reference.truly_duplicate(info["aged_fps"], info["fps"]).sum())
    approx = reference.truncated_dup_count(info["aged_fps"], info["fps"], bits)
    return [reference.Check("dup_count_gap", abs(approx - exact), 0)]


def shrink(cfg: dict, traffic: dict) -> None:
    """Sizes a CPU test run can hold; every other setting stays."""
    cfg["cluster"]["cache_entries_per_shard"] = 1024
    cfg["aged_distinct_fingerprints"] = 12000
    cfg["frontend"].update(max_batch=2048, max_pending=8192)
    traffic.update(in_flight=2048, supply_writes=120000, warmup_batches=2)
