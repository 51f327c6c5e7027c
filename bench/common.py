"""Pieces every traffic family shares: the window, the profiler, the span
timers around calls into a layer, the program's counters, the device
record and the seeded random streams.

A family (``bench/families/<family>.py``) imports these and brings its own
generator and its run; adding one never edits this module.
"""

from __future__ import annotations

import os
import shutil
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np


@dataclass
class Outcome:
    """What a family's run hands the harness: the end-to-end metrics,
    the context the per-layer readers read, the checks against the plain
    references, and ``info`` for the control (``bench/control.py``)."""

    e2e: Dict[str, float]
    ctx: dict
    checks: list
    attempted: int
    failed: int
    device: dict
    planes: Optional[dict] = None
    info: dict = field(default_factory=dict)


def rng(seed: int, *path: int) -> np.random.Generator:
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.random.default_rng([seed, *path])


def mix64(ids: np.ndarray, key: int) -> np.ndarray:
    """Seeded bijection of uint64 ids (splitmix64 finalizer of id + key)."""
    with np.errstate(over="ignore"):
        z = np.asarray(ids, dtype=np.uint64) + np.uint64(key & 0xFFFFFFFFFFFFFFFF)
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return z ^ (z >> np.uint64(31))


class CompileClock:
    """Backend compilations, timed by a listener on JAX's own event."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.times.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t <= t1)


class Span:
    """Wraps a call into a layer: host seconds and records, and a
    ``bench.<name>`` profiler span around each call."""

    def __init__(self, name: str, fn: Callable, records: Callable = len):
        self.name, self.fn, self.records_of = name, fn, records
        self.seconds = 0.0
        self.records = 0

    def __call__(self, *args, **kwargs):
        from jax.profiler import TraceAnnotation

        t0 = time.perf_counter()
        with TraceAnnotation(f"bench.{self.name}"):
            out = self.fn(*args, **kwargs)
        self.seconds += time.perf_counter() - t0
        self.records += self.records_of(args[0])
        return out


class Profiler:
    """The profiler over the window: started when the window opens, with a
    ``bench.window`` host span from open to close; off when ``out_dir`` is
    None.  Python function tracing stays off."""

    def __init__(self, out_dir: Optional[str]):
        self.out_dir = out_dir
        self._span = None

    def open(self) -> None:
        import jax
        from jax.profiler import TraceAnnotation

        if self.out_dir is not None:
            shutil.rmtree(self.out_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self._span = TraceAnnotation("bench.window")
        self._span.__enter__()

    def close(self) -> None:
        self._span.__exit__(None, None, None)

    def planes(self) -> Optional[dict]:
        """Stop the trace and load it (None when not tracing)."""
        if self.out_dir is None:
            return None
        import glob

        import jax

        from . import trace

        jax.profiler.stop_trace()
        paths = sorted(glob.glob(os.path.join(self.out_dir, "plugins", "profile", "*",
                                              "*.xplane.pb")))
        return trace.load_xplane(paths[-1])


class Window:
    """Opens after ``warmup`` edges, closes at the first edge at least
    ``seconds`` after it opened; calls ``on_open``/``on_close`` there."""

    def __init__(self, seconds: float, warmup: int, on_open, on_close):
        self.seconds, self.warmup = seconds, warmup
        self.on_open, self.on_close = on_open, on_close
        self.edges = 0
        self.t0: Optional[float] = None
        self.t1: Optional[float] = None

    @property
    def closed(self) -> bool:
        return self.t1 is not None

    def edge(self, t: float) -> None:
        if self.closed:
            return
        self.edges += 1
        if self.t0 is None:
            if self.edges > self.warmup:
                self.t0 = t
                self.on_open(t)
        elif t >= self.t0 + self.seconds:
            self.t1 = t
            self.on_close(t)


def make_cluster(cfg: dict):
    """The configuration's ``ShardedCluster``."""
    from repro.core import ShardedCluster

    c = cfg["cluster"]
    return ShardedCluster(num_shards=c["shards"], routing=c["routing"], seed=c["seed"],
                          replication_factor=c["replication_factor"],
                          cache_entries=c["cache_entries_per_shard"])


def _indexes(cluster):
    for e in cluster.shards:
        yield e._seen_fps
        yield e.store.fp_index
        yield e.inline.cache.index


def counters(cluster, fe=None, spans=()) -> Dict[str, float]:
    """The program's counters summed over shards, plus the span timers."""
    out = {"engine_writes": 0, "engine_dups": 0, "engine_inline_dups": 0, "engine_hits": 0,
           "probed_device": 0, "probed_host": 0}
    for e in cluster.shards:
        out["engine_writes"] += e._total_writes
        out["engine_dups"] += e._dup_writes
        out["engine_inline_dups"] += e.inline.metrics.inline_dups
        out["engine_hits"] += e.inline.metrics.cache_hits
    for idx in _indexes(cluster):
        st = idx.table_stats()
        out["probed_device"] += st["probed_device"]
        out["probed_host"] += st["probed_host"]
    if fe is not None:
        out["frontend_records"] = fe.records_executed
        out["frontend_batches"] = fe.batches_executed
    for s in spans:
        out[f"{s.name}_s"] = s.seconds
        out[f"{s.name}_records"] = s.records
    return out


def index_shapes(cluster) -> List[int]:
    """Each index's table capacity: a change means a rebuild (and new
    kernel shapes)."""
    return [idx.table_stats()["capacity"] for idx in _indexes(cluster)]


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def memory_peak() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in jax.devices())


def device(memory_peak_bytes: int) -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
            "memory_peak_bytes": memory_peak_bytes}
