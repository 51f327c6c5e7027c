"""Named spans on the served path, on the profiler's clock.

``span(name, **stats)`` returns a JAX profiler ``TraceAnnotation`` named
``dedup.<name>``.  It records nothing unless a profiler trace is running
(``jax.profiler.start_trace``); then each span lands in the trace's
``/host:CPU`` plane, on the line of the thread that opened it and on the
same clock as the device's ``XLA Ops``.  Whether the profiler runs is the
only switch: there is no other recorder, option or flag.

Stats are small ints (``batch``, ``shard``, ``keys`` and the few below);
they land as event stats, never in the name.  A span sits at batch or
launch granularity only, never inside a per-write, per-run or per-key loop:
entering and leaving one costs about a microsecond with no profiler
running, a batch of 32,768 writes opens about a hundred.

``SPANS`` names every span with its layer; the benchmark's reader
(``bench/spans.py``) and the tests hold the program to it.

``trace_gc()`` records each of CPython's cyclic garbage collections as a
``dedup.gc.collect`` span (``generation``, and ``collected`` at its end) on
the thread it interrupts, through one ``gc.callbacks`` hook installed once
per process.  A collection stops every thread, so without its own span it
lands as self time of whichever span happened to be open.
"""

from __future__ import annotations

import gc

PREFIX = "dedup."

SPANS = {
    # serving/frontend.py; batch = the front end's batch number in closing order
    "frontend.fill": "front end",  # event loop, first buffered write to close
    "frontend.close": "front end",  # _flush: list -> arrays, hand-off (keys, slow)
    "frontend.execute": "front end",  # engine thread, around write_batch (queue_us)
    "frontend.ack": "front end",  # _on_batch_done: per-tenant accounting, release, futures (keys)
    # core/cluster.py; batch = the cluster's write_batch call number.  The
    # route..shard spans come from _dispatch, which ingest_batched shares
    # (its shard spans carry batch -1)
    "cluster.write_batch": "cluster and engines",  # coordinator (keys)
    "cluster.route": "cluster and engines",  # _route_chunk
    "cluster.scatter": "cluster and engines",  # ReplayBatch.scatter: per-shard sub-batches
    "cluster.wait": "cluster and engines",  # the _sync barrier: coordinator waits on shards
    "cluster.gather": "cluster and engines",  # flags back to call order
    "shard.write_batch": "cluster and engines",  # worker thread, or inline (shard, keys)
    # core/batch_replay.py
    "engine.prepass": "cluster and engines",  # probes, certify, accumulation, consumes
    "engine.decide": "cluster and engines",  # residual loop and staged store flush
    "engine.boundary": "cluster and engines",  # scalar trigger record (kind: 1 interval, 2 post)
    # core/postprocess.py: one pass (backlog = duplicate rows at its start;
    # merges, reclaimed); core/store.py: the reverse index takes the keys
    # staged since it last ran (keys)
    "post.run": "post-processing",
    "store.reverse": "post-processing",
    # core/fp_index.py; keys = keys probed, inserted, removed or folded
    "fp_index.probe": "membership index",
    "fp_index.insert": "membership index",  # placed = keys the launch placed
    "fp_index.remove": "membership index",
    "fp_index.flush": "membership index",
    # kernels/ops.py: one device launch
    "fp_index.route_keys": "fp-index kernels",  # _route_keys
    "fp_index.put": "fp-index kernels",  # key transfer and dispatch (keys, slots)
    "fp_index.fetch": "fp-index kernels",  # read-back, waiting for the device
    # trace_gc's hook: one of CPython's cyclic collections (generation, collected)
    "gc.collect": "cluster and engines",
}

# engine.boundary kinds (a bit each: both can fall on one record)
BOUNDARY_INTERVAL = 1  # the LDSS estimator's interval ends
BOUNDARY_POST = 2  # a post-processing period ends

def span(name: str, **stats):
    """A ``dedup.<name>`` profiler span; use it as a context manager.  JAX
    is imported here, not with this module, so importing the front end
    alone does not load it."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(PREFIX + name, **stats)


_gc_span = None  # the open dedup.gc.collect span (collections never overlap)


def _on_gc(phase: str, info: dict) -> None:
    global _gc_span
    if phase == "start":
        _gc_span = span("gc.collect", generation=info["generation"])
        _gc_span.__enter__()
    elif _gc_span is not None:
        _gc_span.set_metadata(collected=info["collected"])
        _gc_span.__exit__(None, None, None)
        _gc_span = None


def trace_gc() -> None:
    """Install the ``gc.collect`` span hook; installing it again is a no-op.
    JAX's profiler is imported here, so the hook itself never imports."""
    import jax.profiler  # noqa: F401

    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
