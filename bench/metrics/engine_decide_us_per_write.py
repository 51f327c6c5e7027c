"""Cluster and engines (``core/batch_replay.py``): host microseconds per
write in the engines' residual decision loop (``engine.decide``: run
decisions, cache admissions and evictions, the staged store flush), self
time over every shard thread in the window, over the writes the engines
applied there.  The index's own spans nested inside are not counted here."""

from bench import spans


def read(ctx):
    s = spans.of(ctx)
    writes = ctx["counters"].get("engine_writes", 0)
    if s is None or not writes or "dedup.engine.decide" not in s.self_s:
        return None
    return 1e6 * s.self_of("engine.decide") / writes
