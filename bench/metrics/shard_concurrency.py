"""Cluster and engines (``core/cluster.py``): how many shards work at once
while the cluster serves a batch: the window's time inside
``shard.write_batch`` spans, summed over the shard threads, over its time
inside ``cluster.write_batch`` (1.0: one shard at a time; 4.0: all four of
a 4-shard cluster for the whole call)."""

from bench import spans


def read(ctx):
    s = spans.of(ctx)
    if s is None:
        return None
    calls = s.total_s.get("dedup.cluster.write_batch", 0.0)
    return s.total_s.get("dedup.shard.write_batch", 0.0) / calls if calls else None
