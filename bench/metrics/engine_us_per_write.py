"""Cluster and engines: host microseconds inside ``ShardedCluster.write_batch``
per block write over the window, timed by the benchmark's wrapper."""


def read(ctx):
    c = ctx["counters"]
    n = c.get("write_batch_records", 0)
    return 1e6 * c["write_batch_s"] / n if n else None
