"""Post-processing (``core/postprocess.py``): the share of the shards'
engine time the exact phase takes: the window's time inside ``post.run``
spans over its time inside ``shard.write_batch`` spans, summed over the
shard threads (a pass runs inside the shard call whose write reached the
period)."""

from bench import spans


def read(ctx):
    s = spans.of(ctx)
    if s is None or "dedup.post.run" not in s.total_s:
        return None
    shards = s.total_s.get("dedup.shard.write_batch", 0.0)
    return 100.0 * s.total_s["dedup.post.run"] / shards if shards else None
