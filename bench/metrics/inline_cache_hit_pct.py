"""Cluster and engines: duplicate writes acknowledged as inline cache hits
over the duplicate writes plain membership finds, in the window's batches.

A hit is the fingerprint cache's answer (``InlineMetrics.cache_hits``); the
engines then write a hit anyway where its duplicate run ends below the
spatial threshold, so this share bounds ``inline_dedup_pct`` from above."""


def read(ctx):
    w = ctx.get("writes")
    if not w or not w["duplicates"]:
        return None
    return 100.0 * w["cache_hits"] / w["duplicates"]
