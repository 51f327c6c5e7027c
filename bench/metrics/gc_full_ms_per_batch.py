"""Cluster and engines (``src/repro/obs.py``'s ``trace_gc``): milliseconds of
CPython's full (generation-2) garbage collections per front-end batch: the
summed duration of the ``dedup.gc.collect`` spans with ``generation`` 2 that
start in the window, over the batches the window executed.  A full pass walks
every container the collector tracks, so this grows with whatever per-key
Python state the engines keep tracked.

The span summary keeps no per-span stats, so this reader loads the run's
trace again, and only where the summary holds a ``gc.collect`` span: a
program without the hook stays silent."""

from bench import spans

SPAN = spans.PREFIX + "gc.collect"


def full_s(planes: dict) -> float:
    """Seconds of generation-2 ``gc.collect`` spans that start in the window
    (planes as ``spans.load`` gives them, events with their stats)."""
    plain = {p: {ln: [e[:3] for e in evs] for ln, evs in lines.items()}
             for p, lines in planes.items()}
    lo, hi = spans._window(plain)
    return 1e-9 * sum(d for p, lines in planes.items() if p.startswith("/host:")
                      for evs in lines.values() for n, s, d, *st in evs
                      if n == SPAN and lo <= s < hi and st and st[0].get("generation") == 2)


def read(ctx):
    s = spans.of(ctx)
    batches = ctx["counters"].get("frontend_batches", 0)
    if s is None or not batches or not s.count.get(SPAN):
        return None
    return 1e3 * full_s(spans.load(spans.newest_trace())) / batches
