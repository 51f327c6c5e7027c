"""Device: share of the window's idle device time in which no program span
(``dedup.*``) was open on any host thread: idle time the trace cannot
attribute to a layer of the program."""

from bench import spans


def read(ctx):
    s = spans.of(ctx)
    if s is None or not s.threads or s.idle_s <= 0:
        return None
    return 100.0 * s.idle_unattributed_s / s.idle_s
