"""Front end (``serving/frontend.py``): milliseconds a batch spends filling,
from its first buffered write to its close, averaged over the batches that
start filling in the window (the program's ``frontend.fill`` spans; its
``fill_s`` counter times the same stretch)."""

from bench import spans


def read(ctx):
    s = spans.of(ctx)
    mean = s.mean_s("frontend.fill") if s is not None else None
    return 1e3 * mean if mean is not None else None
