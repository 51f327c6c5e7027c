"""Post-processing (``core/postprocess.py``): milliseconds per pass of the
exact phase, the mean ``post.run`` span that starts in the window, over
every shard thread.  Silent where no pass starts in the window or the
program has no such span."""

from bench import spans


def read(ctx):
    s = spans.of(ctx)
    mean = s.mean_s("post.run") if s is not None else None
    return 1e3 * mean if mean is not None else None
