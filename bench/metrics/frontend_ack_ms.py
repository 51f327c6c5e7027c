"""Front end (``serving/frontend.py``): milliseconds the front end spends
acknowledging a batch's writes (``_on_batch_done``'s delivery loop),
averaged over the window's batches (the program's ``frontend.ack`` spans;
its ``ack_s`` counter times the same loop)."""

from bench import spans


def read(ctx):
    s = spans.of(ctx)
    mean = s.mean_s("frontend.ack") if s is not None else None
    return 1e3 * mean if mean is not None else None
