"""Front end (``serving/frontend.py``): writes per executed batch over the
window, from the program's counters ``records_executed`` and
``batches_executed``."""


def read(ctx):
    c = ctx["counters"]
    batches = c.get("frontend_batches", 0)
    return c["frontend_records"] / batches if batches else None
