"""fp-index kernels (``kernels/ops.py``): share of the key slots the window's
device launches were padded to (tiles x the largest tile's keys rounded up
to ``TILE_KEYS``) that held a real key: the ``keys`` and ``slots`` stats of
``fp_index.put``, the same numbers as ``table_stats()``'s ``launch_keys``
and ``launch_key_slots``."""

from bench import spans


def read(ctx):
    s = spans.of(ctx)
    slots = s.stat("fp_index.put", "slots") if s is not None else 0
    return 100.0 * s.stat("fp_index.put", "keys") / slots if slots else None
