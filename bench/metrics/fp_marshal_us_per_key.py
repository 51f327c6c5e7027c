"""Membership index (``kernels/ops.py``, ``core/fp_index.py``): host
microseconds per key that a device launch carries, spent around the kernel:
routing keys to their home tiles (``fp_index.route_keys``), shipping them
and dispatching (``fp_index.put``) and reading the answer back, which
includes waiting for the device (``fp_index.fetch``).  Self time of the
three over the window, over the keys of the launches that start in it (the
``keys`` stat of ``fp_index.put``)."""

from bench import spans


def read(ctx):
    s = spans.of(ctx)
    keys = s.stat("fp_index.put", "keys") if s is not None else 0
    if not keys:
        return None
    return 1e6 * s.self_of("fp_index.route_keys", "fp_index.put", "fp_index.fetch") / keys
