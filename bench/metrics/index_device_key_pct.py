"""Membership index (``core/fp_index.py``): share of probed keys that device
launches answered, over the window, for every index of every shard (the
program's ``probed_device`` and ``probed_host`` counters)."""


def read(ctx):
    c = ctx["counters"]
    total = c["probed_device"] + c["probed_host"]
    return 100.0 * c["probed_device"] / total if total else None
