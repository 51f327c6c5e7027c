"""fp-index kernels (``kernels/fp_index.py``): the probe's share of its HBM
roofline over the traced window.

Least time is the bytes the probes need over the chip's HBM rate: per key a
device launch answered, its 8 key bytes, the ``WINDOW`` = 16 table slots of 8
bytes it must compare, and a 1-byte answer.  Blocks a kernel stages beyond
that (whole table tiles) do not count.  Kernel time is the summed device
time of the probe programs (``_fp_probe_jit``).  Probes that the index runs
while folding its own pending inserts are in that time but not in the key
count, so the share reads low rather than high.
"""

WINDOW = 16
KEY_BYTES = 8
SLOT_BYTES = 8
ANSWER_BYTES = 1
PROGRAM = "_fp_probe_jit"


def probe_bytes(keys: int) -> int:
    return keys * (KEY_BYTES + WINDOW * SLOT_BYTES + ANSWER_BYTES)


def read(ctx):
    tr, peaks = ctx.get("trace"), ctx.get("peaks")
    if tr is None or peaks is None:
        return None
    seconds = sum(s for name, s in tr.module_s.items() if PROGRAM in name)
    keys = ctx["counters"]["probed_device"]
    if seconds <= 0 or keys <= 0:
        return None
    return 100.0 * probe_bytes(keys) / peaks["hbm_bytes_per_s"] / seconds
