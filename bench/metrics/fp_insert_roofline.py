"""fp-index kernels (``kernels/fp_index.py``): the insert's share of its HBM
roofline over the traced window.

Least time is the bytes the inserts need over the chip's HBM rate: per key a
device launch placed, its 8 key bytes, the ``WINDOW`` = 16 table slots of 8
bytes it scans, the 8-byte slot it writes and a 1-byte status.  Blocks a
kernel stages beyond that (whole table tiles) do not count.  Kernel time is
the summed device time of the insert programs (``_fp_insert_jit``); the
placed keys are the ``placed`` stat of the window's ``fp_index.insert``
spans (``table_stats()``'s ``inserted_device``)."""

from bench import spans

WINDOW = 16
KEY_BYTES = 8
SLOT_BYTES = 8
STATUS_BYTES = 1
PROGRAM = "_fp_insert_jit"


def insert_bytes(keys: int) -> int:
    return keys * (KEY_BYTES + WINDOW * SLOT_BYTES + SLOT_BYTES + STATUS_BYTES)


def read(ctx):
    tr, peaks, s = ctx.get("trace"), ctx.get("peaks"), spans.of(ctx)
    if tr is None or peaks is None or s is None:
        return None
    seconds = sum(t for name, t in tr.module_s.items() if PROGRAM in name)
    keys = s.stat("fp_index.insert", "placed")
    if seconds <= 0 or keys <= 0:
        return None
    return 100.0 * insert_bytes(int(keys)) / peaks["hbm_bytes_per_s"] / seconds
