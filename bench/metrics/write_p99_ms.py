"""Front end (``serving/frontend.py``): the 99th percentile of write latency,
from the client's call to its acknowledgement, over every write acknowledged
in the window (``np.percentile``'s linear rule, as
``benchmarks/serving_latency.py``).

In a closed loop whose in-flight count fills every batch, a batch's writes
share one cycle and a window holds some tens of cycles, so this is close to
the slowest cycle of the window: it shows a stall, and swings with it."""

import numpy as np


def read(ctx):
    w = ctx.get("writes")
    if not w or len(w["latency_s"]) == 0:
        return None
    return float(np.percentile(w["latency_s"], 99)) * 1e3
