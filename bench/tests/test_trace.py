"""The trace reduction, on a trace recorded from a traced chip run of
vmA.deep (2 s from the start of each window: the device plane's ``XLA Ops`` and
``XLA Modules`` lines and the benchmark's host spans) and on hand-made
planes."""

import gzip
import json
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def _load(name):
    with gzip.open(os.path.join(DATA, name), "rt") as f:
        return json.load(f)


def test_block_writes_chip_trace():
    s = trace.reduce(_load("vmA_deep_trace.json.gz"))
    assert s.devices == 1
    assert s.window_s == pytest.approx(2.0)
    assert 0 < s.busy_s < 0.1 * s.window_s  # the host holds the chip back
    assert set(s.module_s) == {"jit__fp_probe_jit", "jit__fp_insert_jit", "jit__fp_remove_jit"}
    # a program's span holds its operations and the short gaps between them
    assert s.busy_s <= sum(s.module_s.values()) <= s.busy_s * 1.01
    b = s.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0][0] == "ShardedCluster.write_batch"
    gaps = [g for _, g in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(g for _, g in (trace.TraceSummary(0, 0, gaps_ns=s.gaps_ns).breakdown(10 ** 6)
                              ["idle_gaps"])) == pytest.approx(s.window_s - s.busy_s)


def test_reduce_unions_overlaps_clips_to_the_window_and_labels_gaps():
    ms = 1e6
    planes = {
        "/host:CPU": {"main": [("bench.window", 10 * ms, 100 * ms),
                               ("bench.write_batch", 20 * ms, 30 * ms),
                               ("other", 0.0, 200 * ms)]},
        "/device:TPU:0": {
            "XLA Ops": [("a", 0.0, 15 * ms), ("b", 12 * ms, 8 * ms), ("a", 60 * ms, 10 * ms),
                        ("c", 105 * ms, 20 * ms)],
            "XLA Modules": [("jit__f(123)", 0.0, 20 * ms), ("jit__f(456)", 60 * ms, 10 * ms)]},
    }
    s = trace.reduce(planes)
    assert s.window_s == pytest.approx(0.1)
    # busy: [10,20] from the overlapping pair, [60,70], [105,110]
    assert s.busy_s == pytest.approx(0.025)
    assert s.idle_pct == pytest.approx(75.0)
    assert s.module_s == {"jit__f": pytest.approx(0.02)}
    assert s.op_s["a"] == pytest.approx(0.015)
    gaps = s.breakdown()["idle_gaps"]
    assert gaps[0] == ["ShardedCluster.write_batch", pytest.approx(0.04)]
    assert gaps[1] == ["other host work", pytest.approx(0.035)]


def test_no_device_plane_reads_as_no_devices():
    s = trace.reduce({"/host:CPU": {"t": [("bench.window", 0.0, 1e9)]}})
    assert s.devices == 0 and s.busy_s == 0
