"""Per-layer readers: the bytes each roofline counts, the peaks table, and
silence where there is nothing to read."""

import pytest

from bench import harness, trace


def test_roofline_byte_functions_count_the_work_not_the_staging():
    probe = harness.load_metric("fp_probe_roofline")
    mod = probe.__globals__
    assert mod["probe_bytes"](1) == 8 + 16 * 8 + 1
    assert mod["probe_bytes"](3000) == 3000 * 137


def _summary(module_s, busy=0.5, window=2.0):
    return trace.TraceSummary(window_s=window, busy_s=busy, module_s=module_s, devices=1)


def test_rooflines_from_a_summary():
    peaks = harness.load_peaks("TPU v5 lite")
    ctx = {"counters": {"probed_device": 819_000_000 // 137}, "peaks": peaks,
           "trace": _summary({"jit__fp_probe_jit": 0.002})}
    # 819e6 bytes over 819 GB/s is 1 ms of least time in 2 ms of kernel time
    assert harness.load_metric("fp_probe_roofline")(ctx) == pytest.approx(50.0, rel=1e-3)
    assert harness.load_metric("device_idle_pct.served")(ctx) == pytest.approx(75.0)


def test_readers_return_nothing_without_a_trace_or_kernel_time():
    ctx = {"counters": {"probed_device": 10, "probed_host": 0}, "trace": None, "peaks": None,
           "writes": {"latency_s": [], "duplicates": 0, "cache_hits": 0}}
    for name in ("fp_probe_roofline", "device_idle_pct.served", "write_p99_ms",
                 "inline_cache_hit_pct"):
        assert harness.load_metric(name)(ctx) is None
    ctx["trace"] = _summary({})
    ctx["peaks"] = harness.load_peaks("TPU v5 lite")
    assert harness.load_metric("fp_probe_roofline")(ctx) is None


def test_write_tail_and_cache_hit_share():
    lat = [0.001 * k for k in range(1, 101)]  # 1..100 ms
    ctx = {"writes": {"latency_s": lat, "duplicates": 400, "cache_hits": 300}}
    assert harness.load_metric("write_p99_ms")(ctx) == pytest.approx(99.01)
    assert harness.load_metric("inline_cache_hit_pct")(ctx) == pytest.approx(75.0)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        harness.load_peaks("source")
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_every_metric_in_the_benchmark_has_a_reader_and_every_cell_reports_enough():
    bench = harness.load_benchmark()
    for m in bench["per_layer"]:
        assert callable(harness.load_metric(m["name"]))
        e2e = {e["name"]: e for e in bench["end_to_end"]}
        assert set(m["workloads"]) <= set(e2e[m["moves"]].get("workloads", m["workloads"]))
    for cell in bench["workloads"]:
        e2e = [m["name"] for m in harness.metrics_of(bench, cell["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.metrics_of(bench, cell["name"], "per_layer")
