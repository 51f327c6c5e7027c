"""vmA.deep end to end at a tiny size on the CPU, through the harness."""

import numpy as np

from bench.tests import tiny


def test_vmA_deep_runs_correct_and_reports_its_metrics():
    r = tiny.run("vmA.deep")
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"writes_per_s", "inline_dedup_pct", "setup_s"}
    assert all(np.isfinite(m["value"]) and m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == {"applied_gap", "dup_count_gap", "flag_report_gap",
                                "false_inline"}


def test_vmA_deep_traced_reports_per_layer_metrics(tmp_path):
    r = tiny.run("vmA.deep", seed=7, trace=True, trace_dir=str(tmp_path / "trace"))
    assert r["correct"], r["checks"]
    m = r["metrics"]
    # the CPU has no device plane and no peaks: the trace readers stay silent
    assert "fp_probe_roofline" not in m and "device_idle_pct.served" not in m
    assert m["frontend_batch_writes"]["value"] == 2048
    assert m["engine_us_per_write"]["value"] > 0
    assert m["write_p99_ms"]["value"] > 0
    # a cache hit is not yet a removed duplicate: hits bound inline dedup
    assert m["inline_cache_hit_pct"]["value"] >= 0
    assert "busy_s" in r["device"] and r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_depths_follow_stream_rates_and_sum_to_in_flight():
    from bench import harness

    _, cfg, traffic = tiny.cell_files("vmA.deep")
    templates = [t for t, spec in cfg["tenants"].items() for _ in range(spec["count"])]
    d = harness.load_family("block_writes").depths(cfg, templates, traffic["in_flight"])
    assert sum(d) == traffic["in_flight"] == 32768
    rates = {t: cfg["tenants"][t]["rate"] for t in cfg["tenants"]}
    by = {t: [x for x, tt in zip(d, templates) if tt == t] for t in rates}
    assert max(by["mail"]) - min(by["mail"]) <= 1
    assert min(by["mail"]) > max(by["home"]) > max(by["web"]) >= 1


def test_inline_dedup_counts_removed_duplicates_over_the_same_batches():
    """The window's counter readings and the reference's duplicates cover
    the same batches: the engines' duplicate count equals plain
    membership's there, and inline dedup is removed duplicates over it."""
    import time

    from bench import harness

    _, cfg, traffic = tiny.cell_files("vmA.deep")
    tiny.shrink(cfg, traffic)
    fam = harness.load_family("block_writes")
    o = fam.run(cfg, traffic, 2147483701, 1.0, None, lambda m: None, time.perf_counter())
    win, w = o.ctx["counters"], o.ctx["writes"]
    assert win["engine_dups"] == w["duplicates"] > 0
    assert o.e2e["inline_dedup_pct"] == 100.0 * win["engine_inline_dups"] / w["duplicates"]
    assert win["engine_hits"] == w["cache_hits"]
