"""A later change adds a configuration, a traffic mix, a traffic family and
a metric as new files and entries; the harness finds each by name with no
edit."""

import json
import shutil

from bench import harness
from bench.tests import tiny


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(f"{harness.ROOT}/BENCHMARK.json", root / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}

    cfg = json.loads((root / "bench/configs/vmA-8GiB.json").read_text())
    cfg["name"] = "vmC-test"
    cfg["tenants"]["ftp"]["count"], cfg["tenants"]["mail"]["count"] = 15, 5
    (root / "bench/configs/vmC-test.json").write_text(json.dumps(cfg))
    traffic = json.loads((root / "bench/traffic/deep.json").read_text())
    traffic["in_flight"] = 1024
    (root / "bench/traffic/shallow.json").write_text(json.dumps(traffic))
    (root / "bench/metrics/batches_in_window.py").write_text(
        "def read(ctx):\n    return ctx['counters']['frontend_batches']\n")

    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "vmC-test", "source": "test", "why": "test", "reduced": [],
                             "file": "bench/configs/vmC-test.json"})
    bench["workloads"].append({"name": "vmC.shallow", "config": "vmC-test",
                               "traffic": "shallow", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "batches_in_window", "unit": "batches",
                               "better": "higher", "source": "program_counter",
                               "layer": "front end", "moves": "writes_per_s",
                               "workloads": ["vmC.shallow"]})
    for m in bench["end_to_end"]:
        if "vmA.deep" in m.get("workloads", []):
            m["workloads"].append("vmC.shallow")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    # nothing that was there changed, apart from the benchmark's entries
    after = {p: p.read_bytes() for p in before}
    assert {p for p in before if before[p] != after[p]} == {root / "BENCHMARK.json"}

    b = harness.load_benchmark(str(root))
    assert harness.load_config(b, "vmC-test", str(root))["tenants"]["ftp"]["count"] == 15
    assert harness.load_traffic("shallow", str(root))["in_flight"] == 1024
    r = tiny.run("vmC.shallow", trace=True, trace_dir=str(tmp_path / "tr"), root=str(root))
    assert r["correct"], r["checks"]
    assert r["metrics"]["batches_in_window"]["value"] > 0
    assert "frontend_batch_writes" not in r["metrics"]  # vmA.deep's metric, not listed here


# a family that writes the generator's block writes straight through
# ShardedCluster.write_batch in fixed batches, with no front end
DIRECT_FAMILY = """
import time

import numpy as np

from bench import common, harness, reference


def run(cfg, traffic, seed, seconds, trace_dir, log, t_start):
    gen = harness.load_family("block_writes")
    aged = gen.aged_trace(cfg["tenants"], int(cfg["aged_distinct_fingerprints"]),
                          int(traffic["supply_writes"]), seed, float(cfg["requests_per_distinct"]))
    cluster = common.make_cluster(cfg)
    cluster.ingest_batched(aged.aged)
    fps = np.concatenate(aged.supply_fp)
    lbas = np.concatenate(aged.supply_lba)
    streams = np.concatenate([np.full(f.size, i) for i, f in enumerate(aged.supply_fp)])
    span = common.Span("write_batch", cluster.write_batch)
    base = common.counters(cluster, spans=[span])
    snaps = {}
    window = common.Window(seconds, 1, lambda t: snaps.setdefault("open", common.counters(
        cluster, spans=[span])), lambda t: snaps.setdefault("close", common.counters(
        cluster, spans=[span])))
    b, done, flags = int(traffic["batch"]), 0, []
    window.edge(time.perf_counter())
    while not window.closed:
        flags.append(span(streams[done:done + b], lbas[done:done + b], fps[done:done + b]))
        done += b
        window.edge(time.perf_counter())
    d = common.delta(common.counters(cluster, spans=[span]), base)
    checks = reference.membership_checks(aged.aged_fps, fps[:done], np.concatenate(flags),
                                         d["engine_writes"], d["engine_dups"], d["engine_hits"])
    win = common.delta(snaps["close"], snaps["open"])
    e2e = {"writes_per_s": win["write_batch_records"] / (window.t1 - window.t0),
           "setup_s": window.t0 - t_start}
    return common.Outcome(e2e, {"counters": win}, checks, attempted=done, failed=0,
                          device=common.device(common.memory_peak()))


def shrink(cfg, traffic):
    cfg["cluster"]["cache_entries_per_shard"] = 1024
    cfg["aged_distinct_fingerprints"] = 8000
"""


def test_new_traffic_family_is_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(f"{harness.ROOT}/BENCHMARK.json", root / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}

    (root / "bench/families/direct_writes.py").write_text(DIRECT_FAMILY)
    (root / "bench/traffic/direct.json").write_text(json.dumps(
        {"family": "direct_writes", "batch": 4096, "supply_writes": 400000}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "vmA.direct", "config": "vmA-8GiB", "traffic": "direct",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("writes_per_s", "engine_us_per_write"):
            m["workloads"].append("vmA.direct")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = {p: p.read_bytes() for p in before}
    assert {p for p in before if before[p] != after[p]} == {root / "BENCHMARK.json"}

    r = tiny.run("vmA.direct", seed=2**31 + 5, root=str(root))
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"writes_per_s", "setup_s"}
    r = tiny.run("vmA.direct", trace=True, trace_dir=str(tmp_path / "tr"), root=str(root))
    assert r["correct"] and r["metrics"]["engine_us_per_write"]["value"] > 0
