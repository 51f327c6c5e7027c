"""The per-layer readers of the program's spans: one case per reader on a
hand-built summary, the case whose denominator is 0, and silence on a trace
without program spans (a program that opens none)."""

import gzip
import json
import os

import pytest

from bench import harness, spans, trace

P = spans.PREFIX


def _spans(**kw):
    base = dict(window_s=2.0, busy_s=0.5)
    base.update(kw)
    return spans.ProgramSpans(**base)


def _full():
    return _spans(
        threads=6,
        total_s={P + "cluster.write_batch": 1.0, P + "shard.write_batch": 2.5},
        self_s={P + "engine.prepass": 0.3, P + "engine.decide": 0.6,
                P + "fp_index.route_keys": 0.1, P + "fp_index.put": 0.2,
                P + "fp_index.fetch": 0.3},
        count={P + "frontend.fill": 4, P + "frontend.ack": 5},
        span_s={P + "frontend.fill": 0.8, P + "frontend.ack": 0.05},
        stats={P + "fp_index.put": {"keys": 60_000, "slots": 240_000},
               P + "fp_index.insert": {"placed": 819_000_000 // 145}},
        gaps=[(P + "engine.decide", 1.2), ("other host work", 0.3)],
        idle_unattributed_s=0.3)


PEAKS = {"hbm_bytes_per_s": 819e9}
# (metric, value on _full(), what zeroes its denominator)
CASES = [
    ("frontend_fill_ms", 200.0, dict(count={})),
    ("frontend_ack_ms", 10.0, dict(count={})),
    ("shard_concurrency", 2.5, dict(total_s={})),
    ("engine_prepass_us_per_write", 1.5, "writes"),
    ("engine_decide_us_per_write", 3.0, "writes"),
    ("fp_marshal_us_per_key", 10.0, dict(stats={})),
    ("fp_launch_fill_pct", 25.0, dict(stats={})),
    # 819e6 bytes over 819 GB/s is 1 ms of least time in 2 ms of insert time
    ("fp_insert_roofline", 50.0, dict(stats={})),
    ("idle_unattributed_pct", 20.0, dict(gaps=[])),
]


def _ctx(s, writes=200_000):
    return {"spans": s, "counters": {"engine_writes": writes}, "peaks": PEAKS,
            "trace": trace.TraceSummary(window_s=2.0, busy_s=0.5,
                                        module_s={"jit__fp_insert_jit": 0.002}, devices=1)}


@pytest.mark.parametrize("name,value,zero", CASES, ids=[c[0] for c in CASES])
def test_reader_value_and_zero_denominator(name, value, zero):
    read = harness.load_metric(name)
    assert read(_ctx(_full())) == pytest.approx(value, rel=1e-3)
    if zero == "writes":
        assert read(_ctx(_full(), writes=0)) is None
    else:
        s = _full()
        for k, v in zero.items():
            setattr(s, k, v)
        assert read(_ctx(s)) is None


@pytest.mark.parametrize("name", [c[0] for c in CASES])
def test_reader_is_silent_without_program_spans(name, tmp_path, monkeypatch):
    # the parent program opens no span: the run's trace holds none
    monkeypatch.setattr(spans, "TRACE_DIR", str(tmp_path))
    read = harness.load_metric(name)
    assert read(_ctx(None)) is None
    ctx = _ctx(None)
    del ctx["spans"]
    assert read(ctx) is None
    # a summary of a trace that holds device ops and no program span
    with gzip.open(os.path.join(os.path.dirname(__file__), "data", "vmA_deep_trace.json.gz"),
                   "rt") as f:
        assert read(_ctx(spans.summarize(json.load(f)))) is None


def test_traced_tiny_run_reports_the_host_span_metrics(tmp_path, monkeypatch):
    """The harness end to end at a tiny size: the readers find the run's own
    trace.  The CPU has no device plane and no peaks, and the cell's indexes
    answer on the host, so the device and launch readers stay silent."""
    from bench.tests import tiny

    monkeypatch.setattr(spans, "TRACE_DIR", str(tmp_path))
    r = tiny.run("vmA.deep", seed=2147483711, trace=True, trace_dir=str(tmp_path))
    assert r["correct"], r["checks"]
    m = r["metrics"]
    for name in ("frontend_fill_ms", "frontend_ack_ms", "shard_concurrency",
                 "engine_prepass_us_per_write", "engine_decide_us_per_write"):
        assert m[name]["value"] > 0, name
    assert 0 < m["shard_concurrency"]["value"] <= 4
    for name in ("fp_marshal_us_per_key", "fp_launch_fill_pct", "fp_insert_roofline",
                 "idle_unattributed_pct"):
        assert name not in m


def test_insert_roofline_counts_the_work_not_the_staging():
    mod = harness.load_metric("fp_insert_roofline").__globals__
    assert mod["insert_bytes"](1) == 8 + 16 * 8 + 8 + 1
    assert mod["PROGRAM"] in "jit__fp_insert_jit"
