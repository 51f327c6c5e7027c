"""The program-span reader (``bench/spans.py``): thread lines kept apart,
self time, idle gaps labelled by program span, and agreement with
``bench/trace.py`` where the program has no spans."""

import gzip
import json
import os
import threading

import pytest

from bench import spans, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1e6


def _ms(name, a, b, **stats):
    return (name, a * MS, (b - a) * MS, stats)


@pytest.fixture
def planes():
    """Two host threads, both named ``python`` as the profiler names them:
    a coordinator in a cluster call waiting on a shard worker, beside the
    device's ops."""
    coordinator = [_ms("bench.window", 0, 100), _ms("bench.write_batch", 96, 100),
                   _ms("dedup.cluster.write_batch", 10, 90, batch=7, keys=100),
                   _ms("dedup.cluster.route", 10, 20), _ms("dedup.cluster.wait", 20, 88),
                   _ms("dedup.cluster.gather", 88, 90)]
    worker = [_ms("dedup.shard.write_batch", 20, 78, shard=1, batch=7, keys=60),
              _ms("dedup.engine.decide", 30, 70), _ms("dedup.fp_index.fetch", 50, 60, keys=9)]
    ops = [("op", a * MS, (b - a) * MS) for a, b in ((0, 5), (45, 55), (80, 82), (84, 85),
                                                      (95, 96))]
    return {"/host:CPU": {"python": coordinator, "python/1": worker},
            "/device:TPU:0": {trace.OPS_LINE: ops, trace.MODULES_LINE: []}}


def test_self_time_is_what_no_child_covers_on_its_own_thread(planes):
    s = spans.summarize(planes)
    assert s.threads == 2 and s.window_s == pytest.approx(0.1)
    want_self = {"cluster.write_batch": 0, "cluster.route": 10, "cluster.wait": 68,
                 "cluster.gather": 2, "shard.write_batch": 18, "engine.decide": 30,
                 "fp_index.fetch": 10}
    assert s.self_s == {spans.PREFIX + n: pytest.approx(v * 1e-3, abs=1e-12)
                        for n, v in want_self.items() if v}
    assert s.total_s["dedup.cluster.write_batch"] == pytest.approx(0.080)
    assert s.total_s["dedup.shard.write_batch"] == pytest.approx(0.058)
    assert s.count["dedup.shard.write_batch"] == 1
    assert s.stat("shard.write_batch", "keys") == 60 and s.stat("fp_index.fetch", "keys") == 9
    assert s.mean_s("cluster.write_batch") == pytest.approx(0.080)
    assert s.mean_s("frontend.fill") is None


def test_gaps_take_the_leaf_span_with_most_overlap_and_wait_only_alone(planes):
    s = spans.summarize(planes)
    # gaps [5,45] [55,80] [82,84] [85,95] [96,100] ms
    labels = {round(g * 1e3, 6): n for n, g in s.gaps}
    assert labels == {40: "dedup.engine.decide", 25: "dedup.engine.decide",
                      2: "dedup.cluster.wait", 10: "dedup.cluster.gather",
                      4: "ShardedCluster.write_batch"}
    assert [g for _, g in s.gaps] == sorted((g for _, g in s.gaps), reverse=True)
    assert s.idle_s == pytest.approx(0.081) == pytest.approx(s.window_s - s.busy_s)
    assert s.idle_by_span == {"dedup.engine.decide": pytest.approx(0.065),
                              "dedup.cluster.wait": pytest.approx(0.002),
                              "dedup.cluster.gather": pytest.approx(0.010),
                              "ShardedCluster.write_batch": pytest.approx(0.004)}
    # [5,10], [90,95] and [96,100]: no program span open on either thread
    assert s.idle_unattributed_s == pytest.approx(0.014)
    b = s.breakdown(2)
    assert b["idle_gaps"] == [["dedup.engine.decide", pytest.approx(0.040)],
                              ["dedup.engine.decide", pytest.approx(0.025)]]


def test_without_program_spans_the_gaps_keep_the_trace_labels():
    with gzip.open(os.path.join(DATA, "vmA_deep_trace.json.gz"), "rt") as f:
        recorded = json.load(f)
    base = trace.reduce(recorded)
    s = spans.summarize(recorded)
    assert s.threads == 0 and s.idle_unattributed_s == pytest.approx(s.idle_s)
    assert (s.window_s, s.busy_s) == (base.window_s, base.busy_s)
    assert s.idle_s == pytest.approx(base.window_s - base.busy_s)
    want = trace.TraceSummary(0, 0, gaps_ns=base.gaps_ns, host_spans=base.host_spans) \
        .breakdown(10 ** 6)["idle_gaps"]
    assert sorted(map(tuple, s.breakdown(10 ** 6)["idle_gaps"])) == sorted(map(tuple, want))
    # the recorded trace's own numbers, which the existing readers pin
    assert base.devices == 1 and base.window_s == pytest.approx(2.0)
    assert base.breakdown()["idle_gaps"][0][0] == "ShardedCluster.write_batch"


def test_thread_lines_kept_apart_leave_the_pooled_reduction_unchanged(planes):
    pooled = {"/host:CPU": {"python": planes["/host:CPU"]["python"] +
                            planes["/host:CPU"]["python/1"]},
              "/device:TPU:0": planes["/device:TPU:0"]}
    plain = lambda p: {k: {ln: [e[:3] for e in evs] for ln, evs in lines.items()}
                       for k, lines in p.items()}
    a, b = trace.reduce(plain(planes)), trace.reduce(plain(pooled))
    assert (a.window_s, a.busy_s, a.module_s, a.gaps_ns) == \
        (b.window_s, b.busy_s, b.module_s, b.gaps_ns)
    assert a.breakdown() == b.breakdown()


def test_load_keeps_same_named_thread_lines_apart(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation

    both = threading.Barrier(2)  # both alive at once: two threads, two lines

    def work(shard):
        with TraceAnnotation("dedup.shard.write_batch", shard=shard, batch=3):
            with TraceAnnotation("dedup.engine.decide"):
                both.wait()

    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("bench.window"):
            threads = [threading.Thread(target=work, args=(s,)) for s in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
    finally:
        jax.profiler.stop_trace()
    planes = spans.load(spans.newest_trace(str(tmp_path)))
    host = planes["/host:CPU"]
    workers = {ln: evs for ln, evs in host.items()
               if any(e[0] == "dedup.shard.write_batch" for e in evs)}
    assert len(workers) == 2  # one line each, though the profiler names both alike
    assert sorted(e[3]["shard"] for evs in workers.values() for e in evs
                  if e[0] == "dedup.shard.write_batch") == [0, 1]
    s = spans.summarize(planes)
    assert s.threads == 2 and s.count["dedup.engine.decide"] == 2
    assert s.stat("shard.write_batch", "batch") == 6


def test_of_reads_the_runs_own_trace_or_nothing(tmp_path, monkeypatch, planes):
    monkeypatch.setattr(spans, "TRACE_DIR", str(tmp_path / "none"))
    assert spans.of({"trace": trace.reduce({})}) is None
    assert spans.of({"trace": None}) is None
    given = spans.summarize(planes)
    assert spans.of({"spans": given}) is given
