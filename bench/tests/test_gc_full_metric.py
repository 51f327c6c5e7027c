"""The ``gc_full_ms_per_batch`` reader: generation-2 ``dedup.gc.collect``
spans of the window on a hand-built trace, silence where the program opens
no such span, and a forced full collection in a real profiler trace."""

import gc

import pytest

from bench import harness, spans, trace

MS = 1e6
SPAN = "dedup.gc.collect"


def _ms(name, a, b, **stats):
    return (name, a * MS, (b - a) * MS, stats)


def _planes():
    """A 100 ms window with three full passes (one starts before it) and two
    young ones, on two host threads, beside the device's ops."""
    coordinator = [_ms("bench.window", 10, 110), _ms(SPAN, 5, 15, generation=2, collected=1),
                   _ms(SPAN, 20, 50, generation=2, collected=7),
                   _ms(SPAN, 60, 61, generation=0, collected=0)]
    worker = [_ms("dedup.engine.decide", 30, 100), _ms(SPAN, 70, 90, generation=2, collected=3),
              _ms(SPAN, 95, 96, generation=1, collected=0)]
    ops = [("op", a * MS, (b - a) * MS) for a, b in ((12, 14), (100, 101))]
    return {"/host:CPU": {"python": coordinator, "python/1": worker},
            "/device:TPU:0": {trace.OPS_LINE: ops, trace.MODULES_LINE: []}}


def _module():
    return harness.load_metric("gc_full_ms_per_batch").__globals__


def test_full_passes_that_start_in_the_window_are_summed():
    # 30 ms + 20 ms; the pass that starts before the window and the young
    # generations do not count
    assert _module()["full_s"](_planes()) == pytest.approx(0.050)


@pytest.mark.parametrize("batches,want", [(4, 12.5), (0, None)])
def test_reader_divides_by_the_window_batches(batches, want, monkeypatch):
    planes = _planes()
    monkeypatch.setattr(spans, "newest_trace", lambda trace_dir=None: "run.xplane.pb")
    monkeypatch.setattr(spans, "load", lambda path: planes)
    ctx = {"spans": spans.summarize(planes), "counters": {"frontend_batches": batches}}
    got = harness.load_metric("gc_full_ms_per_batch")(ctx)
    assert got == (pytest.approx(want) if want is not None else None)


def test_reader_is_silent_without_gc_spans(monkeypatch):
    """The parent program installs no hook: its trace holds no gc span."""
    planes = _planes()
    for lines in planes.values():
        for name in lines:
            lines[name] = [e for e in lines[name] if e[0] != SPAN]
    monkeypatch.setattr(spans, "load", lambda path: pytest.fail("loaded the trace"))
    read = harness.load_metric("gc_full_ms_per_batch")
    assert read({"spans": spans.summarize(planes), "counters": {"frontend_batches": 4}}) is None
    assert read({"spans": None, "counters": {"frontend_batches": 4}}) is None


def test_forced_full_collection_in_a_profiler_trace(tmp_path, monkeypatch):
    import jax
    from jax.profiler import TraceAnnotation

    from repro import obs

    obs.trace_gc()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("bench.window"):
            jax.numpy.ones(8).block_until_ready()
            gc.collect(2)
    finally:
        jax.profiler.stop_trace()
    planes = spans.load(spans.newest_trace(str(tmp_path)))
    full = [st for lines in planes.values() for evs in lines.values() for n, _, _, st in evs
            if n == SPAN and st.get("generation") == 2]
    assert full and all("collected" in st for st in full)
    monkeypatch.setattr(spans, "TRACE_DIR", str(tmp_path))
    ctx = {"spans": spans.summarize(planes), "counters": {"frontend_batches": 1}}
    assert harness.load_metric("gc_full_ms_per_batch")(ctx) > 0
