"""vmC.deep end to end at a tiny size on the CPU, through the harness: the
cell is correct and reports its metrics, its two new checks catch the
faults they are for, its control comes out not correct, and the family
refuses a program without the counters its metrics read."""

import time

import numpy as np
import pytest

from bench import control, harness
from bench.tests import tiny

CHECKS = {"applied_gap", "dup_count_gap", "flag_report_gap", "false_inline", "post_pass_gap",
          "post_backlog_gap", "readback_gap", "exact_gap"}


def test_vmC_deep_runs_correct_and_reports_its_metrics():
    r = tiny.run("vmC.deep")
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"writes_per_s", "inline_dedup_pct", "setup_s"}
    assert all(np.isfinite(m["value"]) and m["value"] > 0 for m in r["metrics"].values())
    assert set(r["checks"]) == CHECKS


def test_vmC_deep_traced_reports_per_layer_metrics(monkeypatch, tmp_path):
    from bench import spans

    # the span readers read the newest trace under spans.TRACE_DIR
    monkeypatch.setattr(spans, "TRACE_DIR", str(tmp_path / "trace"))
    r = tiny.run("vmC.deep", seed=7, trace=True, trace_dir=spans.TRACE_DIR)
    assert r["correct"], r["checks"]
    m = r["metrics"]
    # the CPU has no device plane and no peaks: the device readers stay silent
    assert "fp_probe_roofline" not in m and "device_idle_pct.served" not in m
    assert m["frontend_batch_writes"]["value"] == 2048
    # passes run inside the window, on the shard threads
    assert m["post_ms_per_pass"]["value"] > 0
    assert 0 < m["post_share_pct"]["value"] < 100
    # each staged key walked about once, not the volume every pass
    assert 0.5 < m["reverse_keys_per_write"]["value"] <= 2


def _misdirected_merge(monkeypatch):
    """Each pass points one LBA it merged at a block of other content."""
    from repro.core.postprocess import PostProcessEngine

    real = PostProcessEngine.run

    def run(self, max_merges=None):
        store = self.store
        store._ensure_reverse()
        dups = store.duplicate_fingerprints()
        moved = [k for p in list(store._dup_fps[dups[0]])[1:] for k in store.lbas_of(p)] \
            if dups else []
        out = real(self, max_merges)
        other = next((p for p, f in store.fp_of_pba.items() if f != dups[0]), None) \
            if moved else None
        if other is not None:
            store._lba_pba[moved[0]] = other
        return out

    monkeypatch.setattr(PostProcessEngine, "run", run)


def _exact_pass_skipped(monkeypatch):
    from repro.core.cluster import ShardedCluster

    real = ShardedCluster.run_postprocess

    def skip(self, to_exact=False, max_merges_per_shard=None):
        return 0 if to_exact else real(self, to_exact, max_merges_per_shard)

    monkeypatch.setattr(ShardedCluster, "run_postprocess", skip)


def _aged_key_misdirected(monkeypatch):
    """The exact pass after the window points one key written while the
    state aged, and not since, at a block of other content."""
    from repro.core.cluster import ShardedCluster
    from repro.core.store import lba_key
    from repro.serving.frontend import AsyncDedupFrontend

    real_ingest, real_post = ShardedCluster.ingest_batched, ShardedCluster.run_postprocess
    real_write = AsyncDedupFrontend.write
    aged, served = set(), set()

    def ingest(self, trace, *a, **k):
        aged.update(lba_key(int(s), int(lba)) for s, lba in zip(trace["stream"], trace["lba"]))
        return real_ingest(self, trace, *a, **k)

    def write(self, stream, fp, lba=None, *a, **k):
        served.add(lba_key(stream, lba))
        return real_write(self, stream, fp, lba, *a, **k)

    def post(self, to_exact=False, max_merges_per_shard=None):
        out = real_post(self, to_exact, max_merges_per_shard)
        if to_exact:
            st = self.shards[0].store
            key = next(k for k in st._lba_pba if k in aged and k not in served)
            pba = st._lba_pba[key]
            st._lba_pba[key] = next(p for p, f in st.fp_of_pba.items()
                                    if f != st.fp_of_pba[pba])
        return out

    monkeypatch.setattr(ShardedCluster, "ingest_batched", ingest)
    monkeypatch.setattr(AsyncDedupFrontend, "write", write)
    monkeypatch.setattr(ShardedCluster, "run_postprocess", post)


def _serving(monkeypatch, periodic):
    """Replace the engines' periodic passes once the front end serves them."""
    from repro.core.hybrid import HPDedup
    from repro.serving.frontend import AsyncDedupFrontend

    real_post, real_init = HPDedup.run_postprocess, AsyncDedupFrontend.__init__

    def init(self, cluster, *a, **k):
        for e in cluster.shards:
            e._serving = True
        real_init(self, cluster, *a, **k)

    def post(self, to_exact=False, max_merges=None):
        if to_exact or not getattr(self, "_serving", False):
            return real_post(self, to_exact, max_merges)
        return periodic(self, real_post)

    monkeypatch.setattr(AsyncDedupFrontend, "__init__", init)
    monkeypatch.setattr(HPDedup, "run_postprocess", post)


def _window_passes_skipped(monkeypatch):
    """The window's periodic passes do nothing (their count restarts)."""
    def skip(self, real_post):
        self._writes_since_post = 0

    _serving(monkeypatch, skip)


def _window_passes_throttled(monkeypatch):
    """Each of the window's periodic passes merges one fingerprint."""
    _serving(monkeypatch, lambda self, real_post: real_post(self, False, 1))


@pytest.mark.parametrize("fault,caught_by", [
    (_misdirected_merge, "readback_gap"),
    (_aged_key_misdirected, "readback_gap"),
    (_exact_pass_skipped, "exact_gap"),
    (_window_passes_skipped, "post_pass_gap"),
    (_window_passes_throttled, "post_backlog_gap"),
])
def test_a_broken_exact_phase_comes_out_not_correct(monkeypatch, fault, caught_by):
    fault(monkeypatch)
    r = tiny.run("vmC.deep", seed=43)
    assert not r["correct"]
    assert r["checks"][caught_by]["value"] > r["checks"][caught_by]["limit"], r["checks"]
    print(fault.__name__, {k: v["value"] for k, v in r["checks"].items()})


def test_control_fails_and_program_passes():
    _, cfg, traffic = tiny.cell_files("vmC.deep")
    tiny.shrink(cfg, traffic)
    # 12 bits at this size collide as 32 bits do over the cell's 2M keys
    rows = control.run("vmC.deep", [23], 1.0, bits=12, config=cfg, traffic=traffic,
                       log=lambda m: None)
    assert all(r["program_correct"] and not r["control_correct"] for r in rows)
    assert all(r["control"]["exact_gap"] > 0 for r in rows)


def test_depths_follow_workload_C_rates_and_sum_to_in_flight():
    _, cfg, traffic = tiny.cell_files("vmC.deep")
    templates = [t for t, spec in cfg["tenants"].items() for _ in range(spec["count"])]
    assert {t: templates.count(t) for t in cfg["tenants"]} == {"mail": 5, "ftp": 15, "home": 6,
                                                              "web": 6}
    d = harness.load_family("hybrid_block_writes").bw.depths(cfg, templates,
                                                             traffic["in_flight"])
    assert sum(d) == traffic["in_flight"] == 32768
    by = {t: {x for x, tt in zip(d, templates) if tt == t} for t in cfg["tenants"]}
    # 32768 x 8 / 166.3 = 1576.3 for a mail or FTP disk, 157.6 for home, 49.3
    # for web; the largest remainders take the 6 writes left over
    assert by["mail"] | by["ftp"] == {1576, 1577}
    assert by["home"] <= {157, 158} and by["web"] == {49}
    assert sum(x for x, t in zip(d, templates) if t == "ftp") == 23642


def test_family_fails_at_once_on_a_program_without_the_counter(monkeypatch):
    from repro.core.cluster import ShardedCluster
    from repro.core.store import BlockStore

    real = BlockStore.__init__

    def older(self, *a, **k):
        real(self, *a, **k)
        del self.reverse_keys_walked

    def never(self, *a, **k):
        raise AssertionError("aged before the counters were checked")

    monkeypatch.setattr(BlockStore, "__init__", older)
    monkeypatch.setattr(ShardedCluster, "ingest_batched", never)
    _, cfg, traffic = tiny.cell_files("vmC.deep")
    tiny.shrink(cfg, traffic)
    fam = harness.load_family("hybrid_block_writes")
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="reverse-index counter"):
        fam.run(cfg, traffic, 2147483711, 1.0, None, lambda m: None, t0)
    assert time.perf_counter() - t0 < 30
