"""The controls come out as not correct, and a run whose timed path is
broken underneath comes out as not correct, once for each fault a cell can
have (one chip: no exchange between chips to leave out)."""

import numpy as np
import pytest

from bench import control
from bench.tests import tiny


def _cfg(workload):
    _, cfg, traffic = tiny.cell_files(workload)
    tiny.shrink(cfg, traffic)
    return cfg, traffic


def test_block_writes_control_fails_and_program_passes():
    cfg, traffic = _cfg("vmA.deep")
    # 12 bits at this size collide as 32 bits do over the cell's 2M keys
    rows = control.run("vmA.deep", [21, 22], 1.0, bits=12, config=cfg, traffic=traffic,
                       log=lambda m: None)
    assert all(r["program_correct"] and not r["control_correct"] for r in rows)


def _half_batch(monkeypatch):
    from repro.core.cluster import ShardedCluster

    real = ShardedCluster.write_batch

    def half(self, streams, lbas, fps):
        n = len(fps) // 2
        out = np.zeros(len(fps), dtype=bool)
        out[:n] = real(self, streams[:n], lbas[:n], fps[:n])
        return out

    monkeypatch.setattr(ShardedCluster, "write_batch", half)


def _state_unchanged(monkeypatch):
    from repro.core.fp_index import FingerprintIndex

    def probe_only(self, uniq):
        pending = self.contains_many_async(np.ascontiguousarray(uniq, dtype=np.uint64))
        return pending  # the seen set never learns a fingerprint

    monkeypatch.setattr(FingerprintIndex, "probe_and_add_async", probe_only)


def _answer_altered(monkeypatch):
    from repro.core.hybrid import HPDedup

    real = HPDedup.write_batch

    def flipped(self, streams, lbas, fps):
        out = real(self, streams, lbas, fps)
        out[~out] = True  # every miss acknowledged as deduplicated
        return out

    monkeypatch.setattr(HPDedup, "write_batch", flipped)


@pytest.mark.parametrize("workload,fault,caught_by", [
    ("vmA.deep", _half_batch, "applied_gap"),
    ("vmA.deep", _state_unchanged, "dup_count_gap"),
    ("vmA.deep", _answer_altered, "false_inline"),
])
def test_a_broken_timed_path_comes_out_not_correct(monkeypatch, workload, fault, caught_by):
    fault(monkeypatch)
    r = tiny.run(workload, seed=41)
    assert not r["correct"]
    assert r["checks"][caught_by]["value"] > r["checks"][caught_by]["limit"], r["checks"]
    print(workload, fault.__name__, {k: v["value"] for k, v in r["checks"].items()})
