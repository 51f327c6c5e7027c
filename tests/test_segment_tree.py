"""Weighted victim-stream selection (Fenwick segments, paper SIV-B)."""

import json

import numpy as np

from repro.core.segment_tree import FenwickSegments


def test_draw_proportional_to_weights():
    t = FenwickSegments()
    t.set_weight(1, 1.0)
    t.set_weight(2, 3.0)
    rng = np.random.default_rng(0)
    draws = [t.draw(rng) for _ in range(4000)]
    frac2 = sum(d == 2 for d in draws) / len(draws)
    assert abs(frac2 - 0.75) < 0.04


def test_zero_weight_removes_stream():
    t = FenwickSegments()
    t.set_weight(1, 1.0)
    t.set_weight(2, 2.0)
    t.set_weight(2, 0.0)
    rng = np.random.default_rng(1)
    assert all(t.draw(rng) == 1 for _ in range(100))


def test_grow_beyond_initial_capacity():
    t = FenwickSegments(capacity=4)
    for s in range(40):
        t.set_weight(s, float(s + 1))
    assert abs(t.total - sum(range(1, 41))) < 1e-9
    rng = np.random.default_rng(2)
    assert t.draw(rng) in range(40)


def test_empty_draw_returns_none():
    t = FenwickSegments()
    assert t.draw(np.random.default_rng(0)) is None


def test_snapshot_restores_tree_nodes_bit_exactly():
    """The live Fenwick nodes are sums of incrementally accumulated float
    deltas; re-deriving them from the final weights re-associates those sums
    and can differ by ULPs (this exact history produces several differing
    nodes under rebuild), which would let a restored cache draw a different
    eviction victim.  The snapshot must carry the raw node array verbatim."""
    t = FenwickSegments(capacity=8)
    rng = np.random.default_rng(42)
    for _ in range(500):
        t.set_weight(int(rng.integers(0, 12)), float(rng.uniform(0, 1)))

    restored = FenwickSegments.from_snapshot(json.loads(json.dumps(t.snapshot())))
    assert restored._tree == t._tree  # exact float equality, node for node
    assert restored._weights == t._weights
    assert restored._slot_of == t._slot_of and restored._free == t._free

    # identical RNG streams must keep picking identical victims forever
    r1, r2 = np.random.default_rng(7), np.random.default_rng(7)
    for _ in range(200):
        assert t.draw(r1) == restored.draw(r2)
