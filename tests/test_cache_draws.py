"""The prioritized cache's evicting admission against the plain algorithm.

``PrioritizedCache`` draws its victim uniforms in blocks, keeps the Fenwick
total current instead of re-summing it, pops LRU victims inline and hands
its resident index the admissions and evictions in bulk.  ``PlainCache``
below is the algorithm without any of that: one ``rng.uniform(0.0, tot)``
per draw over a re-summed total, the policies' ``evict_one``/``insert``,
and one scalar index call per admission or eviction.  Driven through the
same seeded operations, the two must agree after every step: victims,
owners, each sub-cache's order, the Fenwick weights and total, the
generator state in every snapshot, and the resident index's buffers and
counters at every probe.
"""

import json

import numpy as np
import pytest

from repro.core import cache as cache_mod
from repro.core.cache import PrioritizedCache, make_policy, policy_from_snapshot
from repro.core.fp_index import FingerprintIndex
from repro.core.segment_tree import FenwickSegments
from repro.core.statetree import from_pairs, pairs


def _plain_draw(seg: FenwickSegments, rng):
    """``FenwickSegments.draw`` as it was: the total re-summed per draw,
    one scalar ``uniform`` per victim."""
    tot = seg._prefix(seg._size)
    if tot <= 0.0:
        return None
    r = rng.uniform(0.0, tot)
    tree, size, pos, mask = seg._tree, seg._size, 0, seg._size
    while mask:
        nxt = pos + mask
        if nxt <= size and tree[nxt] <= r:
            r -= tree[nxt]
            pos = nxt
        mask >>= 1
    stream = seg._stream_of.get(pos)
    if stream is None:
        stream = max(seg._weights, key=seg._weights.get)
    return stream


class PlainCache:
    """The prioritized cache's admission and eviction, one call at a time."""

    def __init__(self, capacity, policy="lru", admission_ratio=0.01, min_ldss=1.0, seed=0):
        self.capacity, self.policy = capacity, policy
        self.admission_ratio, self.min_ldss = admission_ratio, min_ldss
        self.rng = np.random.default_rng(seed)
        self.streams, self.owner, self.ldss = {}, {}, {}
        self.index = FingerprintIndex()
        self._best_ldss = 0.0
        self.segments = FenwickSegments()
        self.total = self.inserted = self.evicted = 0

    def set_ldss(self, ldss):
        self.ldss.update({s: max(float(v), 0.0) for s, v in ldss.items()})
        self._best_ldss = max(self.ldss.values(), default=0.0)
        for s in set(list(self.ldss) + list(self.streams)):
            self.segments.set_weight(s, self._evict_priority(s))

    def _evict_priority(self, stream):
        sub = self.streams.get(stream)
        if not sub or len(sub) == 0:
            return 0.0
        return 1.0 / max(self.ldss.get(stream, self.min_ldss), self.min_ldss)

    def _admitted(self, stream):
        if not self.ldss:
            return True
        mine = self.ldss.get(stream)
        if mine is None or self._best_ldss <= self.min_ldss:
            return True
        return mine >= self.admission_ratio * self._best_ldss

    def _sub(self, stream):
        if stream not in self.streams:
            self.streams[stream] = make_policy(self.policy, max(64, self.capacity // 8))
        return self.streams[stream]

    def lookup(self, stream, fp):
        holder = self.owner.get(fp)
        return None if holder is None else self.streams[holder].lookup(fp)

    def admit(self, stream, fp, pba):
        holder = self.owner.get(fp)
        if holder is not None:
            self.streams[holder].insert(fp, pba)
            return
        if not self._admitted(stream):
            return
        sub = self._sub(stream)
        while self.total >= self.capacity:
            if not self._evict():
                break
        sub.insert(fp, pba)
        self.owner[fp] = stream
        self.index.add(fp)
        self.total += 1
        self.inserted += 1
        if len(sub) == 1:
            self.segments.set_weight(stream, self._evict_priority(stream))

    def _drop(self, stream, sub, fp):
        self.owner.pop(fp, None)
        self.index.discard(fp)
        self.total -= 1
        self.evicted += 1
        if len(sub) == 0:
            self.segments.set_weight(stream, 0.0)

    def _evict(self):
        victim = _plain_draw(self.segments, self.rng)
        if victim is None:
            candidates = [(len(c), s) for s, c in self.streams.items() if len(c)]
            if not candidates:
                return False
            victim = max(candidates)[1]
        sub = self.streams[victim]
        out = sub.evict_one()
        if out is None:
            self.segments.set_weight(victim, 0.0)
            for s, sub in self.streams.items():
                out = sub.evict_one()
                if out is not None:
                    self._drop(s, sub, out[0])
                    return True
            return False
        self._drop(victim, sub, out[0])
        return True

    def snapshot(self):
        return {
            "rng": self.rng.bit_generator.state,
            "streams": [[s, sub.snapshot()] for s, sub in self.streams.items()],
            "owner": pairs(self.owner),
            "ldss": pairs(self.ldss),
            "best_ldss": self._best_ldss,
            "total": self.total,
            "inserted": self.inserted,
            "segments": self.segments.snapshot(),
        }

    def load_snapshot(self, tree):
        self.rng = np.random.default_rng(0)
        self.rng.bit_generator.state = tree["rng"]
        self.streams = {int(s): policy_from_snapshot(sub) for s, sub in tree["streams"]}
        self.owner = from_pairs(tree["owner"], value=int)
        self.index = FingerprintIndex(self.owner)
        self.ldss = from_pairs(tree["ldss"], value=float)
        self._best_ldss = float(tree["best_ldss"])
        self.total, self.inserted = int(tree["total"]), int(tree["inserted"])
        self.segments = FenwickSegments.from_snapshot(tree["segments"])

    def evict_fp(self, fp):
        holder = self.owner.get(fp)
        if holder is None:
            return None
        sub = self.streams[holder]
        pba = sub.peek(fp)
        sub.remove(fp)
        del self.owner[fp]
        self.index.discard(fp)
        self.total -= 1
        if len(sub) == 0:
            self.segments.set_weight(holder, 0.0)
        return pba

    def migrate_in(self, stream, fp, pba):
        holder = self.owner.get(fp)
        if holder is not None:
            self.streams[holder].replace(fp, pba)
            return True
        if self.total >= self.capacity:
            return False
        sub = self._sub(stream)
        sub.insert(fp, pba)
        self.owner[fp] = stream
        self.index.add(fp)
        self.total += 1
        if len(sub) == 1:
            self.segments.set_weight(stream, self._evict_priority(stream))
        return True


def _assert_same(new: PrioritizedCache, old: PlainCache) -> None:
    assert list(new.owner.items()) == list(old.owner.items())
    assert [[s, sub.snapshot()] for s, sub in new.streams.items()] == [
        [s, sub.snapshot()] for s, sub in old.streams.items()
    ]
    assert new.segments.snapshot() == old.segments.snapshot()
    assert new.segments.total == old.segments._prefix(old.segments._size)
    assert (new.total, new.inserted, new.evicted) == (old.total, old.inserted, old.evicted)


def _assert_same_index(new: PrioritizedCache, old: PlainCache) -> None:
    idx = new.index
    assert set(idx) == set(new.owner)
    assert list(idx._keys) == list(old.index._keys)
    assert list(idx._pending_adds) == list(old.index._pending_adds)
    assert list(idx._pending_removes) == list(old.index._pending_removes)
    assert idx._spill == old.index._spill
    assert idx.table_stats() == old.index.table_stats()


STREAMS = 7
CAPACITY = 16


@pytest.mark.parametrize("block", [3, 64])
@pytest.mark.parametrize("policy", ["lru", "lfu", "arc"])
@pytest.mark.parametrize("seed", [0, 1])
def test_evicting_admission_is_the_plain_algorithm(seed, policy, block, monkeypatch):
    monkeypatch.setattr(cache_mod, "_DRAW_BLOCK", block)
    rng = np.random.default_rng(1000 + seed)
    new = PrioritizedCache(CAPACITY, policy=policy, seed=seed)
    old = PlainCache(CAPACITY, policy=policy, seed=seed)
    pool = list(range(1, 61))  # re-admitted and looked up again
    fresh = 1 << 40
    evictions = 0  # over the restored copies too
    for step in range(2500):
        op = rng.random()
        s = int(rng.integers(0, STREAMS))
        fp = int(rng.choice(pool))
        if op < 0.2:
            assert new.lookup(s, fp) == old.lookup(s, fp)
        elif op < 0.65:
            if rng.random() < 0.5:
                fp, fresh = fresh, fresh + 1
            new.admit(s, fp, step)
            old.admit(s, fp, step)
        elif op < 0.7:
            # streams' priorities move; an LDSS of 0 stops a stream's admissions
            ldss = {int(t): float(rng.choice([0.0, 0.5, rng.uniform(1, 100)]))
                    for t in rng.choice(STREAMS, size=int(rng.integers(1, STREAMS)), replace=False)}
            new.set_ldss(ldss)
            old.set_ldss(ldss)
        elif op < 0.75:
            if old.owner and rng.random() < 0.8:
                fp = int(rng.choice(list(old.owner)))
            assert new.evict_fp(fp) == old.evict_fp(fp)
        elif op < 0.8:
            if rng.random() < 0.5:
                fp, fresh = fresh, fresh + 1
            assert new.migrate_in(s, fp, step) == old.migrate_in(s, fp, step)
        elif op < 0.88:
            # a batched probe large enough to go through the table
            probe = np.concatenate([np.array(pool + list(old.owner), dtype=np.uint64),
                                    rng.integers(1, 1 << 62, size=1600, dtype=np.uint64)])
            np.testing.assert_array_equal(new.contains_many(probe),
                                          old.index.contains_many(probe))
            _assert_same_index(new, old)
        elif op < 0.93:
            # a snapshot in the middle of a drawn block; half the time both
            # sides continue from a restored copy
            tree = json.loads(json.dumps(new.snapshot()))
            assert tree == json.loads(json.dumps(old.snapshot()))
            if rng.random() < 0.5:
                evictions += new.evicted
                new = PrioritizedCache(CAPACITY, policy=policy, seed=99)
                new.load_snapshot(tree)
                old = PlainCache(CAPACITY, policy=policy, seed=98)
                old.load_snapshot(tree)
        elif op < 0.95:
            assert new.rng.bit_generator.state == old.rng.bit_generator.state
        elif op < 0.96:
            # weights the ordinary path never leaves: an empty stream drawn
            # (the fallback eviction), or no weight at all (the largest stream)
            if rng.random() < 0.3:
                new.segments, old.segments = FenwickSegments(), FenwickSegments()
            else:
                new.segments.set_weight(s, 1.0)
                old.segments.set_weight(s, 1.0)
        _assert_same(new, old)
    assert evictions + new.evicted > 100
    assert new.rng.bit_generator.state == old.rng.bit_generator.state
    _assert_same_index(new, old)


def test_evicted_counts_every_eviction_and_stays_out_of_the_snapshot():
    cache = PrioritizedCache(capacity=4, policy="lru", seed=3)
    for fp in range(1, 5):
        cache.admit(0, fp, fp)
    assert cache.evicted == 0
    cache.admit(1, 5, 5)
    cache.admit(1, 6, 6)
    assert cache.evicted == 2 and len(cache) == 4
    # a move is not an eviction: pulling an entry out and migrating one in
    held = next(iter(cache.owner))
    assert cache.evict_fp(held) is not None
    assert cache.migrate_in(2, 7, 7)
    assert not cache.migrate_in(2, 8, 8)  # full: dropped, nothing evicted
    assert cache.evicted == 2
    # the drawn stream is empty: the fallback evicts from the first that is not
    cache._sub(3)
    for s in list(cache.streams):
        cache.segments.set_weight(s, 0.0)
    cache.segments.set_weight(3, 1.0)
    before = len(cache.owner)
    cache.admit(1, 9, 9)
    assert cache.evicted == 3 and len(cache.owner) == before
    tree = json.loads(json.dumps(cache.snapshot()))
    assert "evicted" not in json.dumps(tree)
    restored = PrioritizedCache(capacity=4, policy="lru")
    restored.load_snapshot(tree)
    assert restored.evicted == 0
