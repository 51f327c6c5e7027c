"""FingerprintIndex: exactness against a host oracle, kernel equivalence.

The index's contract is *exact* membership — no false positives or
negatives, regardless of table capacity (overflow spills), removals
(tombstones), sentinel-colliding keys, growth rebuilds, or which backend
(numpy fast path / Pallas kernels in interpret mode) answers the probe.
Every test here drives the real batched entry points with ``small_batch=0``
so the device-layout table is exercised, not the host-set shortcut.
"""

import gc
from collections.abc import Set

import numpy as np
import pytest

from repro.core.fp_index import EMPTY_KEY, TOMB_KEY, FingerprintIndex
from repro.kernels.fp_index import WINDOW, slot_hash_host


def _keys(rng, n):
    return rng.integers(0, 1 << 64, size=n, dtype=np.uint64)


# ---------------------------------------------------------------------------
# Differential: random insert/probe/remove vs a plain Python set oracle.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_random_ops_match_set_oracle(backend):
    steps = 250 if backend == "numpy" else 60
    rng = np.random.default_rng(7)
    oracle = set()
    # tiny capacity: growth and window overflow both trigger
    idx = FingerprintIndex(capacity=128, small_batch=0, backend=backend)
    for step in range(steps):
        op = int(rng.integers(0, 4))
        if op <= 1:
            ks = _keys(rng, int(rng.integers(1, 200)))
            if step % 3 == 0:
                idx.add_many(ks)
            else:
                for k in ks.tolist():
                    idx.add(k)
            oracle.update(ks.tolist())
        elif op == 2 and oracle:
            pool = np.fromiter(oracle, dtype=np.uint64, count=len(oracle))
            ks = rng.choice(pool, size=min(40, pool.size), replace=False)
            if step % 2:
                idx.remove_many(ks)
            else:
                for k in ks.tolist():
                    idx.discard(k)
            oracle.difference_update(ks.tolist())
        else:
            probe = _keys(rng, 128)
            if oracle:
                pool = np.fromiter(oracle, dtype=np.uint64, count=len(oracle))
                probe[:32] = rng.choice(pool, size=min(32, pool.size))
            got = idx.contains_many(probe)
            want = np.fromiter((int(k) in oracle for k in probe), dtype=bool, count=probe.size)
            np.testing.assert_array_equal(got, want)
        if step % 25 == 0:
            idx.check_consistency()
            assert set(idx) == oracle
    idx.check_consistency()
    assert set(idx) == oracle


def test_overflow_spills_stay_exact():
    """Force window overflow (insert far past a non-growing load point in
    one batch) and check spilled keys still probe as present."""
    rng = np.random.default_rng(3)
    idx = FingerprintIndex(capacity=64, small_batch=0)
    ks = np.unique(_keys(rng, 3000))
    idx.add_many(ks)  # grows, but the batch overshoots every threshold step
    assert set(idx) == set(ks.tolist())
    np.testing.assert_array_equal(idx.contains_many(ks), np.ones(ks.size, bool))
    idx.check_consistency()
    # removals of spilled and table-resident keys alike
    drop = ks[:: 7]
    idx.remove_many(drop)
    keep = np.setdiff1d(ks, drop)
    np.testing.assert_array_equal(idx.contains_many(drop), np.zeros(drop.size, bool))
    np.testing.assert_array_equal(idx.contains_many(keep), np.ones(keep.size, bool))
    idx.check_consistency()


def test_sentinel_keys_route_to_spill():
    idx = FingerprintIndex(small_batch=0)
    probe = np.array([EMPTY_KEY, TOMB_KEY, 42], dtype=np.uint64)
    np.testing.assert_array_equal(idx.contains_many(probe), [False, False, False])
    idx.add(EMPTY_KEY)
    idx.add(TOMB_KEY)
    idx.add(42)
    np.testing.assert_array_equal(idx.contains_many(probe), [True, True, True])
    assert idx.spilled() == 2
    idx.discard(EMPTY_KEY)
    np.testing.assert_array_equal(idx.contains_many(probe), [False, True, True])
    idx.check_consistency()


def test_tombstone_chains_stay_probeable():
    """A key placed past colliding neighbours must stay findable after the
    neighbours are removed (tombstones must not terminate probe chains)."""
    rng = np.random.default_rng(11)
    idx = FingerprintIndex(capacity=64, small_batch=0)
    cap_mask = np.uint32(idx.table_stats()["capacity"] - 1)
    ks = np.unique(_keys(rng, 4096))
    lo = (ks & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (ks >> np.uint64(32)).astype(np.uint32)
    home = slot_hash_host(lo, hi) & cap_mask
    # pick one crowded home slot
    slots, counts = np.unique(home, return_counts=True)
    crowd = ks[home == slots[np.argmax(counts)]][:4]
    assert crowd.size >= 2
    idx.add_many(crowd)
    idx.remove_many(crowd[:-1])  # tombstone everything before the last one
    assert bool(idx.contains_many(np.array([crowd[-1]], dtype=np.uint64))[0])
    idx.check_consistency()


def test_scalar_and_batched_paths_interleave():
    """Pending-buffer staging: scalar add/discard between batched probes."""
    idx = FingerprintIndex(small_batch=0)
    idx.add(10)
    idx.add(20)
    idx.discard(10)
    idx.add(10)  # re-add while the remove is still pending
    got = idx.contains_many(np.array([10, 20, 30], dtype=np.uint64))
    np.testing.assert_array_equal(got, [True, True, False])
    idx.discard(20)
    idx.add(30)
    got = idx.contains_many(np.array([10, 20, 30], dtype=np.uint64))
    np.testing.assert_array_equal(got, [True, False, True])
    idx.check_consistency()


def test_set_api_compatibility():
    """The index has the ``set`` API host-side consumers use (snapshots
    sort it, resharding unions and discards it, harnesses iterate it),
    over membership the garbage collector does not track."""
    idx = FingerprintIndex([3, 1, 2])
    assert isinstance(idx, Set) and not isinstance(idx, set)
    assert not gc.is_tracked(idx._keys)
    assert sorted(idx) == [1, 2, 3]
    assert len(idx) == 3 and 2 in idx
    plain = set()
    plain |= idx  # harness population scans do exactly this
    assert plain == {1, 2, 3}
    assert (idx | {4}) == {1, 2, 3, 4}
    idx.update([4, 5])
    idx.remove(1)
    with pytest.raises(KeyError):
        idx.remove(1)
    idx |= {9}
    idx -= {5}
    assert sorted(idx) == [2, 3, 4, 9]
    got = idx.contains_many(np.array([1, 2, 9], dtype=np.uint64))
    np.testing.assert_array_equal(got, [False, True, True])
    idx.check_consistency()
    idx.clear()
    assert len(idx) == 0
    idx.check_consistency()


SET_API = [
    ("in", lambda idx: (2 in idx, 7 in idx, np.uint64(3) in idx), (True, False, True)),
    ("len", len, 3),
    ("iteration", lambda idx: sorted(k for k in idx), [1, 2, 3]),
    ("sorted", sorted, [1, 2, 3]),
    ("union_with_set", lambda idx: (idx | {4}, {4} | idx), ({1, 2, 3, 4}, {1, 2, 3, 4})),
    ("difference_with_set", lambda idx: (idx - {1}, {1, 5} - idx), ({2, 3}, {5})),
    ("equality", lambda idx: (idx == {1, 2, 3}, {1, 2, 3} == idx, idx == {1}), (True, True, False)),
    ("ior_into_plain_set", lambda idx: set().__ior__(set(idx)) | idx, {1, 2, 3}),
]


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
@pytest.mark.parametrize("name,op,want", SET_API, ids=[c[0] for c in SET_API])
def test_read_only_set_api(name, op, want, backend):
    """Each read-only set operation a consumer uses, after scalar and batched
    mutations (so the answer comes from the authoritative dict, not a copy);
    results built by the binary operators are plain sets."""
    idx = FingerprintIndex([3, 9], backend=backend, small_batch=0)
    idx.add_many(np.array([1, 2, 9], dtype=np.uint64))
    idx.discard(9)
    got = op(idx)
    assert got == want
    for part in got if isinstance(got, tuple) else (got,):
        assert not isinstance(part, FingerprintIndex)
    assert not gc.is_tracked(idx._keys)
    idx.check_consistency()


def test_rebuild_from_keys_matches_original():
    """The restore path: an index rebuilt from its key list (exactly what
    engine snapshots serialize) answers every probe identically."""
    rng = np.random.default_rng(5)
    idx = FingerprintIndex(small_batch=0)
    ks = np.unique(_keys(rng, 5000))
    idx.add_many(ks)
    idx.remove_many(ks[::3])
    restored = FingerprintIndex(sorted(idx), small_batch=0)
    probe = np.concatenate([ks, _keys(rng, 1000)])
    np.testing.assert_array_equal(idx.contains_many(probe), restored.contains_many(probe))
    assert set(idx) == set(restored)
    restored.check_consistency()


# ---------------------------------------------------------------------------
# Kernel <-> numpy backend equivalence (membership, not layout).
# ---------------------------------------------------------------------------


def test_backends_agree_on_membership():
    rng = np.random.default_rng(13)
    ks = np.unique(_keys(rng, 2000))
    a = FingerprintIndex(capacity=4096, small_batch=0, backend="numpy")
    b = FingerprintIndex(capacity=4096, small_batch=0, backend="pallas")
    a.add_many(ks)
    b.add_many(ks)
    a.remove_many(ks[::5])
    b.remove_many(ks[::5])
    probe = np.concatenate([ks, _keys(rng, 500)])
    np.testing.assert_array_equal(a.contains_many(probe), b.contains_many(probe))
    a.check_consistency()
    b.check_consistency()


def test_slot_hash_host_matches_kernel():
    import jax.numpy as jnp

    from repro.kernels.fp_index import _slot_hash_jnp

    rng = np.random.default_rng(17)
    lo = rng.integers(0, 1 << 32, size=512, dtype=np.uint32)
    hi = rng.integers(0, 1 << 32, size=512, dtype=np.uint32)
    host = slot_hash_host(lo, hi)
    dev = np.asarray(_slot_hash_jnp(jnp.asarray(lo), jnp.asarray(hi)))
    np.testing.assert_array_equal(host, dev)


def test_kernel_probe_against_numpy_table():
    """The Pallas probe must answer exactly over a table the numpy backend
    built (shared layout contract), and vice versa."""
    from repro.kernels.ops import fp_index_insert, fp_index_probe

    rng = np.random.default_rng(19)
    idx = FingerprintIndex(capacity=1024, small_batch=0, backend="numpy")
    ks = np.unique(_keys(rng, 500))
    idx.add_many(ks)
    idx.contains_many(ks)  # flush pending into the table
    tlo, thi = idx._lanes()
    cap = idx.table_stats()["capacity"]
    lo = (ks & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (ks >> np.uint64(32)).astype(np.uint32)
    got = fp_index_probe(lo, hi, tlo, thi, cap)
    np.testing.assert_array_equal(got, np.ones(ks.size, bool))
    absent = np.setdiff1d(_keys(rng, 300), ks)
    alo = (absent & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    ahi = (absent >> np.uint64(32)).astype(np.uint32)
    assert not fp_index_probe(alo, ahi, tlo, thi, cap).any()
    # kernel insert into the numpy-built table: duplicates are PRESENT
    _, _, status = fp_index_insert(lo[:32], hi[:32], tlo.copy(), thi.copy(), cap)
    assert (status == 1).all()


def test_window_is_positive_sane():
    assert WINDOW >= 4  # the bounded-window contract the docs describe


# ---------------------------------------------------------------------------
# Cluster-wide probe: one batched launch per owning shard, vs a host oracle.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ring", ["fresh", "resized"])
@pytest.mark.parametrize("num_shards", [1, 4])
def test_cluster_probe_fps_matches_oracle(ring, num_shards):
    """``probe_fps`` routes each key to its owner on the current ring: on the
    ring the cluster was built with, and after a resize moved keys."""
    from repro.core import ShardedCluster, generate_workload

    trace, _ = generate_workload("B", total_requests=4_000, seed=11)
    cluster = ShardedCluster(num_shards=num_shards, cache_entries=256)
    cluster.replay_batched(trace)
    if ring == "resized":
        assert cluster.resize(num_shards + 1)["moved_fps"] > 0
    written = {int(r["fp"]) for r in trace if r["op"] == 0}

    rng = np.random.default_rng(5)
    probe = np.concatenate(
        [
            np.fromiter(written, dtype=np.uint64, count=len(written)),
            _keys(rng, 2_000),  # mostly absent
        ]
    )
    rng.shuffle(probe)
    got = cluster.probe_fps(probe)
    want = np.fromiter(
        (int(k) in written for k in probe.tolist()), dtype=bool, count=probe.size
    )
    np.testing.assert_array_equal(got, want)
    assert cluster.probe_fps(np.empty(0, dtype=np.uint64)).size == 0


def test_overflow_spill_consulted_when_sentinels_also_spilled():
    """Regression: the spill fast-path's sentinel allowance must count each
    sentinel once.  With fingerprint 0 spilled alongside exactly one
    window-overflow key, a miscounted allowance skipped the spill set and
    produced a false negative for the overflow key."""
    cap = 128
    idx = FingerprintIndex(capacity=cap, small_batch=0)
    target, ks, k = None, [], 1
    while len(ks) < WINDOW + 1:  # WINDOW+1 keys sharing one home slot
        lo = np.uint32(k & 0xFFFFFFFF)
        hi = np.uint32(k >> 32)
        h = int(slot_hash_host(np.array([lo]), np.array([hi]))[0]) & (cap - 1)
        if target is None:
            target = h
        if h == target:
            ks.append(k)
        k += 1
    idx.add_many(np.array(ks, dtype=np.uint64))
    idx.check_consistency()  # fold the journaled insert so the overflow spills
    assert idx.spilled() == 1  # exactly one overflow spill
    for extra in (EMPTY_KEY, TOMB_KEY):
        idx.add(extra)
        flags = idx.contains_many(np.array(ks, dtype=np.uint64))
        np.testing.assert_array_equal(flags, np.ones(len(ks), bool))
    idx.check_consistency()


# ---------------------------------------------------------------------------
# Edge paths: rebuild/growth racing staged mutations, spill/sentinel removal.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_tombstone_rebuild_with_pending_mutations_in_flight(backend):
    """Tombstone pressure (> cap/4) triggers a rebuild at the next flush.
    The rebuild must fold staged-but-unflushed mutations — a scalar-add
    pending dict, a journaled ``add_many``, and scalar discards — instead
    of dropping them with the tombstones."""
    rng = np.random.default_rng(23)
    idx = FingerprintIndex(capacity=256, small_batch=0, backend=backend)
    ks = np.unique(_keys(rng, 170))
    idx.add_many(ks)
    idx.contains_many(ks)  # fold the journal so removals hit table slots
    cap = idx.table_stats()["capacity"]  # the fold may have grown the table
    # tombstone well past the cap//4 rebuild threshold, but don't flush yet
    drop = ks[: cap // 4 + 12]
    assert drop.size < ks.size
    idx.remove_many(drop)
    assert idx.table_stats()["tombstones"] > cap // 4
    oracle = set(ks.tolist()) - set(drop.tolist())
    # stage every mutation flavour while the rebuild is pending
    fresh = np.unique(_keys(rng, 64))
    idx.add_many(fresh)  # journaled
    oracle.update(fresh.tolist())
    for k in ks[-8:].tolist():  # scalar re-adds of still-present keys
        idx.add(k)
    for k in drop[:4].tolist():  # scalar re-adds of tombstoned keys
        idx.add(k)
        oracle.add(k)
    for k in ks[-4:].tolist():  # scalar discards staged behind the re-adds
        idx.discard(k)
        oracle.discard(k)
    # the flush inside this batched probe performs the tombstone rebuild
    probe = np.concatenate([ks, drop, fresh, _keys(rng, 256)])
    got = idx.contains_many(probe)
    want = np.fromiter((int(k) in oracle for k in probe), dtype=bool, count=probe.size)
    np.testing.assert_array_equal(got, want)
    assert idx.table_stats()["tombstones"] <= cap // 4  # pressure actually relieved
    assert set(idx) == oracle
    idx.check_consistency()


def test_remove_many_of_spilled_and_sentinel_keys():
    """``remove_many`` over a batch mixing window-overflow spills, both
    sentinel keys, table-resident keys, and absent keys: spills and
    sentinels leave the spill set, residents tombstone, absents no-op."""
    cap = 128
    idx = FingerprintIndex(capacity=cap, small_batch=0)
    ks, target, k = [], None, 1
    while len(ks) < WINDOW + 1:  # WINDOW+1 keys sharing one home slot
        lo = np.uint32(k & 0xFFFFFFFF)
        hi = np.uint32(k >> 32)
        h = int(slot_hash_host(np.array([lo]), np.array([hi]))[0]) & (cap - 1)
        if target is None:
            target = h
        if h == target:
            ks.append(k)
        k += 1
    idx.add_many(np.array(ks, dtype=np.uint64))
    idx.add(EMPTY_KEY)
    idx.add(TOMB_KEY)
    idx.contains_many(np.array(ks, dtype=np.uint64))  # fold -> overflow spills
    assert idx.spilled() == 3  # one overflow + two sentinels
    absent = np.array([999_999_999], dtype=np.uint64)
    batch = np.concatenate(
        [np.array([EMPTY_KEY, TOMB_KEY], dtype=np.uint64), np.array(ks, dtype=np.uint64), absent]
    )
    idx.remove_many(batch)
    assert idx.spilled() == 0
    assert len(idx) == 0
    np.testing.assert_array_equal(idx.contains_many(batch), np.zeros(batch.size, bool))
    idx.check_consistency()


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_grow_during_probe_and_add(backend):
    """A single ``probe_and_add`` batch large enough to force a capacity
    rebuild mid-call: the returned flags must still be exact (known keys
    flagged, fresh keys inserted once) against the host oracle."""
    rng = np.random.default_rng(29)
    idx = FingerprintIndex(capacity=128, small_batch=0, backend=backend)
    cap0 = idx.table_stats()["capacity"]
    seed = np.unique(_keys(rng, 30))
    idx.add_many(seed)
    oracle = set(seed.tolist())
    # one batch several times the current capacity: the flush inside
    # probe_and_add must grow before inserting the fresh tail
    batch = np.unique(np.concatenate([seed, _keys(rng, 4 * cap0)]))
    known = idx.probe_and_add(batch)
    want_known = np.fromiter(
        (int(k) in oracle for k in batch), dtype=bool, count=batch.size
    )
    np.testing.assert_array_equal(known, want_known)
    assert idx.table_stats()["capacity"] > cap0  # the grow actually happened
    oracle.update(batch.tolist())
    assert set(idx) == oracle
    # every key (pre-grow residents and post-grow inserts) probes present
    np.testing.assert_array_equal(idx.contains_many(batch), np.ones(batch.size, bool))
    idx.check_consistency()


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_add_many_after_pending_discard_stays_resident(backend):
    """A table-resident key discarded (tombstone pending) and then re-added
    by ``add_many`` before any flush must stay a member: the fold finds it
    still in the table, so its pending tombstone has to be dropped."""
    idx = FingerprintIndex(capacity=32, small_batch=0, backend=backend)
    idx.add(1)
    idx.remove_many(np.array([0], dtype=np.uint64))  # flushes: 1 reaches the table
    idx.discard(1)
    idx.add_many(np.array([1], dtype=np.uint64))
    np.testing.assert_array_equal(idx.contains_many(np.array([1], dtype=np.uint64)), [True])
    assert set(idx) == {1}
    idx.check_consistency()


# ---------------------------------------------------------------------------
# The bulk form of add/discard against the scalar calls in order.
# ---------------------------------------------------------------------------


def _shared_home_keys(n, cap=1 << 12, seed=0):
    """``n`` keys with one home slot in every table of up to ``cap`` slots:
    past ``WINDOW`` of them, the rest spill."""
    keys = _keys(np.random.default_rng(seed), 1 << 18)
    keys = keys[(keys != EMPTY_KEY) & (keys != TOMB_KEY)]
    lo = (keys & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (keys >> np.uint64(32)).astype(np.uint32)
    home = slot_hash_host(lo, hi) & np.uint32(cap - 1)
    best = np.bincount(home).argmax()
    return keys[home == best][:n].tolist()


def _buffers(idx):
    return (list(idx._keys), list(idx._pending_adds), list(idx._pending_removes),
            set(idx._spill))


@pytest.mark.parametrize("seed", range(6))
def test_add_discard_matches_the_scalar_calls_in_order(seed):
    """Random interleavings of admissions (adds of non-members) and
    evictions (discards of members), applied one call at a time to one index
    and as the two ordered lists to another: every buffer matches, dict
    order included, and each fold leaves the same table."""
    rng = np.random.default_rng(seed)
    crowd = _shared_home_keys(WINDOW + 6)
    pool = [int(k) for k in _keys(rng, 120)] + crowd
    scalar = FingerprintIndex(capacity=128, small_batch=0)
    bulk = FingerprintIndex(capacity=128, small_batch=0)
    members = []
    for rnd in range(60):
        # half the rounds touch each key once (the whole-list path); the
        # others re-admit evicted keys, repeat keys and use the sentinels
        once = rnd % 2 == 0
        touched = set()
        keys = pool if once else pool + [EMPTY_KEY, TOMB_KEY]
        adds, drops = [], []
        for _ in range(int(rng.integers(0, 50))):
            if members and rng.random() < 0.45:
                free = [k for k in members if not (once and k in touched)]
                if not free:
                    continue
                k = free[int(rng.integers(0, len(free)))]
                members.remove(k)
                scalar.discard(k)
                drops.append(k)
            else:
                free = [k for k in keys if k not in members and not (once and k in touched)]
                if not free:
                    continue
                k = free[int(rng.integers(0, len(free)))]
                members.append(k)
                scalar.add(k)
                adds.append(k)
            touched.add(k)
        bulk.add_discard(adds, drops)
        assert _buffers(bulk) == _buffers(scalar)
        assert set(bulk) == set(members)
        if rng.random() < 0.5:
            scalar.check_consistency()
            bulk.check_consistency()
            np.testing.assert_array_equal(bulk._t64, scalar._t64)
            assert bulk.table_stats() == scalar.table_stats()
    assert scalar.spilled() > 0
