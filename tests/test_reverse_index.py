"""The store's PBA -> LBA reverse index, kept current by deltas.

``BlockStore._ensure_reverse`` adds the keys staged since it last ran,
read off the end of the LBA map's insertion order, instead of rebuilding
from the whole map.  Over seeded mixes of staged writes, staged duplicates,
merges, unmaps, overwrites and relocations the index it keeps equals a full
rebuild; a post-processing pass walks about the writes since the last pass,
however large the volume; and a store whose engine never merges before
``finish()`` walks nothing and keeps nothing per write for it.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from repro.core import HPDedup, generate_workload
from repro.core.fingerprint import OP_WRITE
from repro.core.postprocess import PostProcessEngine
from repro.core.store import BlockStore, lba_key, lba_of_key


def _rebuilt(store: BlockStore) -> dict:
    """The reverse index a whole-map rebuild gives: PBA -> set of keys."""
    out: dict = {}
    for key, pba in store._lba_pba.items():
        out.setdefault(pba, set()).add(key)
    return out


def _kept(store: BlockStore) -> dict:
    store._ensure_reverse()
    out = {pba: {key} for pba, key in store.lbas_of_pba.items()}
    out.update({pba: set(keys) for pba, keys in store._shared_lbas.items()})
    return out


def _mutate(store: BlockStore, rng: np.random.Generator, steps: int) -> None:
    """Random batches of staged writes (fresh keys only, as the batched
    driver certifies them), each followed by a flush and a few scalar
    operations: merges, unmaps, overwrites, fresh scalar writes and
    compaction steps, checking the kept index against a rebuild."""
    next_lba = {s: 0 for s in range(4)}
    for key in store._lba_pba:  # staged keys are fresh: start above what is mapped
        s, lba = lba_of_key(key)
        next_lba[s] = max(next_lba[s], lba + 1)
    for _ in range(steps):
        for _ in range(int(rng.integers(1, 40))):
            s = int(rng.integers(0, 4))
            lba = next_lba[s]
            next_lba[s] += 1
            fp = int(rng.integers(1, 30))
            canon = store.lookup_fp(fp)
            if canon is not None and rng.random() < 0.5:
                store.stage_duplicate(s, lba, canon)
            else:
                store.stage_new_block(s, lba, fp)
        store.flush_staged()
        for _ in range(int(rng.integers(0, 4))):
            op = int(rng.integers(0, 5))
            mapped = list(store._lba_pba)
            if op == 0:
                PostProcessEngine(store).run(max_merges=int(rng.integers(1, 4)))
            elif op == 1 and mapped:
                store.unmap(*lba_of_key(mapped[int(rng.integers(0, len(mapped)))]))
            elif op == 2 and mapped:
                stream, lba = lba_of_key(mapped[int(rng.integers(0, len(mapped)))])
                store.write_new_block(stream, lba, int(rng.integers(1, 30)))
            elif op == 3:
                s = int(rng.integers(0, 4))
                store.write_new_block(s, next_lba[s], int(rng.integers(1, 30)))
                next_lba[s] += 1
            else:
                store.compact(max_moves=int(rng.integers(1, 8)))
            assert _kept(store) == _rebuilt(store)
        assert _kept(store) == _rebuilt(store)
    store.check_consistency()


@pytest.mark.parametrize("seed", range(6))
def test_kept_reverse_index_equals_a_full_rebuild(seed):
    store = BlockStore()
    _mutate(store, np.random.default_rng(seed), steps=60)
    PostProcessEngine(store).run_to_exact()
    assert _kept(store) == _rebuilt(store)
    store.check_consistency()


def test_restore_rebuilds_from_the_map_and_keeps_deltas_after():
    store = BlockStore()
    _mutate(store, np.random.default_rng(11), steps=20)
    tree = store.snapshot()
    assert "reverse_keys_walked" not in json.dumps(tree)  # a counter, not state
    restored = BlockStore()
    restored.load_snapshot(json.loads(json.dumps(tree)))
    walked = restored.reverse_keys_walked
    assert _kept(restored) == _rebuilt(restored)
    assert restored.reverse_keys_walked - walked == len(restored._lba_pba)
    _mutate(restored, np.random.default_rng(12), steps=20)


def _writes(total: int, seed: int = 5):
    trace, _ = generate_workload("C", total_requests=total, seed=seed)
    return trace[trace["op"] == OP_WRITE]


def _walked_per_pass(monkeypatch, engine: HPDedup, writes, batch: int = 2048):
    """Keys each post-processing pass walked while ``writes`` were served."""
    real = PostProcessEngine.run
    walked = []

    def spy(self, max_merges=None):
        k0 = self.store.reverse_keys_walked
        out = real(self, max_merges)
        walked.append(self.store.reverse_keys_walked - k0)
        return out

    monkeypatch.setattr(PostProcessEngine, "run", spy)
    for i in range(0, writes.size, batch):
        w = writes[i:i + batch]
        engine.write_batch(w["stream"], w["lba"], w["fp"])
    monkeypatch.undo()
    return walked


@pytest.mark.parametrize("aged", [20_000, 80_000])
def test_a_pass_walks_the_writes_since_the_last_one_not_the_volume(monkeypatch, aged):
    period = 4096
    writes = _writes(int((aged + 8 * period) * 1.4))  # about 85% of requests write
    engine = HPDedup(cache_entries=1024, postprocess_period=period)
    engine.write_batch(writes["stream"][:aged], writes["lba"][:aged], writes["fp"][:aged])
    volume = len(engine.store._lba_pba)
    walked = _walked_per_pass(monkeypatch, engine, writes[aged:])
    assert len(walked) >= 6 and max(walked) > 0
    # each pass walks the staged keys since the last one (the period) and a
    # few of its own; a whole-map rebuild would walk the volume every pass
    assert max(walked) <= 2 * period < volume / 2
    engine.store.check_consistency()


def test_a_store_that_never_merges_keeps_nothing_per_write_for_it():
    writes = _writes(30_000)
    engine = HPDedup(cache_entries=1024, postprocess_period=0)
    for i in range(0, writes.size, 2048):
        w = writes[i:i + 2048]
        engine.write_batch(w["stream"], w["lba"], w["fp"])
    st = engine.store
    n = writes.size
    # nothing walked; the index holds only the few records the scalar path
    # wrote (estimator boundaries), which map eagerly as they always did
    assert st.reverse_keys_walked == 0
    assert len(st.lbas_of_pba) + len(st._shared_lbas) < n // 100
    # only the store's own state maps grow with the writes
    grows = {name for name, v in vars(st).items()
             if hasattr(v, "__len__") and not isinstance(v, str) and len(v) >= n // 20}
    assert grows <= {"_lba_pba", "fp_of_pba", "refcount", "_fp_pba", "fp_index", "_dup_fps"}
    # finish's exact pass takes the whole map once (less the scalar records
    # the index already held)
    engine.finish()
    mapped = len(st._lba_pba)
    assert mapped - n // 100 < st.reverse_keys_walked <= mapped
    st.check_consistency()


@pytest.mark.parametrize("grow", [(2, 4), (4, 3)])
def test_resharding_moves_keys_through_the_store_and_keeps_both_indexes(grow):
    """``resize`` takes a moved block's keys out of the source store with
    ``release_lbas``; the source's index stays equal to a rebuild and the
    destination's takes the keys with its next delta, passes included."""
    from repro.core import ShardedCluster

    before, after = grow
    trace, _ = generate_workload("C", total_requests=24_000, seed=3)
    half = trace.size // 2
    cluster = ShardedCluster(num_shards=before, routing="fingerprint", seed=0,
                             cache_entries=512, postprocess_period=1024)
    cluster.ingest_batched(trace[:half])
    stats = cluster.resize(after)
    assert stats["moved_blocks"] > 0
    for engine in cluster.shards:
        assert _kept(engine.store) == _rebuilt(engine.store)
    cluster.ingest_batched(trace[half:])
    cluster.run_postprocess(to_exact=True)
    for engine in cluster.shards:
        assert _kept(engine.store) == _rebuilt(engine.store)
        engine.store.check_consistency()


def test_release_lbas_takes_the_keys_out_of_the_map_and_the_index():
    store = BlockStore()
    _mutate(store, np.random.default_rng(21), steps=10)
    shared = next(iter(store._shared_lbas))  # a block several keys map to
    shared_keys = store.lbas_of(shared)
    store.stage_new_block(0, 10_000, 99)
    store.flush_staged()  # a staged key the index has not taken yet
    staged = store.lookup_fp(99)
    for pba, keys in ((staged, [lba_key(0, 10_000)]), (shared, shared_keys)):
        got = store.release_lbas(pba)
        assert sorted(got) == sorted(keys) and len(keys) >= 1
        assert not any(k in store._lba_pba for k in got)
        assert store.lbas_of(pba) == []
        assert _kept(store) == _rebuilt(store)
