"""The engines' aged state stays out of CPython's cyclic garbage collector.

A full collection walks every container the collector tracks, so per-
fingerprint lists or sets in engine state make every full pass cost time in
proportion to how far the store has aged.  The store, the fingerprint
indexes and the caches hold that state in containers the collector does not
track (dicts whose keys and values are ints), so ageing a cluster from a
few thousand fingerprints to tens of thousands adds no tracked objects.
"""

from __future__ import annotations

import gc
import json

import numpy as np
import pytest

from repro.core import (
    DIODE,
    HPDedup,
    PurePostProcessing,
    ShardedCluster,
    engine_finish_replay,
    engine_ingest,
    make_idedup,
    restore_engine,
    snapshot_engine,
)
from repro.core.fingerprint import OP_WRITE, TRACE_DTYPE
from repro.core.fp_index import FingerprintIndex
from repro.core.snapshot import report_to_tree
from repro.core.store import BlockStore, lba_key, lba_of_key
from repro.core.traces import generate_workload

ENGINES = {
    "hpdedup": lambda shard: HPDedup(cache_entries=512, seed=shard),
    "idedup": lambda shard: make_idedup(cache_entries=512, seed=shard),
    "diode": lambda shard: DIODE(cache_entries=512, seed=shard),
    "postprocessing": lambda shard: PurePostProcessing(),
}


def _indexes(engine):
    """Every ``FingerprintIndex`` the engine holds."""
    held = [engine.store.fp_index]
    for owner, attr in ((engine, "_seen_fps"), (engine, "_seen"),
                        (getattr(engine, "inline", engine), "cache")):
        obj = getattr(owner, attr, None)
        if attr == "cache" and obj is not None:
            obj = obj.index
        if isinstance(obj, FingerprintIndex):
            held.append(obj)
    return held


@pytest.fixture(scope="module")
def writes():
    trace, _ = generate_workload("A", total_requests=100_000, seed=11)
    return trace[trace["op"] == OP_WRITE]


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_ageing_adds_no_tracked_objects(engine, writes):
    cluster = ShardedCluster(num_shards=2, engine_factory=ENGINES[engine])
    fps = writes["fp"]
    # the first cut holds ~2k distinct fingerprints, the whole trace ~20k+
    cut = int(np.searchsorted(np.cumsum(~_seen_before(fps)), 2_000))
    cluster.ingest_batched(writes[:cut])
    gc.collect()
    small = len(gc.get_objects())
    distinct_small = sum(len(e.store.fp_table) for e in cluster.shards)
    cluster.ingest_batched(writes[cut:])
    # never tracked, not merely untracked by the last full pass: no
    # collection runs between the ingest and this check
    for e in cluster.shards:
        st = e.store
        # the duplicate candidates' map itself holds dicts, so it is tracked;
        # each of its rows is not
        held = [st._fp_pba, st._lba_pba, st.refcount, st.fp_of_pba, st.lbas_of_pba]
        held += list(st._dup_fps.values()) + [idx._keys for idx in _indexes(e)]
        assert not any(gc.is_tracked(c) for c in held)
        assert len(_indexes(e)) == {"postprocessing": 2}.get(engine, 3)
    gc.collect()
    large = len(gc.get_objects())
    distinct_large = sum(len(e.store.fp_table) for e in cluster.shards)
    assert distinct_large - distinct_small > 15_000
    assert large - small < 0.05 * (distinct_large - distinct_small), (small, large)
    for e in cluster.shards:
        e.store.check_consistency()


KEYS = [(0, 0), (3, 17), (2**31 - 1, 2**63 - 1), (5, -1), (0, -(2**63)), (-4, 9)]


@pytest.mark.parametrize("stream,lba", KEYS)
def test_lba_key_is_exact_for_int64_lbas(stream, lba):
    key = lba_key(stream, lba)
    assert lba_of_key(key) == (stream, lba)
    assert len({lba_key(*k) for k in KEYS}) == len(KEYS)


def test_lba_map_view_reads_like_the_tuple_keyed_dict():
    st = BlockStore()
    for stream, lba, fp in [(0, 5, 1), (1, 5, 2), (0, 6, 1), (0, 5, 3)]:  # last overwrites
        st.write_new_block(stream, lba, fp)
    want = {(0, 5): 3, (1, 5): 1, (0, 6): 2}
    view = st.lba_map
    assert dict(view) == want and dict(view.items()) == want and view == want
    assert list(view) == [(0, 5), (1, 5), (0, 6)]  # insertion order, as the dict's
    assert (1, 5) in view and (1, 6) not in view and view.get((1, 6)) is None
    assert len(view) == 3 and view[(0, 6)] == 2
    assert not gc.is_tracked(st._lba_pba) and not gc.is_tracked(st.lbas_of_pba)
    st.check_consistency()


def _seen_before(fps: np.ndarray) -> np.ndarray:
    """Per write, whether its fingerprint was written earlier in the trace."""
    _, first = np.unique(fps, return_index=True)
    seen = np.ones(fps.size, dtype=bool)
    seen[first] = False
    return seen


# A snapshot the previous store layout wrote (fingerprint rows as lists, the
# seen set a ``set`` subclass), after 24 of TINY's 48 writes through
# ``engine_ingest(HPDedup(cache_entries=2, seed=0), ..., 8)``, and that
# layout's report for the whole trace replayed uninterrupted.
TINY_STREAMS = [0, 0, 0, 0, 1, 1, 1, 1] * 6
TINY_LBAS = [0, 1, 2, 3, 0, 1, 2, 3, 4, 5, 6, 7, 4, 5, 6, 7, 0, 1, 8, 9, 8, 9, 10, 11,
             12, 13, 2, 3, 12, 13, 14, 15, 16, 17, 18, 19, 0, 1, 16, 17, 20, 21, 4, 5, 18, 19,
             20, 21]
TINY_FPS = [1, 2, 3, 4, 1, 2, 5, 6, 7, 8, 9, 1, 7, 8, 3, 4, 5, 6, 7, 8, 1, 2, 3, 4,
            9, 1, 2, 3, 5, 6, 7, 8, 1, 2, 3, 4, 9, 8, 7, 6, 5, 4, 3, 2, 1, 2, 3, 4]
OLD_TREE = json.loads(
    '{"format":"hpdedup-state-tree","version":2,"kind":"hpdedup","state":{"config":{"cache_entrie'
    's":2,"policy":"lru","sampling_rate":0.15,"interval_factor":0.5,"adaptive_threshold":true,"fi'
    'xed_threshold":4,"prioritized":true,"use_jax_estimator":false,"use_unseen":true,"postprocess'
    '_period":0,"data_buffer_blocks":4096,"seed":0},"store":{"lba_map":[[0,0,16],[0,1,17],[0,2,2]'
    ',[0,3,3],[1,0,4],[1,1,5],[1,2,6],[1,3,7],[0,4,8],[0,5,9],[0,6,10],[0,7,11],[1,4,12],[1,5,13]'
    ',[1,6,14],[1,7,15],[0,8,18],[0,9,19],[1,8,20],[1,9,21],[1,10,22],[1,11,23]],"fp_table":[[1,['
    '4,11,20]],[2,[5,21]],[3,[2,14,22]],[4,[3,15,23]],[5,[6,16]],[6,[7,17]],[7,[8,12,18]],[8,[9,1'
    '3,19]],[9,[10]]],"refcount":[[2,1],[3,1],[4,1],[5,1],[6,1],[7,1],[8,1],[9,1],[10,1],[11,1],['
    '12,1],[13,1],[14,1],[15,1],[16,1],[17,1],[18,1],[19,1],[20,1],[21,1],[22,1],[23,1]],"fp_of_p'
    'ba":[[2,3],[3,4],[4,1],[5,2],[6,5],[7,6],[8,7],[9,8],[10,9],[11,1],[12,7],[13,8],[14,3],[15,'
    '4],[16,5],[17,6],[18,7],[19,8],[20,1],[21,2],[22,3],[23,4]],"next_pba":24,"live_blocks":22,"'
    'peak_blocks":22,"disk_writes":24,"freed_blocks":2,"ever_freed":true,"lba_watermark":[[0,10],'
    '[1,12]],"buffer":{"capacity":4096,"lru":[16,17,18,19,20,21,22,23],"hits":0,"misses":8},"gc":'
    '{"epoch":0,"limbo":[],"free_pbas":[0,1],"deferred":false,"relocated":0}},"inline":{"metrics"'
    ':{"writes":24,"reads":0,"inline_dups":0,"cache_hits":0,"broken_runs":0,"cache_inserted":0,"p'
    'er_stream_dups":[],"per_stream_writes":[[0,12],[1,12]]},"cache":{"rng":{"bit_generator":"PCG'
    '64","state":{"state":178882347208143646640022022879736081149,"inc":8713637251758298955547815'
    '9403783844777},"has_uint32":0,"uinteger":0},"streams":[[0,{"kind":"lru","items":[]}],[1,{"ki'
    'nd":"lru","items":[[3,22],[4,23]]}]],"owner":[[3,1],[4,1]],"ldss":[],"best_ldss":0.0,"total"'
    ':2,"inserted":24,"segments":{"size":64,"tree":[0.0,0.0,1.0,0.0,1.0,0.0,0.0,0.0,1.0,0.0,0.0,0'
    '.0,0.0,0.0,0.0,0.0,1.0,0.0,0.0,0.0,0.0,0.0,0.0,0.0,0.0,0.0,0.0,0.0,0.0,0.0,0.0,0.0,1.0,0.0,0'
    '.0,0.0,0.0,0.0,0.0,0.0,0.0,0.0,0.0,0.0,0.0,0.0,0.0,0.0,0.0,0.0,0.0,0.0,0.0,0.0,0.0,0.0,0.0,0'
    '.0,0.0,0.0,0.0,0.0,0.0,0.0,1.0],"weights":[[1,1.0]],"slot_of":[[1,1]],"free":[63,62,61,60,59'
    ',58,57,56,55,54,53,52,51,50,49,48,47,46,45,44,43,42,41,40,39,38,37,36,35,34,33,32,31,30,29,2'
    '8,27,26,25,24,23,22,21,20,19,18,17,16,15,14,13,12,11,10,9,8,7,6,5,4,3,2,0]}},"estimator":{"i'
    'nterval_len":256,"interval_factor":0.5,"reservoirs":{"0":{"k":38,"buf":[1,2,3,4,7,8,9,1,5,6,'
    '7,8],"seen":12,"rng":{"bit_generator":"PCG64","state":{"state":35399562948360463058890781895'
    '381311971,"inc":87136372517582989555478159403783844777},"has_uint32":0,"uinteger":0}},"1":{"'
    'k":38,"buf":[1,2,5,6,7,8,3,4,1,2,3,4],"seen":12,"rng":{"bit_generator":"PCG64","state":{"sta'
    'te":207833532711051698738587646355624148094,"inc":194290289479364712180083596243593368443},"'
    'has_uint32":0,"uinteger":0}}},"stream_writes":{"0":12,"1":12},"history":{"0":[],"1":[]},"pre'
    'dicted":{},"interval_count":0,"writes_in_interval":24,"interval_dups":0,"last_ratio":null,"e'
    'stimations":0},"thresholds":{"v_w":[[0,[0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,'
    '0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0]],[1,[0,0,0,0,0,'
    '0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,'
    '0,0,0,0,0,0,0,0,0,0,0,0,0]]],"v_r":[[0,[0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,'
    '0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0]],[1,[0,0,0,0,0,'
    '0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,'
    '0,0,0,0,0,0,0,0,0,0,0,0,0]]],"threshold":[[0,16.0],[1,16.0]],"reads":[[0,0],[1,0]],"writes":'
    '[[0,12],[1,12]],"dups":[[0,0],[1,0]],"ratio_at_update":[[0,0.0],[1,0.0]],"updates":0},"pendi'
    'ng":[],"read_runs":[]},"post_metrics":{"passes":0,"merges":0,"blocks_reclaimed":0},"writes_s'
    'ince_post":24,"total_writes":24,"dup_writes":15,"seen_fps":[1,2,3,4,5,6,7,8,9]}}'
)
OLD_REPORT = json.loads(
    '{"inline":{"writes":48,"reads":0,"inline_dups":0,"cache_hits":1,"broken_runs":1,"cache_inser'
    'ted":47,"per_stream_dups":[],"per_stream_writes":[[0,24],[1,24]]},"post":{"passes":1,"merges'
    '":9,"blocks_reclaimed":31},"peak_disk_blocks":40,"final_disk_blocks":9,"unique_fingerprints"'
    ':9,"total_writes":48,"total_dup_writes":39}'
)


def _tiny():
    t = np.zeros(len(TINY_FPS), dtype=TRACE_DTYPE)
    t["stream"], t["lba"], t["fp"], t["op"] = TINY_STREAMS, TINY_LBAS, TINY_FPS, OP_WRITE
    return t


def test_snapshot_from_the_previous_layout_restores_bit_exact():
    """Rows with several PBAs, freed blocks and the seen list in the old
    tree load into the new layout, serialize back to the same tree, and
    finish the trace with the report the previous layout gave."""
    restored = restore_engine(json.loads(json.dumps(OLD_TREE)))
    assert restored.store.fp_table[1] == [4, 11, 20]
    assert json.loads(json.dumps(snapshot_engine(restored))) == OLD_TREE
    engine_ingest(restored, _tiny()[24:], 8)
    engine_finish_replay(restored)
    ref = HPDedup(cache_entries=2, seed=0)
    ref.replay_batched(_tiny(), batch_size=8)
    assert report_to_tree(restored.finish()) == OLD_REPORT == report_to_tree(ref.finish())
    assert OLD_REPORT["post"]["merges"] > 0  # the merge path ran over the old rows
    restored.store.check_consistency()
