"""Block store: the persistent layer under both dedup phases (paper §III-B/C).

Models the primary storage stack HPDedup manages:

* **LBA mapping table** — (stream, LBA) -> PBA (NVRAM in the paper), keyed
  by one packed int per (stream, LBA) (``lba_key``); ``lba_map`` is its
  read-only view with tuple keys.
* **On-disk fingerprint table** — fingerprint -> list of PBAs holding that
  content (the post-processing phase scans it; >1 PBA per fingerprint means
  inline missed a duplicate).  Held as fingerprint -> canonical PBA, with
  the whole row only for the fingerprints stored at more than one PBA
  (``fp_table`` is the read-only view of both).
* **Reference counts** — per-PBA; the garbage collector frees PBAs at 0.
* **D-LRU data buffer** — SSD staging buffer for recently accessed blocks.

Metrics exposed: live blocks, *peak* blocks (the paper's disk-capacity
requirement figure, Fig. 7), writes issued to disk.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from collections.abc import Mapping
from itertools import islice
from typing import Callable, Dict, List, Optional, Tuple

from .. import obs
from .fp_index import FingerprintIndex
from .statetree import from_pairs, pairs


class DLRUBuffer:
    """D-LRU staging buffer (CacheDedup's D-LRU, used for the SSD data buffer):
    an LRU over *deduplicated* blocks — keyed by PBA so duplicate content
    occupies one slot regardless of how many LBAs reference it."""

    def __init__(self, capacity_blocks: int):
        self.capacity = capacity_blocks
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        self.hits = 0
        self.misses = 0

    def access(self, pba: int) -> bool:
        hit = pba in self._lru
        if hit:
            self._lru.move_to_end(pba)
            self.hits += 1
        else:
            self.misses += 1
            self._lru[pba] = None
            while len(self._lru) > self.capacity:
                self._lru.popitem(last=False)
        return hit

    def invalidate(self, pba: int) -> None:
        self._lru.pop(pba, None)

    # -- snapshot/restore ------------------------------------------------------
    def snapshot(self) -> dict:
        return {"capacity": self.capacity, "lru": list(self._lru), "hits": self.hits,
                "misses": self.misses}

    def load_snapshot(self, tree: dict) -> None:
        self.capacity = int(tree["capacity"])
        self._lru = OrderedDict((int(p), None) for p in tree["lru"])
        self.hits = int(tree["hits"])
        self.misses = int(tree["misses"])


def lba_key(stream: int, lba: int) -> int:
    """A (stream, lba) key as one int, ``stream * 2**64 + lba``: exact for
    every int64 LBA.  A dict keyed by these (and holding ints) is one the
    garbage collector never tracks, where a fresh tuple key re-tracks it.
    Hot loops inline the same ``(stream << 64) + lba``."""
    return (stream << 64) + lba


def lba_of_key(key: int) -> Tuple[int, int]:
    """``lba_key``'s inverse."""
    stream = (key + (1 << 63)) >> 64
    return stream, key - (stream << 64)


class LbaMap(Mapping):
    """Read-only ``(stream, lba) -> PBA`` view of a store's LBA mapping
    table, which is keyed by ``lba_key`` ints."""

    __slots__ = ("_store",)

    def __init__(self, store: "BlockStore"):
        self._store = store

    def __getitem__(self, key: Tuple[int, int]) -> int:
        return self._store._lba_pba[lba_key(*key)]

    def __iter__(self):
        return map(lba_of_key, self._store._lba_pba)

    def __len__(self) -> int:
        return len(self._store._lba_pba)


class FpTable(Mapping):
    """Read-only ``fingerprint -> [PBA, ...]`` view of a store's fingerprint
    table, canonical PBA first: each lookup builds the list afresh from the
    store's two maps, so a reader cannot mutate the table through it."""

    __slots__ = ("_store",)

    def __init__(self, store: "BlockStore"):
        self._store = store

    def __getitem__(self, fp: int) -> List[int]:
        st = self._store
        pbas = st._dup_fps.get(fp)
        return list(pbas) if pbas is not None else [st._fp_pba[fp]]

    def __iter__(self):
        return iter(self._store._fp_pba)

    def __len__(self) -> int:
        return len(self._store._fp_pba)


class BlockStore:
    """Content store with LBA mapping, fingerprint table and refcounts.

    No per-fingerprint or per-block Python container lives in the store's
    state: the maps are keyed and valued by ints (a fingerprint's several
    PBAs are a dict of ints too), which CPython's cyclic garbage collector
    never tracks, so a full collection costs the same however far the store
    has aged.  Sets appear only for the few PBAs several LBAs share.
    """

    def __init__(self, data_buffer_blocks: int = 4096):
        # the LBA mapping table: lba_key(stream, lba) -> PBA; ``lba_map``
        # views it with (stream, lba) keys
        self._lba_pba: Dict[int, int] = {}
        # reverse index for remapping: PBA -> its one ``lba_key``, and for
        # the few PBAs several LBAs share, PBA -> the set of their keys in
        # a side map; read through ``lbas_of`` / ``pop_lbas``.  It covers the
        # first ``_rev_synced`` keys of ``_lba_pba`` in insertion order; the
        # staged path only appends keys, so the rest are the keys staged
        # since ``_ensure_reverse`` last ran, and that is all it walks
        self.lbas_of_pba: Dict[int, int] = {}
        self._shared_lbas: Dict[int, set] = {}
        self._rev_synced = 0
        # keys ``_ensure_reverse`` has walked (a counter, not state: outside
        # the snapshot)
        self.reverse_keys_walked = 0
        # the fingerprint table: fp -> canonical PBA (the first written);
        # ``fp_table`` views it as fp -> [PBA, ...]
        self._fp_pba: Dict[int, int] = {}
        # membership index over fp_table's key set (batched probes for the
        # serving layer and the cluster; derived, rebuilt on restore)
        self.fp_index = FingerprintIndex()
        # duplicate candidates: fingerprints currently stored at >1 PBA, each
        # with its whole PBA row as an insertion-ordered dict of PBA -> None
        # (canonical first; a dict of ints is one the collector does not
        # track, a list always is).  Replaces the full fp_table scan per
        # post-processing pass; ``duplicate_fingerprints`` sorts its keys so
        # merge order is a deterministic function of store content (and thus
        # identical between a live engine and one restored from its
        # snapshot).
        self._dup_fps: Dict[int, Dict[int, None]] = {}
        self.refcount: Dict[int, int] = {}
        self.fp_of_pba: Dict[int, int] = {}
        self.buffer = DLRUBuffer(data_buffer_blocks)
        self._next_pba = 0
        self.live_blocks = 0
        self.peak_blocks = 0
        self.disk_writes = 0
        # staged columnar write path (batched replay): see stage_new_block
        self._staged_writes: List[Tuple[int, int]] = []  # (fp, pba)
        self._staged_dups: List[int] = []  # pba
        # per-stream LBA watermark: strict upper bound over every LBA this
        # store has mapped (or that the batched driver has certified for
        # staging).  Lets the driver prove key-freshness without probing
        # lba_map per record.  Maintained by _map and _certify-time bulk
        # updates; an over-approximation is always safe (it only forces the
        # slow probe).
        self._lba_watermark: Dict[int, int] = {}
        # True once any PBA has ever been freed; until then a cached
        # (fp, pba) pair can never go stale, so run decisions may skip the
        # TOCTOU revalidation.
        self._ever_freed = False
        # reclaim accounting + hook: freed_blocks counts every PBA the GC
        # releases (overwrite unrefs and post-processing merges alike);
        # on_free, when set, observes each freed PBA — the serving layer
        # uses it to drop KV pages, the cluster to meter shard-local
        # cleanup windows.
        self.freed_blocks = 0
        self.on_free: Optional[Callable[[int], None]] = None
        # -- online GC (epoch/grace-period protocol) ---------------------------
        # A free splits into a *logical* part (unlink the fingerprint, LBA
        # reverse entries, refcount row — immediate, so a re-written
        # fingerprint can never dedup against the dead block) and a
        # *physical* part (freed_blocks / on_free / the hole joining
        # _free_pbas).  With ``deferred_reclaim`` on, the physical part of a
        # free that lands while any epoch is pinned parks in ``_limbo`` until
        # every pin at or below its epoch tag drains (``collect_limbo``) —
        # in-flight work that may still hold a reference to the PBA finishes
        # before the slot is recycled.  Pins are process-local (writes in
        # flight); epoch/limbo/holes are durable state and are serialized.
        self.deferred_reclaim = False
        self.gc_epoch = 0
        self._epoch_lock = threading.Lock()
        self._epoch_pins: Dict[int, int] = {}  # epoch -> outstanding pin count
        self._limbo: List[Tuple[int, int]] = []  # (epoch tag, pba)
        # physically reclaimed PBA slots (range holes).  ``compact`` closes
        # them by relocating live blocks downward; only compaction ever
        # recycles a slot — fresh writes always allocate monotonically.
        self._free_pbas: List[int] = []
        self.relocated_blocks = 0
        # fires after a live block moved old -> new (the serving layer
        # relocates the matching KV page); state is already updated.
        self.on_relocate: Optional[Callable[[int, int], None]] = None

    # -- epoch protocol ----------------------------------------------------------
    def pin_epoch(self) -> int:
        """Register in-flight work under the current epoch; returns the tag
        to pass to ``unpin_epoch``.  While any pin at epoch <= t exists,
        blocks freed at tag t are reclaimed logically but not physically."""
        with self._epoch_lock:
            e = self.gc_epoch
            self._epoch_pins[e] = self._epoch_pins.get(e, 0) + 1
            return e

    def unpin_epoch(self, epoch: int) -> None:
        with self._epoch_lock:
            n = self._epoch_pins.get(epoch, 0) - 1
            if n > 0:
                self._epoch_pins[epoch] = n
            else:
                self._epoch_pins.pop(epoch, None)

    def advance_epoch(self) -> int:
        """Open a new grace period: frees from here on carry the new tag, so
        they outlive every pin taken before the advance."""
        with self._epoch_lock:
            self.gc_epoch += 1
            return self.gc_epoch

    def collect_limbo(self, force: bool = False) -> int:
        """Physically reclaim parked frees whose grace period drained.

        An entry tagged t is ready when no pin at epoch <= t remains (it can
        no longer be referenced by in-flight work).  ``force=True`` ignores
        pins — only valid at a full barrier (finish / resize quiesce), where
        nothing is in flight by construction.  Returns the reclaim count."""
        if not self._limbo:
            return 0
        with self._epoch_lock:
            horizon = None if force else min(self._epoch_pins, default=None)
            if horizon is None:
                ready, self._limbo = self._limbo, []
            else:
                ready = [ent for ent in self._limbo if ent[0] < horizon]
                if ready:
                    self._limbo = [ent for ent in self._limbo if ent[0] >= horizon]
        for _, pba in ready:
            self._reclaim(pba)
        return len(ready)

    # -- write path ------------------------------------------------------------
    def write_new_block(self, stream: int, lba: int, fp: int) -> int:
        """Write content to a fresh PBA (inline phase found no duplicate)."""
        pba = self._next_pba
        self._next_pba += 1
        self._add_pba(fp, pba)
        self.fp_of_pba[pba] = fp
        self.refcount[pba] = 0
        self._map(stream, lba, pba)
        self.live_blocks += 1
        self.peak_blocks = max(self.peak_blocks, self.live_blocks)
        self.disk_writes += 1
        self.buffer.access(pba)
        return pba

    def map_duplicate(self, stream: int, lba: int, pba: int) -> None:
        """Point an LBA at an existing PBA (inline dedup hit)."""
        self._map(stream, lba, pba)
        self.buffer.access(pba)

    # -- staged columnar write path (batched replay) ---------------------------
    #
    # The batched driver proves per sub-batch that no (stream, LBA) key is
    # overwritten (vectorized collision check), which means no refcount can
    # drop and no PBA can be freed mid-batch.  Under that guarantee the write
    # path splits into an *eager* part that later records in the same batch
    # may read (``lba_map`` for reads, ``fp_of_pba`` for the run-decision
    # TOCTOU guard) and a *deferred* part (``fp_table``/``refcount``/capacity
    # counters) applied in one pass by ``flush_staged`` before any external
    # observer (post-processing, reports) can look.  The reverse LBA index
    # takes the staged keys the next time remapping needs it
    # (``_ensure_reverse``), and the D-LRU buffer — whose state feeds no
    # report — is modeled only on the per-record path.

    def stage_new_block(self, stream: int, lba: int, fp: int) -> int:
        """Batched-path ``write_new_block``; caller guarantees (stream, lba)
        is not currently mapped."""
        pba = self._next_pba
        self._next_pba += 1
        self.fp_of_pba[pba] = fp
        self._lba_pba[(stream << 64) + lba] = pba
        self._staged_writes.append((fp, pba))
        return pba

    def stage_duplicate(self, stream: int, lba: int, pba: int) -> None:
        """Batched-path ``map_duplicate``; same no-overwrite precondition."""
        self._lba_pba[(stream << 64) + lba] = pba
        self._staged_dups.append(pba)

    def flush_staged(self) -> None:
        """Apply deferred accounting for staged writes in one columnar pass."""
        sw, sd = self._staged_writes, self._staged_dups
        if not sw and not sd:
            return
        if sw:
            ft = self._fp_pba
            ft_get = ft.get
            dups = self._dup_fps
            fresh_fps = []
            for fp, pba in sw:
                canon = ft_get(fp)
                if canon is None:
                    ft[fp] = pba
                    fresh_fps.append(fp)
                else:
                    row = dups.get(fp)
                    if row is None:
                        dups[fp] = {canon: None, pba: None}
                    else:
                        row[pba] = None
            if fresh_fps:
                self.fp_index.add_many(fresh_fps)
            # fresh PBAs start at refcount 1 (the write's own LBA mapping).
            # Staged PBAs are allocated monotonically, so within one batch
            # they almost always form one contiguous range — dict.fromkeys
            # over the range skips materializing the PBA list entirely.
            p0, p1 = sw[0][1], sw[-1][1]
            if p1 - p0 + 1 == len(sw):
                self.refcount.update(dict.fromkeys(range(p0, p1 + 1), 1))
            else:
                self.refcount.update(dict.fromkeys([p for _, p in sw], 1))
            self.live_blocks += len(sw)
            self.peak_blocks = max(self.peak_blocks, self.live_blocks)
            self.disk_writes += len(sw)
        if sd:
            rc = self.refcount
            rc_get = rc.get
            for pba in sd:
                rc[pba] = rc_get(pba, 0) + 1
        sw.clear()
        sd.clear()

    def _ensure_reverse(self) -> None:
        """Bring the PBA -> LBA-keys reverse index up to date: add the keys
        ``_lba_pba`` gained since the last call, read off the end of its
        insertion order, so the walk is as long as the writes staged since
        then, whatever the volume maps.

        Every other change to ``_lba_pba`` keeps the index current itself:
        an overwrite, a merge, a relocation or a migration changes a key's
        PBA only after this call and updates its reverse entry, and a key
        removed from the map (``unmap``, ``release_lbas``) takes
        ``_rev_synced`` down by one (removals happen only after this call).
        Adding a key the index already holds changes nothing, so walking a
        key twice is harmless."""
        lm = self._lba_pba
        n = len(lm) - self._rev_synced
        if n <= 0:
            return
        with obs.span("store.reverse", keys=n):
            single, shared = self.lbas_of_pba, self._shared_lbas
            first = single.setdefault
            tail = list(islice(reversed(lm.items()), n))
            for key, pba in reversed(tail):
                keys = shared.get(pba)
                if keys is not None:
                    keys.add(key)
                    continue
                other = first(pba, key)
                if other != key:  # a second reference: the PBA becomes shared
                    del single[pba]
                    shared[pba] = {other, key}
            self._rev_synced = len(lm)
            self.reverse_keys_walked += n

    # -- reverse index: PBA -> lba_key ints --------------------------------------
    def _rev_add(self, pba: int, key: int) -> None:
        shared = self._shared_lbas.get(pba)
        if shared is not None:
            shared.add(key)
            return
        other = self.lbas_of_pba.setdefault(pba, key)
        if other != key:  # a second reference: the PBA becomes shared
            del self.lbas_of_pba[pba]
            self._shared_lbas[pba] = {other, key}

    def _rev_discard(self, pba: int, key: int) -> None:
        shared = self._shared_lbas.get(pba)
        if shared is None:
            if self.lbas_of_pba.get(pba) == key:
                del self.lbas_of_pba[pba]
            return
        shared.discard(key)
        if len(shared) == 1:  # one reference left
            del self._shared_lbas[pba]
            self.lbas_of_pba[pba] = shared.pop()

    def lbas_of(self, pba: int) -> List[int]:
        """The ``lba_key``s the reverse index holds for ``pba``."""
        shared = self._shared_lbas.get(pba)
        if shared is not None:
            return list(shared)
        key = self.lbas_of_pba.get(pba)
        return [] if key is None else [key]

    def pop_lbas(self, pba: int) -> List[int]:
        """``lbas_of(pba)``, dropping the PBA's reverse entry."""
        shared = self._shared_lbas.pop(pba, None)
        if shared is not None:
            return list(shared)
        key = self.lbas_of_pba.pop(pba, None)
        return [] if key is None else [key]

    def release_lbas(self, pba: int) -> List[int]:
        """Take ``pba``'s keys out of the LBA map and the reverse index (its
        block leaves this store, as in resharding); returns the keys."""
        self._ensure_reverse()
        keys = self.pop_lbas(pba)
        lm = self._lba_pba
        for key in keys:
            del lm[key]
        self._rev_synced -= len(keys)
        return keys

    def put_lbas(self, pba: int, keys: List[int]) -> None:
        """Set ``pba``'s reverse entry to exactly ``keys`` (``lba_key``s)."""
        self.pop_lbas(pba)
        if len(keys) > 1:
            self._shared_lbas[pba] = set(keys)
        elif keys:
            self.lbas_of_pba[pba] = keys[0]

    # -- fingerprint table -------------------------------------------------------
    def _add_pba(self, fp: int, pba: int) -> None:
        """Append ``pba`` to ``fp``'s row (a new row enters the index)."""
        canon = self._fp_pba.get(fp)
        if canon is None:
            self._fp_pba[fp] = pba
            self.fp_index.add(fp)
            return
        row = self._dup_fps.get(fp)
        if row is None:
            self._dup_fps[fp] = {canon: None, pba: None}
        else:
            row[pba] = None

    def _drop_pba(self, fp: int, pba: int) -> None:
        """Remove ``pba`` from ``fp``'s row; an emptied row leaves the
        table and the index, a row left with one PBA stops being a
        duplicate candidate."""
        row = self._dup_fps.get(fp)
        if row is None:
            if self._fp_pba.get(fp) == pba:
                del self._fp_pba[fp]
                self.fp_index.discard(fp)
            return
        if pba not in row:
            return
        del row[pba]
        if len(row) == 1:
            del self._dup_fps[fp]
        self._fp_pba[fp] = next(iter(row))

    def _map(self, stream: int, lba: int, pba: int) -> None:
        key = (stream << 64) + lba
        lm = self._lba_pba
        old = lm.get(key)
        if old == pba:
            return
        if old is not None:
            # overwrite: the reverse index is about to be read and changed,
            # so it takes the staged keys first
            self._ensure_reverse()
            self._rev_discard(old, key)
            self._unref(old)
        lm[key] = pba
        self._rev_add(pba, key)
        if old is None and self._rev_synced == len(lm) - 1:
            self._rev_synced += 1  # nothing staged since the last call: covered
        self.refcount[pba] = self.refcount.get(pba, 0) + 1
        if lba >= self._lba_watermark.get(stream, 0):
            self._lba_watermark[stream] = lba + 1

    def unmap(self, stream: int, lba: int) -> Optional[int]:
        """Drop a key's mapping and unref its PBA (GC may free it).

        The cluster's router uses this as the cross-shard overwrite
        invalidation: when a key's newest content hashes to a different
        shard, the old owner must release its stale block.  Returns the
        unmapped PBA, or ``None`` if the key was not mapped.
        """
        key = (stream << 64) + lba
        if key not in self._lba_pba:
            return None
        self._ensure_reverse()
        pba = self._lba_pba.pop(key)
        self._rev_synced -= 1
        self._rev_discard(pba, key)
        self._unref(pba)
        return pba

    def _unref(self, pba: int) -> None:
        rc = self.refcount.get(pba, 0) - 1
        self.refcount[pba] = rc
        if rc <= 0:
            self._free(pba)

    def _free(self, pba: int) -> None:
        """Logical free: unlink the block from every lookup structure NOW —
        in particular the fingerprint table/index, so a later write of the
        same content can never dedup against the dead block — then reclaim
        the slot physically, or park it in limbo while epochs are pinned."""
        self._ever_freed = True
        fp = self.fp_of_pba.pop(pba, None)
        if fp is not None:
            self._drop_pba(fp, pba)
        self.refcount.pop(pba, None)
        self.pop_lbas(pba)
        self.buffer.invalidate(pba)
        self.live_blocks -= 1
        if self.deferred_reclaim:
            with self._epoch_lock:
                if self._epoch_pins:
                    self._limbo.append((self.gc_epoch, pba))
                    return
        self._reclaim(pba)

    def _reclaim(self, pba: int) -> None:
        """Physical reclaim: the observable free (counter, then hook, so the
        hook sees the updated count) and the slot becoming a compactable
        hole."""
        self.freed_blocks += 1
        if self.on_free is not None:
            self.on_free(pba)
        self._free_pbas.append(pba)

    # -- read path ---------------------------------------------------------------
    def read(self, stream: int, lba: int) -> Optional[int]:
        pba = self._lba_pba.get((stream << 64) + lba)
        if pba is not None:
            self.buffer.access(pba)
        return pba

    # -- membership (FingerprintIndex-backed) --------------------------------------
    def has_fp(self, fp: int) -> bool:
        """Is any live block's content fingerprinted ``fp``?"""
        return fp in self.fp_index

    # -- post-processing support ---------------------------------------------------
    def duplicate_fingerprints(self) -> List[int]:
        """Fingerprints stored at more than one PBA (inline misses).

        Served from the incremental candidate map — no fp_table scan.  The
        result is sorted so a budgeted merge pass picks the same victims on
        a live store and on one restored from its snapshot (the map's own
        order is its insertion history, which a restore does not keep).
        """
        return sorted(self._dup_fps)

    def merge_fingerprint(self, fp: int) -> int:
        """Collapse all PBAs of ``fp`` onto the canonical (first) PBA.

        Returns the number of disk blocks reclaimed.
        """
        pbas = self._dup_fps.get(fp)
        if pbas is None:
            return 0
        self._ensure_reverse()
        canonical, *extras = pbas
        reclaimed = 0
        for p in extras:
            for key in self.pop_lbas(p):
                self._lba_pba[key] = canonical
                self._rev_add(canonical, key)
                self.refcount[canonical] = self.refcount.get(canonical, 0) + 1
                self.refcount[p] -= 1
            if self.refcount.get(p, 0) <= 0:
                self._free(p)
                reclaimed += 1
        return reclaimed

    # -- online GC: compaction -------------------------------------------------------
    def compact(self, max_moves: Optional[int] = None) -> Dict[int, int]:
        """Close PBA range holes by relocating live blocks downward.

        The highest live blocks move into the lowest reclaimed slots
        (classic defragmentation, budgeted by ``max_moves`` so foreground
        traffic can interleave), every lookup structure follows the move
        (fingerprint-table row, PBA metadata, refcount, LBA mappings via the
        reverse index), and trailing holes are returned to the allocator by
        lowering ``_next_pba``.  Slots in limbo are *not* holes — their
        grace period hasn't drained — so compaction never touches them.
        Only compaction recycles PBA slots; fresh writes stay monotonic.

        Returns ``{old_pba: new_pba}`` for every relocated block, so the
        engine layer can patch decision state that carries PBAs (fingerprint
        caches, pending duplicate runs) and keep inline decisions bit-exact
        with a never-compacted run.
        """
        relocations: Dict[int, int] = {}
        if not self._free_pbas:
            return relocations
        assert not self._staged_writes and not self._staged_dups, (
            "compact() requires flushed staged writes"
        )
        self._ensure_reverse()
        holes = sorted(self._free_pbas)
        live_desc = sorted(self.fp_of_pba, reverse=True)
        hi = 0
        for old in live_desc:
            if max_moves is not None and len(relocations) >= max_moves:
                break
            if hi >= len(holes):
                break
            new = holes[hi]
            if new >= old:
                break  # every remaining hole sits above every remaining block
            hi += 1
            self._relocate(old, new)
            relocations[old] = new
        # vacated slots become holes at the top of the range; trailing holes
        # (and only those — a limbo slot below them blocks the trim) shrink
        # the allocated span so fresh writes reuse the space
        hole_set = set(holes[hi:])
        hole_set.update(relocations)
        while self._next_pba - 1 in hole_set:
            self._next_pba -= 1
            hole_set.remove(self._next_pba)
        self._free_pbas = sorted(hole_set)
        return relocations

    def _relocate(self, old: int, new: int) -> None:
        """Move one live block's identity from slot ``old`` to ``new``."""
        fp = self.fp_of_pba.pop(old)
        self.fp_of_pba[new] = fp
        row = self._dup_fps.get(fp)
        if row is None:
            self._fp_pba[fp] = new
        else:
            # in place: canonical order is positional
            self._dup_fps[fp] = row = {new if p == old else p: None for p in row}
            self._fp_pba[fp] = next(iter(row))
        self.refcount[new] = self.refcount.pop(old)
        keys = self.pop_lbas(old)
        for key in keys:
            self._lba_pba[key] = new
        self.put_lbas(new, keys)
        self.buffer.invalidate(old)
        self.relocated_blocks += 1
        if self.on_relocate is not None:
            self.on_relocate(old, new)

    # -- shard migration support ---------------------------------------------------
    def extract_fp(self, fp: int) -> Optional[List[int]]:
        """Pop ``fp``'s whole fingerprint-table row (resharding moves it to
        another shard's store); keeps the index and candidate set coherent."""
        canon = self._fp_pba.pop(fp, None)
        if canon is None:
            return None
        self.fp_index.discard(fp)
        return list(self._dup_fps.pop(fp, None) or (canon,))

    def absorb_fp(self, fp: int, pbas: List[int]) -> None:
        """Append a migrated row to ``fp``'s fingerprint-table entry."""
        for pba in pbas:
            self._add_pba(fp, pba)

    # -- snapshot/restore ----------------------------------------------------------
    def snapshot(self) -> dict:
        """Full store state as a JSON-safe tree (see ``core.snapshot``).

        Valid at any batch boundary: staged columnar writes are flushed first
        (idempotent) so the deferred accounting is folded in.  The reverse
        LBA index is *not* serialized — it is a pure function of ``lba_map``
        and is rebuilt lazily after restore.  The ``on_free`` reclaim hook is
        process-local and must be re-attached by its owner (the serving
        layer does this in ``DedupKVServer.load_state``).
        """
        self.flush_staged()
        return {
            "lba_map": [[*lba_of_key(k), p] for k, p in self._lba_pba.items()],
            "fp_table": [[fp, list(self._dup_fps.get(fp) or (pba,))]
                         for fp, pba in self._fp_pba.items()],
            "refcount": pairs(self.refcount),
            "fp_of_pba": pairs(self.fp_of_pba),
            "next_pba": self._next_pba,
            "live_blocks": self.live_blocks,
            "peak_blocks": self.peak_blocks,
            "disk_writes": self.disk_writes,
            "freed_blocks": self.freed_blocks,
            "ever_freed": self._ever_freed,
            "lba_watermark": pairs(self._lba_watermark),
            "buffer": self.buffer.snapshot(),
            # online-GC state: limbo entries keep their epoch tag so a restore
            # mid-grace-period resumes the exact same drain schedule.  Epoch
            # *pins* are process-local (a pin is a live in-flight write) and
            # are never serialized — a snapshot is taken at a batch boundary
            # where no write is in flight.
            "gc": {
                "epoch": self.gc_epoch,
                "limbo": [[e, p] for e, p in self._limbo],
                "free_pbas": list(self._free_pbas),
                "deferred": self.deferred_reclaim,
                "relocated": self.relocated_blocks,
            },
        }

    def load_snapshot(self, tree: dict) -> None:
        self._lba_pba = {lba_key(int(s), int(lba)): int(p) for s, lba, p in tree["lba_map"]}
        self._fp_pba, self._dup_fps = {}, {}
        for fp, pbas in tree["fp_table"]:
            if pbas:
                self._fp_pba[int(fp)] = int(pbas[0])
            if len(pbas) > 1:
                self._dup_fps[int(fp)] = dict.fromkeys(int(p) for p in pbas)
        # derived structures: rebuilt from the serialized table, never stored
        self.fp_index = FingerprintIndex(self._fp_pba)
        self.refcount = from_pairs(tree["refcount"], value=int)
        self.fp_of_pba = from_pairs(tree["fp_of_pba"], value=int)
        self._next_pba = int(tree["next_pba"])
        self.live_blocks = int(tree["live_blocks"])
        self.peak_blocks = int(tree["peak_blocks"])
        self.disk_writes = int(tree["disk_writes"])
        self.freed_blocks = int(tree["freed_blocks"])
        self._ever_freed = bool(tree["ever_freed"])
        self._lba_watermark = from_pairs(tree["lba_watermark"], value=int)
        self.buffer.load_snapshot(tree["buffer"])
        self._staged_writes = []
        self._staged_dups = []
        self.lbas_of_pba, self._shared_lbas = {}, {}
        self._rev_synced = 0  # the whole map is the next delta
        gc = tree.get("gc") or {}
        self.gc_epoch = int(gc.get("epoch", 0))
        self._limbo = [(int(e), int(p)) for e, p in gc.get("limbo", [])]
        self._free_pbas = [int(p) for p in gc.get("free_pbas", [])]
        self.deferred_reclaim = bool(gc.get("deferred", False))
        self.relocated_blocks = int(gc.get("relocated", 0))
        self._epoch_pins = {}

    # -- invariants (used by property tests) --------------------------------------
    @property
    def lba_map(self) -> LbaMap:
        """The LBA mapping table as ``(stream, lba) -> PBA`` (read-only)."""
        return LbaMap(self)

    @property
    def fp_table(self) -> FpTable:
        """The fingerprint table as ``fp -> [PBA, ...]`` (read-only)."""
        return FpTable(self)

    def lookup_fp(self, fp: int) -> Optional[int]:
        return self._fp_pba.get(fp)

    def unique_fingerprints(self) -> int:
        return len(self._fp_pba)

    def check_consistency(self) -> None:
        """Raise AssertionError if internal tables disagree."""
        assert not self._staged_writes and not self._staged_dups, "unflushed staged writes"
        self._ensure_reverse()
        assert set(self.fp_index) == set(self._fp_pba), "fp_index drifted from fp_table"
        self.fp_index.check_consistency()
        for fp, pbas in self._dup_fps.items():
            assert len(pbas) > 1, f"duplicate candidate {fp} holds {pbas}"
            assert self._fp_pba.get(fp) == next(iter(pbas)), f"canonical PBA of {fp} drifted"
        live = set()
        for fp, pbas in self.fp_table.items():
            assert len(pbas) == len(set(pbas)), f"dup PBAs for fp {fp}"
            for p in pbas:
                assert self.fp_of_pba.get(p) == fp
                live.add(p)
        assert len(live) == self.live_blocks, (len(live), self.live_blocks)
        assert all(len(keys) > 1 for keys in self._shared_lbas.values()), "unshared PBA in sets"
        assert not self._shared_lbas.keys() & self.lbas_of_pba.keys(), "PBA both shared and not"
        refs: Dict[int, int] = {}
        for key, pba in self._lba_pba.items():
            assert pba in live, f"LBA maps to freed PBA {pba}"
            assert key in self.lbas_of(pba), f"reverse index missing {lba_of_key(key)}"
            refs[pba] = refs.get(pba, 0) + 1
        for p in live:
            assert self.refcount.get(p, 0) == refs.get(p, 0), (
                p,
                self.refcount.get(p),
                refs.get(p),
            )
        # GC bookkeeping: holes and limbo slots are dead, unique, and
        # disjoint.  (No span bound: a hole left by freeing a block migrated
        # in from another shard carries that shard's PBA namespace, which
        # can sit numerically above the local allocator.)
        holes = list(self._free_pbas)
        limbo = [p for _, p in self._limbo]
        assert len(set(holes)) == len(holes), "duplicate hole PBAs"
        assert len(set(limbo)) == len(limbo), "duplicate limbo PBAs"
        assert not set(holes) & set(limbo), "PBA both hole and limbo"
        for p in holes + limbo:
            assert p not in live, f"live PBA {p} marked reclaimed"
