"""Device-resident exact fingerprint index (DESIGN §4).

``FingerprintIndex`` is the one membership layer every probe in the stack
goes through: the inline phase's all-time seen set, the fingerprint cache's
batched pre-probe, the block store's fingerprint-table membership and the
cluster's multi-shard scatter probe all hold one of these.  It pairs

* a **device-layout hash table** — the tiled bounded-window open-addressing
  layout of ``repro.kernels.fp_index``, two uint32 lane arrays probed
  either by the Pallas kernel set (the TPU default; interpret mode when
  forced on the CPU) or by a bit-identical vectorized numpy implementation
  (the CPU default) — with
* the **authoritative host state** — a plain ``dict`` of Python int
  fingerprints (each mapped to ``None``), the ground truth the table
  accelerates.  A dict that holds only ints is one the cyclic garbage
  collector does not track, so an aged index of millions of keys costs a
  full collection nothing; a ``set`` (or a ``set`` subclass) is always
  tracked, and every full pass would walk each of its entries.

On the Pallas backend the lane arrays are **persistent device buffers**:
insert/remove launches alias them in place and ship keys only, and the
host ``_t64`` mirror is materialized lazily — only when the host-side
paths (``_lanes``, ``check_consistency``) actually ask for it.  A rebuild
(growth, tombstone pressure, restore) resets the table host-side and
re-uploads on the next device launch.

Exactness contract (property-tested in tests/test_fp_index.py):

* no false positives or negatives, ever: the table stores full 64-bit keys
  (not a partial-hash filter), keys that cannot live in the table — window
  **overflow**, and the two values colliding with the in-band EMPTY/
  TOMBSTONE sentinels (0 and 2^64-1) — **spill to a host set** that every
  batched probe consults, and removals tombstone their slot;
* the table is **derived, never serialized**: snapshots persist the key
  set (exactly as the engines always did) and a restored index rebuilds
  its table from it, so the snapshot state-tree format is untouched and a
  corrupted table can always be rebuilt host-side.

Mutations stage lazily and fold into the table before the next batched
probe: scalar add/discard (the per-record oracle path) stage into pending
dicts at native-set speed, and ``add_many`` stages its whole key array
into a journal — so bulk insertion costs what the plain host set costs,
and the table build happens once, vectorized, at the next probe.  Batched
probes (``contains_many``, ``probe_and_add``) are one vectorized launch
per call, with ``*_async`` variants that split the launch from the
consume so device probes overlap host work; tiny batches are answered by
the host set, below the size where a vectorized launch wins
(``small_batch``, set to 0 by tests that want the table path exercised
unconditionally).  ``table_stats()`` counts the probed keys each path
answered, so a run can show whether the device did the work, and the
device launches by op with the keys they carried, the key slots they were
padded to, the keys they placed and removed, and the keys probed while
folding the journal.  Each probe, insert, remove and fold is one
``dedup.fp_index.*`` profiler span (``repro.obs``).
"""

from __future__ import annotations

from collections.abc import MutableSet
from typing import Iterable

import numpy as np

from .. import obs
from ..kernels.fp_index import (
    EMPTY32,
    OVERFLOW,
    PLACED,
    PLACED_TOMB,
    TOMB32,
    WINDOW,
    slot_hash_host,
    table_phys_len,
    table_shape,
    tile_shape,
)

EMPTY_KEY = 0  # lo == hi == EMPTY32
TOMB_KEY = (1 << 64) - 1  # lo == hi == TOMB32
_U32 = np.uint64(0xFFFFFFFF)

DEFAULT_CAPACITY = 1 << 12
# Above this fill fraction the table rebuilds at the next power of two.
# Deliberately low (memory-for-speed): probe cost is dominated by how many
# probe rounds survive past the first gather, which shrinks geometrically
# with the load factor — measured on this host, a ~0.25-loaded table probes
# ~3x faster than a ~0.5-loaded one, for 8 bytes/slot of extra memory.
# Window overflow (-> host spill) is also rarer at low load.
GROW_LOAD = 0.35
# Probing fewer keys than this goes through the host set: a vectorized
# launch has fixed overhead that only pays off on real batches.  Measured
# crossover on this host is ~1.5-2k keys (the per-key Python set probe is
# ~40-110ns; the table path's flush + gather setup is ~30-70us) — relevant
# for the sharded cluster, whose scatter divides driver batches into
# per-shard sub-batches that can land right at this scale.
SMALL_BATCH = 1536


def _split(keys: np.ndarray):
    return (keys & _U32).astype(np.uint32), (keys >> np.uint64(32)).astype(np.uint32)


class FingerprintIndex(MutableSet):
    """Exact membership index over 64-bit fingerprints.

    A ``collections.abc.MutableSet``: every host-side consumer of the
    engines' seen sets (snapshots sort it, resharding migration unions and
    discards it, harness population scans iterate it) gets the set API —
    ``in``, ``len``, iteration, comparisons and ``|``/``&``/``-``/``^``
    with sets, which return plain ``set`` objects.  The authoritative
    membership is ``_keys``, an untracked ``dict`` of int -> None; the
    table, spill and pending buffers are the device-resident acceleration
    layered on top.  All mutations go through the mutators below (they keep
    the table coherent).
    """

    __slots__ = (
        "_keys",
        "_cap",
        "_tile_shift",
        "_tile_pad",
        "_t64",
        "_dev_lo",
        "_dev_hi",
        "_host_dirty",
        "_spill",
        "_pending_adds",
        "_pending_removes",
        "_journal",
        "_journal_n",
        "_table_live",
        "_tombstones",
        "_backend",
        "small_batch",
        "_probed_device",
        "_probed_host",
        "_launches_device",
        "_launch_keys",
        "_launch_key_slots",
        "_inserted_device",
        "_removed_device",
        "_flush_probe_keys",
    )

    def __init__(
        self,
        keys: Iterable[int] = (),
        *,
        capacity: int = DEFAULT_CAPACITY,
        backend: str = "auto",
        small_batch: int = SMALL_BATCH,
    ):
        self._keys = dict.fromkeys(keys)
        if backend not in ("auto", "numpy", "pallas"):
            raise ValueError(f"backend must be auto|numpy|pallas, got {backend!r}")
        self._backend = backend
        self.small_batch = small_batch
        # probed keys answered by device launches / on the host (the
        # small-batch set path or the numpy table)
        self._probed_device = 0
        self._probed_host = 0
        # device launches by op, the real keys they carried and the key
        # slots they were padded to (tiles x K), the keys insert launches
        # placed and remove launches tombstoned, and the keys probed while
        # folding the add_many journal
        self._launches_device = {"probe": 0, "insert": 0, "remove": 0}
        self._launch_keys = 0
        self._launch_key_slots = 0
        self._inserted_device = 0
        self._removed_device = 0
        self._flush_probe_keys = 0
        cap = 1
        while cap < capacity:
            cap <<= 1
        self._rebuild(cap)

    # -- backend ---------------------------------------------------------------
    def _use_pallas(self) -> bool:
        if self._backend == "auto":
            from ..kernels.ops import device_platform

            self._backend = "pallas" if device_platform() == "tpu" else "numpy"
        return self._backend == "pallas"

    # -- device-buffer management ----------------------------------------------
    def _dev_tables(self):
        """The persistent device lane buffers, uploading the host table on
        first use (and after a rebuild dropped them)."""
        if self._dev_lo is None:
            import jax.numpy as jnp

            shape = table_shape(self._cap)
            tlo, thi = self._host_lanes()
            self._dev_lo = jnp.asarray(tlo.reshape(shape))
            self._dev_hi = jnp.asarray(thi.reshape(shape))
        return self._dev_lo, self._dev_hi

    def _launch(self, op: str, keys: np.ndarray) -> np.ndarray:
        """One device launch of ``op`` over sentinel-free keys, counted;
        returns the per-key int32 answer in batch order."""
        from ..kernels.ops import fp_index_launch

        lo, hi = _split(keys)
        tlo, thi = self._dev_tables()
        tables, answer, slots = fp_index_launch(op, lo, hi, tlo, thi, self._cap)
        if tables is not None:
            self._adopt_dev(*tables)
        self._launches_device[op] += 1
        self._launch_keys += keys.size
        self._launch_key_slots += slots
        return answer

    def _adopt_dev(self, tlo, thi) -> None:
        """Keep the in-place-updated buffers a launch returned; the host
        mirror is now stale and will re-materialize on demand."""
        self._dev_lo, self._dev_hi = tlo, thi
        self._host_dirty = True

    def _sync_host(self) -> None:
        """Materialize the host ``_t64`` mirror from the device buffers."""
        if self._host_dirty:
            tlo = np.asarray(self._dev_lo).reshape(-1)
            thi = np.asarray(self._dev_hi).reshape(-1)
            self._t64 = (thi.astype(np.uint64) << np.uint64(32)) | tlo.astype(np.uint64)
            self._host_dirty = False

    def _host_lanes(self):
        """Host-side lane arrays in the kernels' tiled ``(T, tile_phys)``
        physical layout (copies, synced from device if needed)."""
        self._sync_host()
        tiles, _, tile_phys = tile_shape(self._cap)
        t2 = self._t64.reshape(tiles, tile_phys)
        return (t2 & _U32).astype(np.uint32), (t2 >> np.uint64(32)).astype(np.uint32)

    def _lanes(self):
        """The table as the kernels' two uint32 lane arrays (copies)."""
        return self._host_lanes()

    def _set_lanes(self, tlo: np.ndarray, thi: np.ndarray) -> None:
        self._t64 = (
            (np.asarray(thi).astype(np.uint64) << np.uint64(32))
            | np.asarray(tlo).astype(np.uint64)
        ).reshape(-1)
        self._dev_lo = self._dev_hi = None  # device copy is stale now
        self._host_dirty = False

    # -- table maintenance -----------------------------------------------------
    def _rebuild(self, cap: int) -> None:
        """(Re)build the table from the authoritative set — the restore path
        and the growth path are the same code on purpose.  Folds any pending
        mutations (the set already reflects them), clears spill back to what
        genuinely cannot live in the table, and invalidates the device
        buffers — the next launch re-uploads the fresh table."""
        n_set = len(self._keys)
        while n_set > GROW_LOAD * cap:
            cap <<= 1
        self._cap = cap
        _, tile_cap, tile_phys = tile_shape(cap)
        self._tile_shift = tile_cap.bit_length() - 1
        self._tile_pad = tile_phys - tile_cap
        # host table: the kernels' two uint32 lane arrays, interleaved into
        # one uint64 word per slot so the numpy fast path pays one gather
        # and one compare per probe round (``_lanes`` translates at the
        # Pallas kernel boundary); flat view of the tiled physical layout
        self._t64 = np.zeros(table_phys_len(cap), dtype=np.uint64)
        self._dev_lo = self._dev_hi = None
        self._host_dirty = False
        self._spill = {k for k in (EMPTY_KEY, TOMB_KEY) if k in self._keys}
        self._pending_adds = {}
        self._pending_removes = {}
        self._journal = []
        self._journal_n = 0
        self._table_live = 0
        self._tombstones = 0
        if n_set > len(self._spill):
            keys = np.fromiter(self._keys, dtype=np.uint64, count=n_set)
            if self._spill:
                keys = keys[(keys != np.uint64(EMPTY_KEY)) & (keys != np.uint64(TOMB_KEY))]
            for a in range(0, keys.size, 1 << 16):
                self._table_insert(keys[a : a + (1 << 16)])

    def _grow_if_needed(self, incoming: int) -> bool:
        """Rebuild at a bigger capacity if ``incoming`` more table entries
        would pass the load threshold (or tombstones piled up).  Returns
        True when it rebuilt — the rebuild re-inserts *every* set member,
        so the caller must then skip its own explicit insert.
        """
        need = self._table_live + incoming
        if need <= GROW_LOAD * self._cap and self._tombstones <= self._cap // 4:
            return False
        cap = self._cap
        while need > GROW_LOAD * cap:
            cap <<= 1
        self._rebuild(cap)
        return True

    def _flush(self) -> None:
        """Fold pending mutations into the table (one ``fp_index.flush``
        span, when there are any)."""
        if not self._pending_adds and not self._pending_removes and not self._journal:
            return
        with obs.span("fp_index.flush",
                      keys=len(self._pending_adds) + len(self._pending_removes) + self._journal_n):
            self._fold()

    def _fold(self) -> None:
        """Order matters: the scalar pending-add dict holds keys known absent
        from the table (direct insert), the ``add_many`` journal may hold
        anything (unique + probe-filter first), and removals fold last so a
        journaled key that was discarded after staging is inserted and then
        tombstoned — never left dangling in the table.
        """
        journal_keys = None
        if self._journal:
            journal_keys = (
                self._journal[0] if len(self._journal) == 1 else np.concatenate(self._journal)
            )
            journal_keys = np.unique(journal_keys)
            self._journal = []
            self._journal_n = 0
        incoming = len(self._pending_adds) + (journal_keys.size if journal_keys is not None else 0)
        if self._grow_if_needed(incoming):
            return  # the rebuild folded every buffer (set is authoritative)
        if self._pending_adds:
            keys = np.fromiter(self._pending_adds, dtype=np.uint64, count=len(self._pending_adds))
            self._pending_adds = {}
            self._table_insert(keys)
        if journal_keys is not None:
            special = (journal_keys == np.uint64(EMPTY_KEY)) | (
                journal_keys == np.uint64(TOMB_KEY)
            )
            if special.any():
                self._spill.update(k for k in journal_keys[special].tolist() if k in self._keys)
                journal_keys = journal_keys[~special]
            if journal_keys.size:
                self._flush_probe_keys += journal_keys.size
                known = self._table_probe(journal_keys)
                fresh = journal_keys[~known]
                if fresh.size:
                    self._table_insert(fresh)
        if self._pending_removes:
            keys = np.fromiter(
                self._pending_removes, dtype=np.uint64, count=len(self._pending_removes)
            )
            self._pending_removes = {}
            self._table_remove(keys)

    def _phys_homes(self, keys: np.ndarray) -> np.ndarray:
        """Physical (flat) home slot per key: logical home mapped through
        the tiled layout (each tile starts ``tile_phys - tile_cap`` slots
        later than the one before)."""
        lo, hi = _split(keys)
        home = (slot_hash_host(lo, hi) & np.uint32(self._cap - 1)).astype(np.int64)
        if self._cap >> self._tile_shift > 1:
            home += (home >> self._tile_shift) * self._tile_pad
        return home

    def _table_insert(self, keys: np.ndarray) -> None:
        """Place unique, sentinel-free keys known absent from the table;
        window overflow spills to the host set."""
        if keys.size == 0:
            return
        with obs.span("fp_index.insert", keys=keys.size) as span:
            if self._use_pallas():
                status = self._launch("insert", keys)
                over = status == OVERFLOW
                placed = int(np.count_nonzero((status == PLACED) | (status == PLACED_TOMB)))
                self._inserted_device += placed
                span.set_metadata(placed=placed)
                self._table_live += int(keys.size - over.sum())
                self._tombstones -= int(np.count_nonzero(status == PLACED_TOMB))
                if over.any():
                    self._spill.update(keys[over].tolist())
                return
            home = self._phys_homes(keys)
            t64 = self._t64
            tomb = np.uint64(TOMB_KEY)
            for r in range(WINDOW):
                if keys.size == 0:
                    return
                slot = home + r
                cur = t64[slot]
                free = (cur == 0) | (cur == tomb)
                cand = np.nonzero(free)[0]
                if cand.size:
                    # one winner per distinct slot — writing candidates in
                    # *reversed* batch order makes the first-in-batch write
                    # land last and stick; losers (whose slot now holds the
                    # winner) probe the next offset, exactly as if the winner
                    # had been inserted before them
                    rev = cand[::-1]
                    t64[slot[rev]] = keys[rev]
                    won = t64[slot[cand]] == keys[cand]
                    win = cand[won]
                    self._tombstones -= int((cur[win] == tomb).sum())
                    self._table_live += win.size
                    if win.size == keys.size:
                        return
                    keep = np.ones(keys.size, dtype=bool)
                    keep[win] = False
                    keys, home = keys[keep], home[keep]
            if keys.size:
                self._spill.update(keys.tolist())

    def _table_remove(self, keys: np.ndarray) -> None:
        """Tombstone table slots for keys known resident in the table."""
        if keys.size == 0:
            return
        with obs.span("fp_index.remove", keys=keys.size):
            if self._use_pallas():
                hits = int(np.count_nonzero(self._launch("remove", keys)))
                self._removed_device += hits
                self._table_live -= hits
                self._tombstones += hits
                return
            home = self._phys_homes(keys)
            t64 = self._t64
            for r in range(WINDOW):
                if home.size == 0:
                    return
                slot = home + r
                match = t64[slot] == keys
                if match.any():
                    t64[slot[match]] = np.uint64(TOMB_KEY)
                    self._table_live -= int(match.sum())
                    self._tombstones += int(match.sum())
                    keep = ~match
                    keys, home = keys[keep], home[keep]

    def _table_probe_launch(self, keys: np.ndarray):
        """Run an exact membership probe of sentinel-free keys against
        table + spill; returns a zero-arg consumer producing the flags.

        On the Pallas backend the launch reads its answer back before it
        returns (``fp_index.fetch`` waits for the device), and the consumer
        folds in the spill set.  The numpy backend computes eagerly.
        """
        with obs.span("fp_index.probe", keys=keys.size):
            if self._use_pallas():
                hit = self._launch("probe", keys) != 0
                return lambda: self._spill_fixup(keys, hit)
            if self._table_live == 0:
                found = np.zeros(keys.size, dtype=bool)
            else:
                home = self._phys_homes(keys)
                found = np.zeros(keys.size, dtype=bool)
                idx = np.arange(keys.size)
                rem = keys
                t64 = self._t64
                for r in range(WINDOW):
                    cur = t64[home + r]
                    match = cur == rem
                    if match.any():
                        found[idx[match]] = True
                    # EMPTY terminates a probe chain: inserts are first-fit, so
                    # a key never sits past a slot that was EMPTY when it
                    # arrived, and removals tombstone instead of emptying — the
                    # active set shrinks geometrically with the load factor, so
                    # most keys resolve within the first round or two
                    undecided = ~(match | (cur == 0))
                    if not undecided.any():
                        break
                    idx, rem, home = idx[undecided], rem[undecided], home[undecided]
            out = self._spill_fixup(keys, found)
            return lambda: out

    def _spill_fixup(self, keys: np.ndarray, found: np.ndarray) -> np.ndarray:
        # consult the spill set unless it holds nothing but sentinel keys
        # (sentinel-free probe keys can never match those)
        spill = self._spill
        if len(spill) > (1 if EMPTY_KEY in spill else 0) + (1 if TOMB_KEY in spill else 0):
            miss = np.nonzero(~found)[0]
            if miss.size:
                found[miss] = np.fromiter(
                    map(spill.__contains__, keys[miss].tolist()), dtype=bool, count=miss.size
                )
        return found

    def _table_probe(self, keys: np.ndarray) -> np.ndarray:
        return self._table_probe_launch(keys)()

    # -- batched API -----------------------------------------------------------
    def contains_many_async(self, fps):
        """Batched membership probe, split into launch and consume.

        Returns a zero-arg callable producing the (N,) bool flags.  The
        index must not be mutated between launch and consume.
        """
        keys = np.ascontiguousarray(fps, dtype=np.uint64)
        n = keys.size
        if n == 0:
            out = np.zeros(0, dtype=bool)
            return lambda: out
        if n <= self.small_batch:
            self._probed_host += n
            with obs.span("fp_index.probe", keys=n):
                out = np.fromiter(map(self._keys.__contains__, keys.tolist()), dtype=bool,
                                  count=n)
            return lambda: out
        self._flush()
        consume = self._table_probe_launch(keys)
        if self._backend == "pallas":
            self._probed_device += n
        else:
            self._probed_host += n
        special = (keys == np.uint64(EMPTY_KEY)) | (keys == np.uint64(TOMB_KEY))
        if not special.any():
            return consume

        def consume_special():
            out = consume()
            si = np.nonzero(special)[0]
            out[si] = np.fromiter(
                (int(keys[i]) in self._spill for i in si), dtype=bool, count=si.size
            )
            return out

        return consume_special

    def contains_many(self, fps) -> np.ndarray:
        """Side-effect-free batched membership probe."""
        return self.contains_many_async(fps)()

    def probe_and_add_async(self, uniq: np.ndarray):
        """``probe_and_add`` split into launch and consume (see
        ``contains_many_async``); insertion happens at consume time."""
        uniq = np.ascontiguousarray(uniq, dtype=np.uint64)
        pending = self.contains_many_async(uniq)

        def consume():
            known = pending()
            fresh = uniq[~known]
            if fresh.size == 0:
                return known
            self._keys.update(dict.fromkeys(fresh.tolist()))
            if fresh.size <= self.small_batch:
                # stage through the pending buffer like scalar adds (the keys
                # are not in the set yet per `known`, so the invariant holds)
                for k in fresh.tolist():
                    if k == EMPTY_KEY or k == TOMB_KEY:
                        self._spill.add(k)
                    elif k in self._pending_removes:
                        del self._pending_removes[k]
                    else:
                        self._pending_adds[k] = None
                return known
            special = (fresh == np.uint64(EMPTY_KEY)) | (fresh == np.uint64(TOMB_KEY))
            if special.any():
                self._spill.update(fresh[special].tolist())
                fresh = fresh[~special]
            if not self._grow_if_needed(fresh.size):
                self._table_insert(fresh)
            return known

        return consume

    def probe_and_add(self, uniq: np.ndarray) -> np.ndarray:
        """One batched membership query + insertion of the missing keys.

        ``uniq`` must be unique (``np.unique`` output).  Returns the
        *pre-insert* membership flags — the inline pre-pass's ground-truth
        duplicate accounting in a single launch.
        """
        return self.probe_and_add_async(uniq)()

    def add_many(self, fps) -> None:
        """Batched insert (duplicates in the batch are fine).

        Costs one host-set update; the table build is journaled and folded
        lazily at the next batched probe (unique + probe-filter + one
        vectorized insert), so bulk insertion runs at native set speed.
        """
        keys = np.ascontiguousarray(fps, dtype=np.uint64)
        if keys.size == 0:
            return
        self._keys.update(dict.fromkeys(keys.tolist()))
        if self._pending_removes:
            # a re-added key whose tombstone is still pending sits in the
            # table: the fold would find it there, then tombstone it
            for k in keys.tolist():
                self._pending_removes.pop(k, None)
        self._journal.append(keys.copy())
        self._journal_n += keys.size

    def remove_many(self, fps) -> None:
        """Batched removal; keys not present are ignored."""
        keys = np.unique(np.ascontiguousarray(fps, dtype=np.uint64))
        if keys.size == 0:
            return
        self._flush()
        members = self._keys
        present = np.fromiter(map(members.__contains__, keys.tolist()), dtype=bool,
                              count=keys.size)
        keys = keys[present]
        if keys.size == 0:
            return
        for k in keys.tolist():
            del members[k]
        in_spill = np.fromiter(
            map(self._spill.__contains__, keys.tolist()), dtype=bool, count=keys.size
        )
        if in_spill.any():
            self._spill.difference_update(keys[in_spill].tolist())
            keys = keys[~in_spill]
        self._table_remove(keys)

    # -- set API ---------------------------------------------------------------
    def __contains__(self, fp) -> bool:
        return fp in self._keys

    def __iter__(self):
        return iter(self._keys)

    def __len__(self) -> int:
        return len(self._keys)

    @classmethod
    def _from_iterable(cls, it) -> set:
        # the binary operators build their result through this: a plain set
        return set(it)

    # -- scalar mutators (pending-buffer staged) -------------------------------
    def add(self, fp: int) -> None:
        if fp in self._keys:
            return
        self._keys[fp] = None
        if fp == EMPTY_KEY or fp == TOMB_KEY:
            self._spill.add(fp)
        elif fp in self._pending_removes:
            del self._pending_removes[fp]  # still physically in the table
        else:
            self._pending_adds[fp] = None

    def discard(self, fp: int) -> None:
        if fp not in self._keys:
            return
        del self._keys[fp]
        if fp == EMPTY_KEY or fp == TOMB_KEY:
            # sentinels only ever live in spill (or an unfolded journal —
            # the fold re-checks set membership, so dropping it here is
            # enough either way)
            self._spill.discard(fp)
        elif fp in self._spill:
            self._spill.discard(fp)
        elif fp in self._pending_adds:
            del self._pending_adds[fp]  # never reached the table
        else:
            # either physically in the table, or sitting in an unfolded
            # journal; the flush folds journals before removals, so this
            # stays correct in both cases
            self._pending_removes[fp] = None

    def remove(self, fp: int) -> None:
        if fp not in self._keys:
            raise KeyError(fp)
        self.discard(fp)

    def pop(self) -> int:
        for fp in self._keys:
            self.discard(fp)
            return fp
        raise KeyError("pop from an empty FingerprintIndex")

    def update(self, *others) -> None:
        for other in others:
            if isinstance(other, np.ndarray):
                self.add_many(other)
            else:
                for fp in other:
                    self.add(fp)

    def difference_update(self, *others) -> None:
        for other in others:
            for fp in list(other) if other is self else other:
                self.discard(fp)

    def intersection_update(self, *others) -> None:
        keep = set(self._keys)
        for other in others:
            keep &= set(other)
        for fp in [k for k in self._keys if k not in keep]:
            self.discard(fp)

    def symmetric_difference_update(self, other) -> None:
        for fp in set(other):
            if fp in self._keys:
                self.discard(fp)
            else:
                self.add(fp)

    def __ior__(self, other):
        self.update(other)
        return self

    def __isub__(self, other):
        self.difference_update(other)
        return self

    def __iand__(self, other):
        self.intersection_update(other)
        return self

    def __ixor__(self, other):
        self.symmetric_difference_update(other)
        return self

    def clear(self) -> None:
        self._keys.clear()
        self._rebuild(self._cap)

    # -- diagnostics / tests ---------------------------------------------------
    def spilled(self) -> int:
        """Host-spilled keys (window overflow + sentinel-colliding)."""
        return len(self._spill)

    def table_stats(self) -> dict:
        return {
            "capacity": self._cap,
            "live": self._table_live,
            "tombstones": self._tombstones,
            "spilled": len(self._spill),
            "pending": len(self._pending_adds) + len(self._pending_removes) + self._journal_n,
            "backend": self._backend,
            "device_resident": self._dev_lo is not None,
            "device_bytes": 0 if self._dev_lo is None else self._dev_lo.nbytes + self._dev_hi.nbytes,
            "probed_device": self._probed_device,
            "probed_host": self._probed_host,
            "launches_device": dict(self._launches_device),
            "launch_keys": self._launch_keys,
            "launch_key_slots": self._launch_key_slots,
            "inserted_device": self._inserted_device,
            "removed_device": self._removed_device,
            "flush_probe_keys": self._flush_probe_keys,
        }

    def check_consistency(self) -> None:
        """Assert the derived structures exactly re-derive the set."""
        self._flush()
        self._sync_host()
        decoded = self._t64
        occupied = decoded[(decoded != EMPTY_KEY) & (decoded != TOMB_KEY)]
        table_keys = set(occupied.tolist())
        assert len(occupied) == len(table_keys), "duplicate table entries"
        assert len(occupied) == self._table_live, (len(occupied), self._table_live)
        assert table_keys.isdisjoint(self._spill)
        assert table_keys | self._spill == set(self._keys), "table+spill != authoritative set"
