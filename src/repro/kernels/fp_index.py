"""Pallas TPU kernels: exact fingerprint-index hash-table probe/insert/remove.

The inline phase's hot path is *membership*: "has this fingerprint ever been
seen / is it cached / is it in the on-disk table?" (paper §III-B/§IV).  The
host engines answer that with per-fingerprint Python dict ops; this module
moves the probe loop onto the accelerator as a fixed-layout open-addressing
hash table over **uint32 lanes**:

* The table is two arrays ``table_lo`` / ``table_hi`` of ``uint32`` (a
  64-bit fingerprint is split into its low/high words — Pallas TPU kernels
  have no uint64).
* A key's home slot is a 32-bit avalanche hash of both words masked to the
  power-of-two *logical* capacity; collisions linear-probe a **bounded
  window** of ``WINDOW`` consecutive slots.
* The logical slots are laid out in **tiles** of up to ``TILE_SLOTS``
  slots.  A tile is stored as ``rows`` lane rows of 128 slots — the logical
  slots rounded up to whole (8, 128) groups of ``GROUP_SLOTS``, plus one
  more group of tail pad — so a probe window is always contiguous *within
  one tile*, and the two aligned groups starting at the window's group
  always hold it.  The device arrays are shaped ``(num_tiles, rows, 128)``;
  logical home slot ``h`` lives in tile ``h // tile_cap`` at flat in-tile
  position ``h % tile_cap`` (``phys_slots`` is the flat mapping the numpy
  backend shares).
* The grid runs **one table tile per grid row**: each grid step stages a
  single tile (not the whole table) in VMEM, so logical capacity is bounded
  by HBM, not VMEM.  The host wrapper routes each key to its home tile
  (sort-by-tile + pad, see ``kernels.ops``) and passes the per-tile key
  counts as scalar prefetch, so the loop stops at each tile's last key;
  tiles are mutually independent because windows never cross tile edges.
* Keys and their in-tile home slots sit in SMEM, where the per-key loop
  reads them as scalars.  Each key loads the two aligned (8, 128) groups
  holding its window, masks the window's ``WINDOW`` lanes, and reduces.
* ``EMPTY`` (all-zero) and ``TOMBSTONE`` (all-ones) are in-band sentinels;
  the host wrapper (``repro.core.fp_index``) routes the two colliding key
  values — 0 and 2^64-1 — to its spill set, so the table never stores them
  and the kernels never see them.
* **Probe** scans each key's whole window and reports a hit iff some slot
  holds both words — exact membership for every key the table holds, by
  construction (full 64-bit compare, not a partial-hash filter).
* **Insert** places each key in the first ``EMPTY``/``TOMBSTONE`` slot of
  its window (keys are processed sequentially inside each tile, so there
  are no write conflicts) and reports per-key status; a full window means
  *overflow* and the host wrapper spills the key — exactness never depends
  on table capacity.  The status distinguishes placement into an EMPTY
  slot from consuming a TOMBSTONE, so the host tracks its tombstone count
  without reading the table back.
* **Remove** tombstones the matching slot (keys known resident only).

The table arrays live on device and are updated in place: insert/remove
alias their table inputs to their table outputs (``input_output_aliases``),
so steady-state launches ship **keys only** — the host wrapper keeps the
returned device buffers for the next launch and materializes a host mirror
only when the numpy path or a consistency check asks for one.

The kernels compile for and run on TPU v5e; on the CPU (tests) they run in
interpret mode.  The host wrapper's numpy backend implements the identical
physical layout and window discipline, and tests/test_fp_index.py pins the
two membership-equivalent against each other.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Bounded linear-probe window: every key lives within WINDOW slots of its
# home slot or spills to the host.  16 lanes keeps overflow vanishingly rare
# below ~60% load.
WINDOW = 16
# Keys per grid step (second grid dimension tiles the key batch).
TILE_KEYS = 1024
# Logical slots per table tile: one grid step stages one tile in VMEM
# (2 lane arrays x (TILE_SLOTS + GROUP_SLOTS) x 4B ~ 264 KiB), so the
# table's logical capacity is HBM-bound.
TILE_SLOTS = 1 << 15
LANES = 128
# One (8, 128) uint32 tile: the unit of aligned loads and of tile padding.
GROUP_ROWS = 8
GROUP_SLOTS = GROUP_ROWS * LANES

# In-band slot sentinels (lo == hi == the value).
EMPTY32 = 0
TOMB32 = 0xFFFFFFFF

# xxhash32 primes, kept as Python ints: Pallas kernels may not capture
# device-array constants, so every use site casts inline (HLO literals).
_P1 = 2654435761
_P2 = 2246822519
_P3 = 3266489917


def tile_shape(cap: int):
    """``(num_tiles, tile_cap, tile_phys)`` for logical capacity ``cap``.

    ``cap`` must be a power of two.  Tables at or below ``TILE_SLOTS`` are a
    single tile (``tile_cap == cap``); larger tables split into
    ``cap // TILE_SLOTS`` tiles of ``TILE_SLOTS`` logical slots each.
    ``tile_phys`` is the tile's physical slot count: ``tile_cap`` rounded
    up to whole groups, plus one group of tail pad.
    """
    if cap & (cap - 1):
        raise ValueError(f"logical capacity {cap} must be a power of two")
    tile_cap = min(cap, TILE_SLOTS)
    groups = -(-tile_cap // GROUP_SLOTS) + 1
    return cap // tile_cap, tile_cap, groups * GROUP_SLOTS


def table_shape(cap: int):
    """Device shape ``(num_tiles, rows, LANES)`` of each lane array."""
    t, _, tile_phys = tile_shape(cap)
    return t, tile_phys // LANES, LANES


def table_phys_len(cap: int) -> int:
    """Total physical slots (flat) for logical capacity ``cap``."""
    t, _, tile_phys = tile_shape(cap)
    return t * tile_phys


def phys_slots(home, cap: int):
    """Physical (flat) slot index of each logical home slot.

    The layout contract shared by the numpy backend and the kernels: tile
    ``h // tile_cap`` starts ``tile_phys - tile_cap`` slots later per
    preceding tile.  Accepts and returns integer numpy arrays.
    """
    _, tile_cap, tile_phys = tile_shape(cap)
    return home + (home // tile_cap) * (tile_phys - tile_cap)


def slot_hash_host(lo, hi):
    """Home-slot hash over numpy uint32 arrays — the layout contract.

    Mirrored verbatim (same constants, same 32-bit wraparound) by
    ``_slot_hash_jnp``; tests assert the two agree so the numpy backend and
    the kernels probe identical slots.
    """
    import numpy as np

    x = (lo ^ np.uint32(0x9E3779B9)) * np.uint32(2654435761)
    x ^= x >> np.uint32(15)
    x = (x + hi) * np.uint32(2246822519)
    x ^= x >> np.uint32(13)
    x = x * np.uint32(3266489917)
    return x ^ (x >> np.uint32(16))


def _slot_hash_jnp(lo: jnp.ndarray, hi: jnp.ndarray) -> jnp.ndarray:
    x = (lo ^ jnp.uint32(0x9E3779B9)) * jnp.uint32(_P1)
    x = x ^ jax.lax.shift_right_logical(x, jnp.uint32(15))
    x = (x + hi) * jnp.uint32(_P2)
    x = x ^ jax.lax.shift_right_logical(x, jnp.uint32(13))
    x = x * jnp.uint32(_P3)
    return x ^ jax.lax.shift_right_logical(x, jnp.uint32(16))


def _check_tiled(counts, keys_lo, table_lo, cap: int):
    t, rows, lanes = table_shape(cap)
    if table_lo.shape != (t, rows, lanes):
        raise ValueError(f"table shape {table_lo.shape} != {(t, rows, lanes)} for capacity {cap}")
    if counts.shape != (t,):
        raise ValueError(f"counts shape {counts.shape} != ({t},)")
    (n,) = keys_lo.shape
    k = n // t
    if k * t != n or k % TILE_KEYS:
        raise ValueError(f"keys per tile ({n}/{t}) must be a multiple of TILE_KEYS={TILE_KEYS}")
    return t, k, rows


def _window_iota():
    """Flat slot offset of every lane of a two-group window load."""
    shape = (2 * GROUP_ROWS, LANES)
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _window(slot):
    """``(row0, rel)`` for an in-tile home slot: the window lives in the two
    aligned groups starting at row ``row0``; ``rel`` is each loaded lane's
    offset from the home slot (the window is ``0 <= rel < WINDOW``)."""
    row0 = pl.multiple_of((slot // GROUP_SLOTS) * GROUP_ROWS, GROUP_ROWS)
    return row0, slot % GROUP_SLOTS


def _tile_keys(cnt_ref):
    """Number of real keys in this grid step's key block."""
    start = pl.program_id(1) * TILE_KEYS
    return jnp.clip(cnt_ref[pl.program_id(0)] - start, 0, TILE_KEYS)


def _key_spec(k: int):
    """One key block of tile ``i``'s ``k``-key segment, in SMEM."""
    kb = k // TILE_KEYS
    return pl.BlockSpec((TILE_KEYS,), lambda i, j, cnt: (i * kb + j,), memory_space=pltpu.SMEM)


def _table_spec(rows: int):
    return pl.BlockSpec((1, rows, LANES), lambda i, j, cnt: (i, 0, 0))


def _in_tile_slots(keys_lo, keys_hi, tile_cap: int):
    return (_slot_hash_jnp(keys_lo, keys_hi) & jnp.uint32(tile_cap - 1)).astype(jnp.int32)


def _probe_kernel(cnt_ref, klo_ref, khi_ref, slot_ref, tlo_ref, thi_ref, out_ref):
    """Batched membership probe: one aligned two-group load per key."""
    iota = _window_iota()

    def body(i, carry):
        row0, off = _window(slot_ref[i])
        rel = iota - off
        wlo = tlo_ref[0, pl.ds(row0, 2 * GROUP_ROWS), :]
        whi = thi_ref[0, pl.ds(row0, 2 * GROUP_ROWS), :]
        hit = (rel >= 0) & (rel < WINDOW) & (wlo == klo_ref[i]) & (whi == khi_ref[i])
        out_ref[i] = jnp.max(hit.astype(jnp.int32))
        return carry

    jax.lax.fori_loop(0, _tile_keys(cnt_ref), body, 0)


def fp_probe_pallas(
    counts: jnp.ndarray,
    keys_lo: jnp.ndarray,
    keys_hi: jnp.ndarray,
    table_lo: jnp.ndarray,
    table_hi: jnp.ndarray,
    *,
    cap: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """(T*K,) int32 membership flags for tile-routed split keys.

    ``keys_*`` are flat ``(T*K,)``: segment ``t`` holds the keys whose home
    slot lives in table tile ``t``, padded to ``K`` (a multiple of
    TILE_KEYS); ``counts[t]`` says how many are real.  ``table_*`` are the
    ``table_shape(cap)`` lane arrays.  Flags past each tile's count are
    undefined; the caller drops them.
    """
    t, k, rows = _check_tiled(counts, keys_lo, table_lo, cap)
    slots = _in_tile_slots(keys_lo, keys_hi, tile_shape(cap)[1])
    key = _key_spec(k)
    return pl.pallas_call(
        _probe_kernel,
        out_shape=jax.ShapeDtypeStruct((t * k,), jnp.int32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(t, k // TILE_KEYS),
            in_specs=[key, key, key, _table_spec(rows), _table_spec(rows)],
            out_specs=key,
        ),
        interpret=interpret,
    )(counts, keys_lo, keys_hi, slots, table_lo, table_hi)


# Insert statuses.
PLACED = 0  # consumed an EMPTY slot
PRESENT = 1  # key already in its window
OVERFLOW = 2  # window full -> host spill
PLACED_TOMB = 3  # consumed a TOMBSTONE slot


def _copy_tile_in(tlo_in_ref, thi_in_ref, tlo_ref, thi_ref):
    """Stage the tile into the aliased output block on its first key block:
    output blocks are never loaded from HBM, and every tile is written back
    whole, keyed or not."""

    @pl.when(pl.program_id(1) == 0)
    def _():
        tlo_ref[...] = tlo_in_ref[...]
        thi_ref[...] = thi_in_ref[...]


def _insert_kernel(
    cnt_ref, klo_ref, khi_ref, slot_ref, tlo_in_ref, thi_in_ref, tlo_ref, thi_ref, status_ref
):
    """Sequential batched insert: first-fit within each key's window.

    Keys are placed one at a time inside each tile (grid steps over one
    tile's key blocks run back-to-back on the same resident table block),
    so a key inserted earlier in the batch is visible (as PRESENT) to later
    duplicates and two keys sharing a window never claim the same slot.
    ``tlo_ref``/``thi_ref`` alias the input table buffers (in-place update);
    after the first key block stages the tile, all reads and writes go
    through the output refs.
    """
    _copy_tile_in(tlo_in_ref, thi_in_ref, tlo_ref, thi_ref)
    iota = _window_iota()

    def body(i, carry):
        kl = klo_ref[i]
        kh = khi_ref[i]
        row0, off = _window(slot_ref[i])
        rel = iota - off
        rows = pl.ds(row0, 2 * GROUP_ROWS)
        wlo = tlo_ref[0, rows, :]
        whi = thi_ref[0, rows, :]
        inwin = (rel >= 0) & (rel < WINDOW)
        present = jnp.max((inwin & (wlo == kl) & (whi == kh)).astype(jnp.int32)) > 0
        empty = inwin & (wlo == jnp.uint32(EMPTY32)) & (whi == jnp.uint32(EMPTY32))
        tomb = inwin & (wlo == jnp.uint32(TOMB32)) & (whi == jnp.uint32(TOMB32))
        # first free lane of each kind (WINDOW = "none")
        first_empty = jnp.min(jnp.where(empty, rel, WINDOW))
        first_tomb = jnp.min(jnp.where(tomb, rel, WINDOW))
        target = jnp.minimum(first_empty, first_tomb)
        has_free = target < WINDOW

        @pl.when(jnp.logical_not(present) & has_free)
        def _place():
            at = rel == target
            tlo_ref[0, rows, :] = jnp.where(at, kl, wlo)
            thi_ref[0, rows, :] = jnp.where(at, kh, whi)

        status_ref[i] = jnp.where(
            present,
            jnp.int32(PRESENT),
            jnp.where(
                has_free,
                jnp.where(first_tomb < first_empty, jnp.int32(PLACED_TOMB), jnp.int32(PLACED)),
                jnp.int32(OVERFLOW),
            ),
        )
        return carry

    jax.lax.fori_loop(0, _tile_keys(cnt_ref), body, 0)


def _mutate_call(kernel, counts, keys_lo, keys_hi, table_lo, table_hi, cap, interpret):
    t, k, rows = _check_tiled(counts, keys_lo, table_lo, cap)
    slots = _in_tile_slots(keys_lo, keys_hi, tile_shape(cap)[1])
    key = _key_spec(k)
    return pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct(table_lo.shape, jnp.uint32),
            jax.ShapeDtypeStruct(table_hi.shape, jnp.uint32),
            jax.ShapeDtypeStruct((t * k,), jnp.int32),
        ],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(t, k // TILE_KEYS),
            in_specs=[key, key, key, _table_spec(rows), _table_spec(rows)],
            out_specs=[_table_spec(rows), _table_spec(rows), key],
        ),
        # operand indices count the scalar-prefetch counts (operand 0)
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
    )(counts, keys_lo, keys_hi, slots, table_lo, table_hi)


def fp_insert_pallas(counts, keys_lo, keys_hi, table_lo, table_hi, *, cap: int,
                     interpret: bool = False):
    """Insert tile-routed split keys; returns ``(table_lo, table_hi, status)``.

    Same key/table layout as ``fp_probe_pallas``.  The table arrays are
    updated in place on device (input/output aliasing) — steady-state
    launches transfer keys only.
    """
    return _mutate_call(_insert_kernel, counts, keys_lo, keys_hi, table_lo, table_hi, cap,
                        interpret)


def _remove_kernel(
    cnt_ref, klo_ref, khi_ref, slot_ref, tlo_in_ref, thi_in_ref, tlo_ref, thi_ref, status_ref
):
    """Tombstone the matching slot of each (resident) key."""
    _copy_tile_in(tlo_in_ref, thi_in_ref, tlo_ref, thi_ref)
    iota = _window_iota()

    def body(i, carry):
        row0, off = _window(slot_ref[i])
        rel = iota - off
        rows = pl.ds(row0, 2 * GROUP_ROWS)
        wlo = tlo_ref[0, rows, :]
        whi = thi_ref[0, rows, :]
        match = (rel >= 0) & (rel < WINDOW) & (wlo == klo_ref[i]) & (whi == khi_ref[i])
        found = jnp.max(match.astype(jnp.int32))

        @pl.when(found > 0)
        def _tombstone():
            tlo_ref[0, rows, :] = jnp.where(match, jnp.uint32(TOMB32), wlo)
            thi_ref[0, rows, :] = jnp.where(match, jnp.uint32(TOMB32), whi)

        status_ref[i] = found
        return carry

    jax.lax.fori_loop(0, _tile_keys(cnt_ref), body, 0)


def fp_remove_pallas(counts, keys_lo, keys_hi, table_lo, table_hi, *, cap: int,
                     interpret: bool = False):
    """Remove tile-routed split keys; returns ``(table_lo, table_hi, status)``.

    ``status`` is 1 where a slot was tombstoned, 0 on a miss.  In-place on
    device, keys-only transfer, like insert.
    """
    return _mutate_call(_remove_kernel, counts, keys_lo, keys_hi, table_lo, table_hi, cap,
                        interpret)
