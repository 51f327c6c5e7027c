"""Jitted public wrappers for the Pallas kernels.

Handles backend dispatch (compiled on TPU, interpret mode on the CPU, an
error anywhere else), padding to tile boundaries, dtype viewing, and the
conversion between kernel outputs and the host-side fingerprint ints the
dedup engines consume.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from .cdc import HALO_WORDS, cdc_candidates_pallas
from .fingerprint import LANES, NUM_HASHES, TILE_B, fingerprint_pallas
from .fp_index import (
    TILE_KEYS,
    fp_insert_pallas,
    fp_probe_pallas,
    fp_remove_pallas,
    slot_hash_host,
    table_shape,
    tile_shape,
)
from .histogram import NBINS_DEFAULT, TILE, ffh_pallas


def device_platform() -> str:
    """JAX's default platform, restricted to the two this code runs on.

    ``"tpu"`` runs the kernels compiled; ``"cpu"`` (tests) runs them in
    interpret mode.  Any other platform raises rather than silently
    interpreting on an accelerator the kernels were not built for."""
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(f"Pallas kernels run on 'tpu' (or interpreted on 'cpu'), not {platform!r}")
    return platform


def _interpret(interpret: bool | None) -> bool:
    return device_platform() == "cpu" if interpret is None else interpret


def _pad_axis(x: jnp.ndarray, axis: int, multiple: int, value=0) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fingerprint_jit(blocks: jnp.ndarray, interpret: bool) -> jnp.ndarray:
    return fingerprint_pallas(blocks, interpret=interpret)


def fingerprint_blocks(blocks, interpret: bool | None = None) -> jnp.ndarray:
    """Fingerprint content blocks.

    Args:
      blocks: (B, W) array of 32-bit words (any 32-bit dtype; bytes should be
        packed little-endian by the caller), or (B, W8) uint8 which is viewed
        as words after padding to 4 bytes.
    Returns:
      (B, NUM_HASHES) uint32 fingerprints.
    """
    blocks = jnp.asarray(blocks)
    if blocks.dtype == jnp.uint8:
        blocks = _pad_axis(blocks, 1, 4)
        blocks = jax.lax.bitcast_convert_type(
            blocks.reshape(blocks.shape[0], -1, 4), jnp.uint32
        ).reshape(blocks.shape[0], -1)
    elif blocks.dtype in (jnp.int32, jnp.float32):
        blocks = jax.lax.bitcast_convert_type(blocks, jnp.uint32)
    elif blocks.dtype != jnp.uint32:
        raise TypeError(f"unsupported dtype {blocks.dtype}")
    b = blocks.shape[0]
    blocks = _pad_axis(blocks, 1, LANES)
    blocks = _pad_axis(blocks, 0, TILE_B)
    return _fingerprint_jit(blocks, _interpret(interpret))[:b]


def _fold64(fp128: np.ndarray) -> np.ndarray:
    """Fold (B, NUM_HASHES) uint32 kernel output to (B,) uint64 (two words
    verbatim, two mixed in) — collision probability ~2^-64 per pair.  The
    zero guard stays with the callers (CDC mixes the length in first)."""
    fp = np.asarray(fp128, dtype=np.uint64)
    lo = fp[:, 0] ^ (fp[:, 2] * np.uint64(0x9E3779B97F4A7C15) & np.uint64(0xFFFFFFFFFFFFFFFF))
    hi = fp[:, 1] ^ fp[:, 3]
    return (hi << np.uint64(32)) | (lo & np.uint64(0xFFFFFFFF))


def fingerprint_ints(blocks, interpret: bool | None = None) -> np.ndarray:
    """(B,) uint64 fingerprints for the host-side dedup engines."""
    out = _fold64(fingerprint_blocks(blocks, interpret=interpret))
    out[out == 0] = 1  # 0 is reserved
    return out


def _mix_len64(lens: np.ndarray) -> np.ndarray:
    """splitmix64 of chunk lengths: XORed into chunk fingerprints so two
    chunks whose zero-padded images coincide (one is the other plus trailing
    zeros) still hash apart."""
    z = np.asarray(lens, dtype=np.uint64) + np.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def chunk_fp64(fp128, lens) -> np.ndarray:
    """(C,) uint64 chunk fingerprints from kernel output + true lengths.

    Shared by every CDC backend (fused device, numpy, scalar oracle) so the
    fold/length-mix is identical by construction."""
    out = _fold64(fp128) ^ _mix_len64(lens)
    out[out == 0] = 1  # 0 is reserved
    return out


@functools.partial(jax.jit, static_argnames=("avg_size", "interpret"))
def _cdc_candidates_jit(haloed: jnp.ndarray, avg_size: int, interpret: bool) -> jnp.ndarray:
    return cdc_candidates_pallas(haloed, avg_size, interpret=interpret)


def cdc_candidate_flags(haloed, avg_size: int, interpret: bool | None = None) -> jnp.ndarray:
    """Candidate-flag words for haloed CDC rows (see ``kernels.cdc``).

    Accepts a host array or a device-resident one (the fused path uploads
    once and reuses the same buffer for the chunk-fingerprint launch).
    """
    return _cdc_candidates_jit(jnp.asarray(haloed), avg_size, _interpret(interpret))


# Chunks per fused gather+fingerprint launch.  The launch's device temp is
# O(CHUNKS_PER_LAUNCH * max_size) bytes — compiled for v5e it is 66 MiB at
# 16 KiB chunks, about one byte per gathered payload byte, well within
# CHUNK_TEMP_BYTES (1/16 of a 16 GiB v5e chip's HBM;
# tests/test_tpu_compile.py pins it) — and every full launch has the same
# shape, so one compile serves any number of chunks.
CHUNKS_PER_LAUNCH = 4096
CHUNK_TEMP_BYTES = 1 << 30


@functools.partial(jax.jit, static_argnames=("w_pad", "interpret"))
def _chunk_fp_jit(haloed: jnp.ndarray, starts: jnp.ndarray, lens: jnp.ndarray,
                  w_pad: int, interpret: bool) -> jnp.ndarray:
    """Fused gather + fingerprint over device-resident CDC rows.

    ``starts``/``lens`` are global byte offsets/lengths into the payload
    stream (the rows' payload columns, concatenated).  Chunk starts are not
    word-aligned, so each chunk gathers the ``w_pad + 1`` payload words
    covering it straight out of the 2-D rows and funnel-shifts adjacent
    words by the start's byte offset, then zero-masks bytes past its true
    length and runs the fingerprint kernel — all inside one jit, no host
    round-trip, and no byte-granular intermediate.
    """
    seg_words = haloed.shape[1] - HALO_WORDS
    total = haloed.shape[0] * seg_words
    span = jnp.arange(w_pad + 1, dtype=jnp.int32)[None, :]
    word = jnp.minimum((starts[:, None] >> 2) + span, total - 1)
    g = haloed[word // seg_words, HALO_WORDS + word % seg_words]
    shift = ((starts & 3) * 8).astype(jnp.uint32)[:, None]
    lo = jax.lax.shift_right_logical(g[:, :-1], shift)
    hi = jnp.where(shift == 0, jnp.uint32(0), g[:, 1:] << (jnp.uint32(32) - shift))
    # bytes of word k inside the chunk: clip(len - 4k, 0, 4)
    nbytes = jnp.clip(lens[:, None] - 4 * span[:, :-1], 0, 4).astype(jnp.uint32)
    keep = jnp.where(nbytes == 4, jnp.uint32(0xFFFFFFFF),
                     (jnp.uint32(1) << (nbytes * jnp.uint32(8))) - jnp.uint32(1))
    return fingerprint_pallas((lo | hi) & keep, interpret=interpret)


def cdc_chunk_fingerprints(haloed, starts, lens, max_size: int,
                           interpret: bool | None = None) -> np.ndarray:
    """(C,) uint64 fingerprints for chunks of device-resident CDC rows.

    Every chunk is zero-padded to ``max_size`` bytes (``w_pad`` words) before
    hashing, so all backends hash identical padded images; the true length is
    mixed into the fold (``chunk_fp64``).  ``max_size`` must make ``w_pad`` a
    LANES multiple (``core.cdc`` validates ``max_size % 512 == 0``).  Chunks
    run ``CHUNKS_PER_LAUNCH`` at a time; the last launch is zero-padded to a
    power-of-two multiple of ``TILE_B``.
    """
    starts = np.ascontiguousarray(starts, dtype=np.int32)
    lens = np.ascontiguousarray(lens, dtype=np.int32)
    c = starts.size
    if c == 0:
        return np.empty(0, dtype=np.uint64)
    w_pad = max_size // 4
    if w_pad % LANES:
        raise ValueError(f"max_size={max_size} must be a multiple of {LANES * 4}")
    interpret = _interpret(interpret)
    haloed = jnp.asarray(haloed)
    fp128 = []
    for a in range(0, c, CHUNKS_PER_LAUNCH):
        n = min(CHUNKS_PER_LAUNCH, c - a)
        size = TILE_B
        while size < n:
            size *= 2  # power-of-two tile counts: few distinct shapes to compile
        st = np.zeros(size, dtype=np.int32)
        ln = np.zeros(size, dtype=np.int32)
        st[:n] = starts[a : a + n]
        ln[:n] = lens[a : a + n]
        fp128.append(_chunk_fp_jit(haloed, jnp.asarray(st), jnp.asarray(ln), w_pad, interpret))
    fp128 = np.concatenate([np.asarray(f) for f in fp128])[:c]
    return chunk_fp64(fp128, lens)


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def _fp_probe_jit(counts, klo, khi, tlo, thi, cap: int, interpret: bool) -> jnp.ndarray:
    return fp_probe_pallas(counts, klo, khi, tlo, thi, cap=cap, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("cap", "interpret"), donate_argnums=(3, 4))
def _fp_insert_jit(counts, klo, khi, tlo, thi, cap: int, interpret: bool):
    return fp_insert_pallas(counts, klo, khi, tlo, thi, cap=cap, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("cap", "interpret"), donate_argnums=(3, 4))
def _fp_remove_jit(counts, klo, khi, tlo, thi, cap: int, interpret: bool):
    return fp_remove_pallas(counts, klo, khi, tlo, thi, cap=cap, interpret=interpret)


def _route_keys(keys_lo, keys_hi, cap: int):
    """Group split keys by home tile for the tiled kernels.

    Returns ``(counts, klo, khi, flat_pos)``: per-tile key counts, flat
    ``(T*K,)`` key arrays (segment ``t`` holds tile ``t``'s keys in batch
    order, zero-padded to ``K``) and the flat position of each input key
    inside them, for scattering per-key kernel outputs back to batch order.
    ``K`` is the max per-tile count rounded up to TILE_KEYS — per-tile
    routing is what lets each grid step stage a single table tile instead
    of the whole table.
    """
    num_tiles, tile_cap, _ = tile_shape(cap)
    klo = np.ascontiguousarray(keys_lo, dtype=np.uint32)
    khi = np.ascontiguousarray(keys_hi, dtype=np.uint32)
    n = klo.size
    if num_tiles == 1:
        k = max(TILE_KEYS, -(-n // TILE_KEYS) * TILE_KEYS)
        klo2 = np.zeros(k, dtype=np.uint32)
        khi2 = np.zeros(k, dtype=np.uint32)
        klo2[:n] = klo
        khi2[:n] = khi
        return np.array([n], dtype=np.int32), klo2, khi2, np.arange(n, dtype=np.int64)
    tile = (slot_hash_host(klo, khi) & np.uint32(cap - 1)) // np.uint32(tile_cap)
    order = np.argsort(tile, kind="stable")
    counts = np.bincount(tile, minlength=num_tiles)
    k = max(TILE_KEYS, -(-int(counts.max()) // TILE_KEYS) * TILE_KEYS)
    starts = np.cumsum(counts) - counts
    sorted_tile = tile[order]
    pos = np.arange(n, dtype=np.int64) - starts[sorted_tile]
    flat_sorted = sorted_tile.astype(np.int64) * k + pos
    klo2 = np.zeros(num_tiles * k, dtype=np.uint32)
    khi2 = np.zeros(num_tiles * k, dtype=np.uint32)
    klo2[flat_sorted] = klo[order]
    khi2[flat_sorted] = khi[order]
    flat_pos = np.empty(n, dtype=np.int64)
    flat_pos[order] = flat_sorted
    return counts.astype(np.int32), klo2, khi2, flat_pos


def _table_pair(table_lo, table_hi, cap: int):
    """The lane arrays in the kernels' ``table_shape(cap)`` layout (host
    arrays in the flat-per-tile ``(T, tile_phys)`` layout are reshaped)."""
    shape = table_shape(cap)
    tlo = jnp.asarray(table_lo)
    thi = jnp.asarray(table_hi)
    if tlo.size != np.prod(shape) or thi.shape != tlo.shape:
        raise ValueError(f"tables {tlo.shape}/{thi.shape} do not hold capacity {cap} {shape}")
    return tlo.reshape(shape), thi.reshape(shape)


_FP_INDEX_JITS = {"probe": _fp_probe_jit, "insert": _fp_insert_jit, "remove": _fp_remove_jit}


def fp_index_launch(op: str, keys_lo, keys_hi, table_lo, table_hi, cap: int,
                    interpret: bool | None = None):
    """One fp-index kernel launch: route the keys to their home tiles, ship
    them, run ``op`` (``probe``/``insert``/``remove``) and read its per-key
    answer back.

    Returns ``(tables, answer, slots)``: the updated ``(table_lo,
    table_hi)`` device buffers (None for a probe), the (N,) int32 per-key
    answer in batch order (probe hit, insert status, remove hit) and the key
    slots the launch was padded to (tiles x ``K``).
    """
    n = len(keys_lo)
    with obs.span("fp_index.route_keys", keys=n):
        counts, klo, khi, flat_pos = _route_keys(keys_lo, keys_hi, cap)
    with obs.span("fp_index.put", keys=n, slots=klo.size):
        tlo, thi = _table_pair(table_lo, table_hi, cap)
        out = _FP_INDEX_JITS[op](jnp.asarray(counts), jnp.asarray(klo), jnp.asarray(khi), tlo,
                                 thi, cap=cap, interpret=_interpret(interpret))
    tables, answer = (None, out) if op == "probe" else (out[:2], out[2])
    with obs.span("fp_index.fetch", keys=n):
        answer = np.asarray(answer)[flat_pos]
    return tables, answer, klo.size


def fp_index_probe(keys_lo, keys_hi, table_lo, table_hi, cap: int,
                   interpret: bool | None = None) -> np.ndarray:
    """(N,) bool membership flags for split uint32 keys against the table.

    ``table_lo``/``table_hi`` hold logical capacity ``cap`` in the tiled
    layout of ``kernels.fp_index`` — device buffers stay resident; only the
    keys travel.  Keys must be sentinel-free; they are routed to their home
    tiles host-side and padded per tile (pad flags are dropped in the
    scatter-back).
    """
    return fp_index_launch("probe", keys_lo, keys_hi, table_lo, table_hi, cap, interpret)[1] != 0


def fp_index_insert(keys_lo, keys_hi, table_lo, table_hi, cap: int,
                    interpret: bool | None = None):
    """Insert split uint32 keys; returns ``(table_lo, table_hi, status)``.

    The returned table arrays are **device buffers** of shape
    ``table_shape(cap)`` (the donated inputs, updated in place) — callers
    keep them resident for the next launch and only materialize a host
    mirror on demand.  ``status`` is a (N,) numpy array in batch order
    (PLACED / PRESENT / OVERFLOW / PLACED_TOMB per ``kernels.fp_index``)."""
    (tlo, thi), status, _ = fp_index_launch("insert", keys_lo, keys_hi, table_lo, table_hi, cap,
                                            interpret)
    return tlo, thi, status


def fp_index_remove(keys_lo, keys_hi, table_lo, table_hi, cap: int,
                    interpret: bool | None = None):
    """Tombstone split uint32 keys; returns ``(table_lo, table_hi, removed)``.

    Like ``fp_index_insert``: device-resident in-place update, keys-only
    transfer.  ``removed`` is a (N,) bool numpy array in batch order."""
    (tlo, thi), status, _ = fp_index_launch("remove", keys_lo, keys_hi, table_lo, table_hi, cap,
                                            interpret)
    return tlo, thi, status != 0


@functools.partial(jax.jit, static_argnames=("nbins", "interpret"))
def _ffh_jit(counts: jnp.ndarray, nbins: int, interpret: bool) -> jnp.ndarray:
    return ffh_pallas(counts, nbins, interpret=interpret)


def ffh_counts(counts, nbins: int = NBINS_DEFAULT, interpret: bool | None = None) -> jnp.ndarray:
    """FFH of occurrence counts (zeros = padding, ignored)."""
    counts = jnp.asarray(counts, dtype=jnp.int32).reshape(-1)
    counts = _pad_axis(counts, 0, TILE * LANES)
    return _ffh_jit(counts, nbins, _interpret(interpret))
