"""Pallas TPU kernel: lane-parallel 128-bit block fingerprinting.

The paper fingerprints every 4 KB block with MD5 on the CPU — the hot loop of
the whole inline phase.  MD5 is a long serial dependency chain of 32-bit ops,
which wastes the TPU's 8x128 vector unit.  We instead define a TPU-native
hash (DESIGN.md §2) whose data flow matches the VPU:

* a block of ``W`` 32-bit words is viewed as ``W/128`` chunks of 128 lanes;
* each chunk is whitened lane-wise (xor with per-lane keys, multiply by odd
  constants, xor-shift) and reduced over the lane axis with a weighted sum —
  one VPU pass per chunk, all blocks in the tile progressing in parallel;
* chunk digests fold sequentially (only ``W/128`` iterations) through an
  xxhash-style avalanche;
* four independent key sets produce 4 x 32 bits = a 128-bit fingerprint.

Collision behaviour is that of a multiply-shift universal family — ample for
dedup indexing (and the engine supports byte-verify on match, like ZFS
``verify=on``).  Crypto preimage resistance is deliberately traded away; the
paper needs identity, not secrecy.

Tiling: blocks tile at ``TILE_B`` rows in VMEM; the full word dimension
stays resident because one block's hash needs all its words
(``BlockSpec((TILE_B, W), lambda i: (i, 0))``).  For 4 KB blocks
(W = 1024 words) a 256-row tile is 1 MiB of VMEM — comfortably
double-bufferable on v5e (16 MiB VMEM less scratch).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_B = 256        # blocks per grid step
LANES = 128         # TPU lane width; word dim must be a multiple
NUM_HASHES = 4      # 4 x 32-bit = 128-bit fingerprint

# xxhash32 primes (odd -> invertible multipliers mod 2^32).  Kept as Python
# ints: Pallas kernels may not capture device-array constants, so every use
# site casts inline (the cast becomes an HLO literal).
PRIME1 = 2654435761
PRIME2 = 2246822519
PRIME3 = 3266489917
PRIME4 = 668265263
PRIME5 = 374761393

SEEDS = (0x02CC5D05, 0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D)


def _lane_keys(salt: int) -> jnp.ndarray:
    """Per-lane whitening keys: a Weyl sequence on the lane index."""
    lane = jnp.arange(LANES, dtype=jnp.uint32)
    return (lane * jnp.uint32(0x9E3779B9) + jnp.uint32(salt)) | jnp.uint32(1)


def _avalanche(h: jnp.ndarray) -> jnp.ndarray:
    """xxhash32 finalization mix."""
    h = h ^ jax.lax.shift_right_logical(h, jnp.uint32(15))
    h = h * jnp.uint32(PRIME2)
    h = h ^ jax.lax.shift_right_logical(h, jnp.uint32(13))
    h = h * jnp.uint32(PRIME3)
    h = h ^ jax.lax.shift_right_logical(h, jnp.uint32(16))
    return h


def _rotl(v: jnp.ndarray, r: int) -> jnp.ndarray:
    r = jnp.uint32(r)
    return (v << r) | jax.lax.shift_right_logical(v, jnp.uint32(32) - r)


def _fingerprint_kernel(x_ref, o_ref, *, w: int):
    """Hash one (TILE_B, W) uint32 tile -> (TILE_B, NUM_HASHES) uint32.

    Each 128-lane chunk is read from the ref once (an aligned dynamic lane
    slice) and feeds all four hashes.  The weighted lane reduction sums in
    int32: Mosaic has no unsigned reductions, and a two's-complement sum
    wraps exactly like the uint32 one, so the digests are unchanged.
    """
    tile_b = x_ref.shape[0]
    lane = jnp.arange(LANES, dtype=jnp.uint32)
    keys, lane_mults = [], []
    for which in range(NUM_HASHES):
        keys.append(_lane_keys(0xA5A5A5A5 + 0x01000193 * which)[None, :])
        lane_mults.append(
            ((lane * jnp.uint32(PRIME4) + jnp.uint32(SEEDS[which])) | jnp.uint32(1))[None, :]
        )

    def body(c, hs):
        chunk = x_ref[:, pl.ds(pl.multiple_of(c * LANES, LANES), LANES)]
        # fold the chunk index so chunk permutations change the digest
        tag = (c.astype(jnp.uint32) + jnp.uint32(1)) * jnp.uint32(PRIME5)
        out = []
        for h, key, mult in zip(hs, keys, lane_mults):
            t = (chunk ^ key) * jnp.uint32(PRIME1)
            t = t ^ jax.lax.shift_right_logical(t, jnp.uint32(15))
            t = t * jnp.uint32(PRIME2)
            # weighted lane reduction: order-sensitive within the chunk
            prod = jax.lax.bitcast_convert_type(t * mult, jnp.int32)
            s = jax.lax.bitcast_convert_type(jnp.sum(prod, axis=1), jnp.uint32)
            h = _rotl(h + s * jnp.uint32(PRIME3), 13) * jnp.uint32(PRIME1)
            out.append(h ^ tag)
        return tuple(out)

    hs = tuple(jnp.full((tile_b,), SEEDS[which], dtype=jnp.uint32) for which in range(NUM_HASHES))
    hs = jax.lax.fori_loop(0, w // LANES, body, hs)
    # length padding, then the avalanche
    o_ref[...] = jnp.stack([_avalanche(h ^ jnp.uint32(w)) for h in hs], axis=1)


def fingerprint_pallas(blocks: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """Fingerprint (B, W) uint32 blocks -> (B, NUM_HASHES) uint32.

    B must be a multiple of TILE_B and W a multiple of LANES (ops.py pads).
    """
    b, w = blocks.shape
    if b % TILE_B:
        raise ValueError(f"B={b} must be a multiple of TILE_B={TILE_B}")
    if w % LANES:
        raise ValueError(f"W={w} must be a multiple of LANES={LANES}")
    grid = (b // TILE_B,)
    return pl.pallas_call(
        functools.partial(_fingerprint_kernel, w=w),
        out_shape=jax.ShapeDtypeStruct((b, NUM_HASHES), jnp.uint32),
        grid=grid,
        in_specs=[pl.BlockSpec((TILE_B, w), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((TILE_B, NUM_HASHES), lambda i: (i, 0)),
        interpret=interpret,
    )(blocks)
