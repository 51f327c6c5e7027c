"""Compile-only checks of the dedup hot-path kernels for a TPU v5e chip.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: unaligned dynamic slices, unsigned reductions, blocks that
break the (8, 128) tiling rule, or a launch whose temp does not fit HBM.
These tests lower and compile each kernel — and the fused chunk-fingerprint
jit at its per-launch chunk count — for a described ``v5e:2x2`` topology
without a chip attached.  Nothing runs; a pass here is not a chip run.

The topology is described inside a module fixture (never at import), so
every xdist worker collects the same tests and only the worker that is
handed this file loads the TPU compiler.
"""

from __future__ import annotations

import functools
import os

import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the persistent
    # cache without the chip: keep it out of the cache entirely
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes, **static):
    import jax

    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(functools.partial(fn, **static)).lower(*args).compile()


@pytest.mark.parametrize("blocks,words", [(1024, 1024), (256, 4096)], ids=["4KiB", "16KiB"])
def test_fingerprint_kernel_compiles(one_chip, blocks, words):
    import jax.numpy as jnp

    from repro.kernels.fingerprint import NUM_HASHES, fingerprint_pallas

    compiled = _compile(fingerprint_pallas, one_chip, ((blocks, words), jnp.uint32),
                        interpret=False)
    assert compiled.memory_analysis().output_size_in_bytes == blocks * NUM_HASHES * 4


def test_cdc_candidates_kernel_compiles(one_chip):
    import jax.numpy as jnp

    from repro.kernels.cdc import HALO_WORDS, SEG_WORDS, cdc_candidates_pallas

    _compile(cdc_candidates_pallas, one_chip, ((512, HALO_WORDS + SEG_WORDS), jnp.uint32),
             avg_size=4096, interpret=False)


def test_ffh_kernel_compiles(one_chip):
    import jax.numpy as jnp

    from repro.kernels.histogram import LANES, TILE, ffh_pallas

    _compile(ffh_pallas, one_chip, ((64 * TILE * LANES,), jnp.int32), nbins=40, interpret=False)


@pytest.mark.parametrize("op", ["probe", "insert", "remove"])
def test_fp_index_kernels_compile_multi_tile(one_chip, op):
    import jax.numpy as jnp

    from repro.kernels import fp_index as k

    cap = 64 * k.TILE_SLOTS  # a 64-tile table
    t, rows, lanes = k.table_shape(cap)
    assert t == 64
    fn = {"probe": k.fp_probe_pallas, "insert": k.fp_insert_pallas,
          "remove": k.fp_remove_pallas}[op]
    keys = t * 2 * k.TILE_KEYS
    _compile(fn, one_chip, ((t,), jnp.int32), ((keys,), jnp.uint32), ((keys,), jnp.uint32),
             ((t, rows, lanes), jnp.uint32), ((t, rows, lanes), jnp.uint32),
             cap=cap, interpret=False)


def test_fused_chunk_fingerprint_fits_its_temp_bound(one_chip):
    """The fused CDC gather + fingerprint at the chunker's per-launch chunk
    count and the largest chunk size: compiles, and its device temp stays
    under the stated bound regardless of how many rows are resident."""
    import jax.numpy as jnp

    from repro.kernels.cdc import HALO_WORDS, SEG_WORDS
    from repro.kernels.ops import CHUNK_TEMP_BYTES, CHUNKS_PER_LAUNCH, _chunk_fp_jit

    rows = (256 << 20) // (SEG_WORDS * 4)  # 256 MiB of payload resident
    compiled = _compile(
        _chunk_fp_jit, one_chip,
        ((rows, HALO_WORDS + SEG_WORDS), jnp.uint32),
        ((CHUNKS_PER_LAUNCH,), jnp.int32), ((CHUNKS_PER_LAUNCH,), jnp.int32),
        w_pad=16384 // 4, interpret=False,
    )
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert 0 < temp <= CHUNK_TEMP_BYTES, temp
