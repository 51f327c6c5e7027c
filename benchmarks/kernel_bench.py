"""Kernel + data-plane throughput benchmarks.

Wall-clock numbers off the chip measure the *interpret-mode* kernel
(correctness vehicle); the derived column reports the analytic roofline of
the device the run sees, from its HBM rate (``HBM_BYTES_PER_S``, keyed by
``device_kind``; an unknown device is an error, not a default): the
fingerprint kernel is memory-bound (reads every block once, writes
16 B/block), so its ceiling is HBM bandwidth.
"""

from __future__ import annotations

import time
from typing import List

import numpy as np

from repro.kernels.ops import ffh_counts, fingerprint_blocks, fingerprint_ints

# Published HBM bandwidth per chip, by jax ``device_kind`` (Google Cloud
# documentation, "TPU v5e": 819 GB/s).
HBM_BYTES_PER_S = {"TPU v5 lite": 819e9}


def hbm_bytes_per_s() -> float:
    """HBM rate of the device this process runs on; raises if unknown."""
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in HBM_BYTES_PER_S:
        raise KeyError(f"no HBM rate recorded for device kind {kind!r}")
    return HBM_BYTES_PER_S[kind]


def _time(fn, *args, reps=3):
    fn(*args)  # warm/compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    if hasattr(out, "block_until_ready"):
        out.block_until_ready()
    return (time.perf_counter() - t0) / reps


def bench_fingerprint() -> List[dict]:
    rows = []
    rng = np.random.default_rng(0)
    for b, w in ((256, 1024), (1024, 1024), (4096, 256)):
        x = rng.integers(0, 2**32, size=(b, w), dtype=np.uint32)
        dt = _time(fingerprint_blocks, x)
        gb = b * w * 4 / 1e9
        rows.append({
            "bench": "fingerprint_kernel", "blocks": b, "words": w,
            "us_per_call_interpret": round(dt * 1e6, 1),
            "interpret_gbps": round(gb / dt, 3),
            "roofline_us": round((b * w * 4 + b * 16) / hbm_bytes_per_s() * 1e6, 2),
        })
    return rows


def bench_ingest_dataplane() -> List[dict]:
    """The paper's hot loop end-to-end: hash + dedup-engine decision rate."""
    from repro.core import HPDedup

    rng = np.random.default_rng(1)
    n = 20_000
    blocks = rng.integers(0, 2**32, size=(n, 256), dtype=np.uint32)
    # ~50% duplicates with temporal locality (duplicate a block ~100 back)
    for i in range(200, n):
        if rng.random() < 0.5:
            blocks[i] = blocks[i - int(rng.integers(1, 150))]
    t0 = time.perf_counter()
    fps = fingerprint_ints(blocks)
    t_fp = time.perf_counter() - t0
    eng = HPDedup(cache_entries=8192, adaptive_threshold=False, fixed_threshold=1)
    t0 = time.perf_counter()
    for i, fp in enumerate(fps):
        eng.write(0, i, int(fp))
    t_eng = time.perf_counter() - t0
    return [{
        "bench": "ingest_dataplane", "blocks": n,
        "fingerprint_us_per_block": round(t_fp / n * 1e6, 2),
        "engine_us_per_block": round(t_eng / n * 1e6, 2),
        "inline_dedup_ratio": round(eng.finish(run_post_to_exact=False).inline_dedup_ratio, 3),
    }]


def bench_paged_attention() -> List[dict]:
    """Decode attention over deduped pages (interpret timing + note)."""
    import jax.numpy as jnp

    from repro.kernels.paged_attention import paged_attention

    rng = np.random.default_rng(3)
    B, H, KVH, D, ps, pps = 4, 8, 2, 128, 16, 8
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((B * pps, ps, KVH, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((B * pps, ps, KVH, D)), jnp.float32)
    table = jnp.asarray(rng.integers(0, B * pps, (B, pps)), jnp.int32)
    lengths = jnp.full((B,), ps * pps, jnp.int32)
    dt = _time(lambda: paged_attention(q, kp, vp, table, lengths, interpret=True))
    cache_gb = B * pps * ps * KVH * D * 2 * 4 / 1e9
    return [{
        "bench": "paged_attention_kernel", "batch": B, "pages": pps,
        "us_per_call_interpret": round(dt * 1e6, 1),
        "roofline_us": round(cache_gb * 1e9 / hbm_bytes_per_s() * 1e6, 2),
    }]


def bench_ffh() -> List[dict]:
    rng = np.random.default_rng(2)
    rows = []
    for n in (4096, 65_536):
        c = rng.integers(0, 60, size=n).astype(np.int32)
        dt = _time(ffh_counts, c, 40)
        rows.append({
            "bench": "ffh_kernel", "counts": n,
            "us_per_call_interpret": round(dt * 1e6, 1),
        })
    return rows
