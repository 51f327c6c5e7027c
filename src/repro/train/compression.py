"""Int8 gradient compression with error feedback for cross-pod reduction.

At 1000+ nodes the data-parallel gradient all-reduce crosses the slowest
links (pod-to-pod DCN/optical), so wire bytes matter more than FLOPs.
``compressed_psum_mean`` replaces a bf16/f32 psum with:

  1. per-chunk symmetric int8 quantization (scale = max|g| per chunk),
  2. reduce-scatter implemented as all_to_all of int8 shards + local sum
     (wire payload is int8 -> ~4x fewer bytes than f32 on the wire),
  3. all_gather of the int8-quantized reduced shards,
  4. dequantize + divide by the axis size.

``ErrorFeedback`` keeps the quantization residual and re-adds it next step
(EF-SGD), which is what makes 8-bit gradient exchange converge like fp32 in
practice.  Used by the trainer's ``dp_compress`` mode and measured in
EXPERIMENTS.md §Perf (collective-bytes reduction on the pod axis).
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp


def _quantize(x: jnp.ndarray, chunk: int = 256) -> Tuple[jnp.ndarray, jnp.ndarray]:
    flat = x.reshape(-1)
    pad = (-flat.size) % chunk
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, chunk)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _dequantize(q: jnp.ndarray, scale: jnp.ndarray, shape, size: int) -> jnp.ndarray:
    flat = (q.astype(jnp.float32) * scale).reshape(-1)[:size]
    return flat.reshape(shape)


def compressed_psum_mean(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Mean-reduce ``x`` over a shard_map axis with int8 wire payload.

    Must be called inside shard_map with ``axis_name`` bound.  The exchange
    is all_to_all (int8 + f32 scales) -> local sum -> all_gather (int8), so
    every hop carries ~1/4 of the fp32 bytes.
    """
    n = jax.lax.axis_size(axis_name)
    shape, size = x.shape, x.size
    pad = (-size) % (n * 256)
    flat = jnp.pad(x.reshape(-1), (0, pad))
    shards = flat.reshape(n, -1)                     # shard i goes to device i

    q, scale = _quantize(shards.reshape(-1))         # quantize the whole payload
    q = q.reshape(n, -1, 256)
    scale = scale.reshape(n, -1, 1)

    # reduce-scatter: all_to_all the per-destination shards, sum locally
    q_t = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0, tiled=False)
    s_t = jax.lax.all_to_all(scale, axis_name, split_axis=0, concat_axis=0, tiled=False)
    local = jnp.sum(q_t.astype(jnp.float32) * s_t, axis=0)   # (chunks, 256)

    # quantize the reduced shard and all_gather it (int8 on the wire again)
    q2, s2 = _quantize(local.reshape(-1))
    qg = jax.lax.all_gather(q2, axis_name, axis=0)           # (n, chunks, 256)
    sg = jax.lax.all_gather(s2, axis_name, axis=0)
    full = (qg.astype(jnp.float32) * sg[..., None].reshape(qg.shape[0], -1, 1)).reshape(-1)
    return full[:size].reshape(shape) / n


class ErrorFeedback:
    """Residual accumulator: g_eff = g + e;  e' = g_eff - Q(g_eff)."""

    @staticmethod
    def init(grads: Any) -> Any:
        return jax.tree.map(lambda g: jnp.zeros_like(g, dtype=jnp.float32), grads)

    @staticmethod
    def apply(grads: Any, err: Any) -> Tuple[Any, Any]:
        corrected = jax.tree.map(lambda g, e: g.astype(jnp.float32) + e, grads, err)

        def residual(c):
            q, s = _quantize(c.reshape(-1))
            deq = _dequantize(q, s, c.shape, c.size)
            return deq, c - deq

        pairs = jax.tree.map(residual, corrected)
        deq = jax.tree.map(lambda p: p[0], pairs, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2)
        new_err = jax.tree.map(lambda p: p[1], pairs, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2)
        return deq, new_err
