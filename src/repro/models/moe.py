"""Mixture-of-Experts with manual-SPMD (shard_map) sort-based dispatch.

GSPMD cannot partition the data-dependent sort/scatter/gather of a dropping
MoE dispatch: on the mixtral train cell it replicated the dispatch buffers
per device (observed 247 GiB/device, with "involuntary full
rematerialization" SPMD warnings).  So the dispatch runs under
``jax.shard_map`` over the (pod, data, model) mesh: every index operation
sees *local* shapes, expert matmuls consume the local "model" slice of the
expert weights (ff-sharded; experts additionally divide over "model" when
possible), and a single ``psum`` over "model" combines the w_out partials.
This is exactly the "map the paper's communication pattern onto shard_map"
guidance — the collective schedule is explicit: one psum per MoE layer.

Outside a mesh context (unit tests, single-device smoke) the same local
function runs directly — one code path, validated against a dense-experts
reference in tests/test_moe.py.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.act_sharding import active_mesh, batch_mesh_axes

from .layers import ParamFactory


def init_moe(pf: ParamFactory, d: int, ff: int, num_experts: int, act: str) -> dict:
    p = {
        "router": pf.normal((d, num_experts), ("embed", "experts"), stddev=0.02),
        "w_in": pf.normal((num_experts, d, ff), ("experts", "embed", "ff")),
        "w_out": pf.normal((num_experts, ff, d), ("experts", "ff", "embed")),
    }
    if act == "swiglu":
        p["w_gate"] = pf.normal((num_experts, d, ff), ("experts", "embed", "ff"))
    return p


def _moe_local(
    x: jnp.ndarray,            # (t, d) local tokens
    router: jnp.ndarray,       # (d, E) replicated
    w_in: jnp.ndarray,         # (E_loc, d, ff_loc) local expert slice
    w_gate: Optional[jnp.ndarray],
    w_out: jnp.ndarray,        # (E_loc, ff_loc, d)
    *,
    top_k: int,
    capacity_factor: float,
    act: str,
    expert_offset: jnp.ndarray,  # () int32: first expert id of the local slice
    psum_axes: Tuple[str, ...],
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dense-shape dropping dispatch on local tokens; returns (out, aux)."""
    t, d = x.shape
    e = router.shape[-1]
    e_loc = w_in.shape[0]
    tk = t * top_k

    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(expert_ids[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(me * ce)
    if psum_axes:
        aux = jax.lax.pmean(aux, psum_axes)

    capacity = int(max(1, capacity_factor * tk / e))

    flat_expert = expert_ids.reshape(tk)
    flat_gate = gate_vals.reshape(tk)
    flat_tok = jnp.repeat(jnp.arange(t), top_k)

    order = jnp.argsort(flat_expert)
    se = flat_expert[order]
    stok = flat_tok[order]
    sgate = flat_gate[order]

    counts = jnp.sum(jax.nn.one_hot(se, e, dtype=jnp.int32), axis=0)
    run_start = jnp.cumsum(counts) - counts
    pos = jnp.arange(tk) - run_start[se]
    keep = pos < capacity
    pos_c = jnp.where(keep, pos, 0)
    # keep only experts materialized on this shard
    local_e = se - expert_offset
    on_shard = (local_e >= 0) & (local_e < e_loc)
    keep = keep & on_shard
    local_e = jnp.clip(local_e, 0, e_loc - 1)

    xtok = jnp.where(keep[:, None], x[stok], 0.0)
    buf = jnp.zeros((e_loc, capacity, d), dtype=x.dtype)
    buf = buf.at[local_e, pos_c].add(xtok)

    h = jnp.einsum("ecd,edf->ecf", buf, w_in)
    if act == "swiglu":
        g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    y = jnp.einsum("ecf,efd->ecd", h, w_out)

    vals = y[local_e, pos_c] * jnp.where(keep, sgate, 0.0)[:, None].astype(y.dtype)
    out = jnp.zeros((t, d), dtype=x.dtype).at[stok].add(vals.astype(x.dtype))
    if psum_axes:
        out = jax.lax.psum(out, psum_axes)
    return out, aux


def moe_apply(
    p: dict,
    x: jnp.ndarray,             # (B, S, d)
    *,
    top_k: int,
    capacity_factor: float,
    act: str = "swiglu",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (output (B,S,d), aux load-balance loss scalar)."""
    b, s, d = x.shape
    e = p["w_in"].shape[0]
    mesh = active_mesh()
    w_gate = p.get("w_gate")

    if mesh is None or "model" not in mesh.shape:
        out, aux = _moe_local(
            x.reshape(b * s, d), p["router"], p["w_in"], w_gate, p["w_out"],
            top_k=top_k, capacity_factor=capacity_factor, act=act,
            expert_offset=jnp.int32(0), psum_axes=(),
        )
        return out.reshape(b, s, d), aux

    m = mesh.shape["model"]
    baxes = batch_mesh_axes(mesh)
    bsize = 1
    for a in baxes:
        bsize *= mesh.shape[a]
    if b % max(bsize, 1) != 0:
        baxes = ()  # decode with tiny batches: replicate tokens across DP
    ep = e % m == 0  # true expert parallelism vs tensor-parallel experts
    e_loc = e // m if ep else e
    wspec = P(("model" if ep else None), None, (None if ep else "model"))
    wspec_out = P(("model" if ep else None), (None if ep else "model"), None)
    xspec = P(baxes if baxes else None, None, None)

    def mapped(x_, router, w_in, w_gate_, w_out):
        if ep:
            idx = jax.lax.axis_index("model")
            offset = (idx * e_loc).astype(jnp.int32)
        else:
            offset = jnp.int32(0)
        bl, sl, _ = x_.shape
        out, aux = _moe_local(
            x_.reshape(bl * sl, d), router, w_in, w_gate_, w_out,
            top_k=top_k, capacity_factor=capacity_factor, act=act,
            expert_offset=offset, psum_axes=("model",),
        )
        return out.reshape(bl, sl, d), aux

    out, aux = jax.shard_map(
        mapped,
        mesh=mesh,
        in_specs=(xspec, P(None, None), wspec, (wspec if w_gate is not None else P()), wspec_out),
        out_specs=(xspec, P()),
        check_vma=False,
    )(x, p["router"], p["w_in"], w_gate if w_gate is not None else jnp.zeros((), x.dtype), p["w_out"])
    return out, aux
