import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x shape x mesh)
cell and extract the roofline inputs from the compiled artifact.

The two lines above MUST stay the first statements in this module — jax
locks the device count on first initialization, and only the dry-run is
allowed to fake 512 host devices (smoke tests and benches see 1).

Per cell this script:
  1. builds the model and its abstract params (ShapeDtypeStruct, no alloc),
  2. derives PartitionSpecs from logical axes (repro.sharding),
  3. ``jax.jit(step, in_shardings=...).lower(...).compile()``,
  4. records ``memory_analysis()`` (bytes/device), ``cost_analysis()``
     (FLOPs + bytes accessed, per device), and the collective schedule
     parsed from the compiled HLO (wire bytes per device per step),
  5. appends a JSON record (one per cell) to the output file.

Usage:
  python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun.json
"""

import argparse
import json
import re
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, ShapeSpec, get_config
from repro.models import build_model
from repro.models import config as C
from repro.sharding import activation_rules, batch_pspecs, cache_pspecs, param_pspecs, shardings_of
from repro.train.optimizer import AdamW, AdamWState
from repro.train.train_step import make_grad_accum_train_step, make_serve_step, make_train_step

from .analytic import analytic_terms
from .mesh import make_production_mesh

# long_500k runs only for bounded-state decoders (DESIGN.md §4).
LONG_CONTEXT_ARCHS = {"mixtral-8x7b", "recurrentgemma-2b", "rwkv6-1.6b"}

# v5e roofline constants (per chip)
PEAK_FLOPS = 197e12        # bf16
HBM_BW = 819e9             # bytes/s
ICI_BW = 4.95e10           # bytes/s/link (~50 GB/s)
HBM_BYTES = 16 * 2**30


def skip_reason(arch: str, shape: ShapeSpec) -> Optional[str]:
    cfg = get_config(arch)
    if shape.name == "long_500k" and arch not in LONG_CONTEXT_ARCHS:
        return "full-attention arch: 500k decode state unbounded (DESIGN.md §4)"
    if cfg.is_encdec and shape.name == "long_500k":
        return "enc-dec: quadratic encoder at 500k frames (DESIGN.md §4)"
    return None


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins; no allocation).
# ---------------------------------------------------------------------------


def input_specs(cfg: C.ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    cdt = jnp.dtype(cfg.compute_dtype)
    tok = jax.ShapeDtypeStruct((b, s), i32)
    if cfg.is_encdec:
        sd = min(cfg.decoder_slots, 448)
        return {
            "encoder_embeds": jax.ShapeDtypeStruct((b, s, cfg.d_model), cdt),
            "decoder_tokens": jax.ShapeDtypeStruct((b, sd), i32),
            "targets": jax.ShapeDtypeStruct((b, sd), i32),
            "mask": jax.ShapeDtypeStruct((b, sd), jnp.float32),
        }
    specs: Dict[str, Any] = {
        "targets": tok,
        "mask": jax.ShapeDtypeStruct((b, s), jnp.float32),
    }
    if cfg.family == "vlm" or not cfg.embed_inputs:
        specs["embeds"] = jax.ShapeDtypeStruct((b, s, cfg.d_model), cdt)
    else:
        specs["inputs"] = tok
    if shape.kind == "prefill":
        specs.pop("targets"), specs.pop("mask")
    return specs


# ---------------------------------------------------------------------------
# Collective schedule extraction.
# ---------------------------------------------------------------------------

_SHAPE_RE = re.compile(r"(?:f|bf|s|u|pred)(?:8|16|32|64)?\[([0-9,]*)\]")
_DTYPE_BYTES = {"f32": 4, "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "s8": 1, "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "f8": 1}
_COLL_RE = re.compile(
    r"=\s*((?:f|bf|s|u|pred)[0-9]*\[[0-9,]*\][^=]*?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)


def _first_shape_bytes(type_str: str) -> int:
    m = re.match(r"((?:f|bf|s|u|pred)[0-9]*)\[([0-9,]*)\]", type_str.strip().strip("("))
    if not m:
        return 0
    dt, dims = m.groups()
    nbytes = _DTYPE_BYTES.get(dt, 4)
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * nbytes


def collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Sum per-device wire bytes of every collective in the compiled module.

    Shapes in the SPMD module are per-device.  Wire-cost model (ring):
    all-reduce ~ 2x result bytes; all-gather ~ result bytes; reduce-scatter
    ~ operand (= result x group) bytes ~ approximated by result x 1 here via
    the *result* shape of the op line; all-to-all / collective-permute ~
    result bytes.
    """
    totals = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0, "all-to-all": 0, "collective-permute": 0}
    counts = dict.fromkeys(totals, 0)
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.search(
            r"= ((?:f|bf|s|u|pred)[0-9]*\[[0-9,]*\])[^ ]* (all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)(?:-start)?\(",
            line,
        )
        if not m:
            # tuple-typed results: (bf16[...], bf16[...]) all-reduce-start(...)
            m2 = re.search(
                r"= \(((?:f|bf|s|u|pred)[0-9]*\[[0-9,]*\])[^)]*\) (all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)(?:-start)?\(",
                line,
            )
            if not m2:
                continue
            type_str, op = m2.groups()
        else:
            type_str, op = m.groups()
        if "-done(" in line:
            continue  # avoid double counting start/done pairs
        nbytes = _first_shape_bytes(type_str)
        factor = 2 if op == "all-reduce" else 1
        totals[op] += nbytes * factor
        counts[op] += 1
    return {"bytes": totals, "counts": counts, "total_bytes": sum(totals.values())}


# ---------------------------------------------------------------------------
# Cell runner.
# ---------------------------------------------------------------------------


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool = False,
    remat: str = "dots",
    fsdp: bool = True,
    serve_fsdp: bool = False,
    moment_dtype: str = "float32",
    microbatches: int = 1,
    grad_compress: bool = False,
    kv_dedup_factor: float = 1.0,
    act_rules: Optional[Dict[str, Any]] = None,
    verbose: bool = True,
) -> Dict[str, Any]:
    shape = SHAPES[shape_name]
    rec: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind,
        "remat": remat,
        "fsdp": fsdp,
    }
    reason = skip_reason(arch, shape)
    if reason:
        rec["status"] = "skipped"
        rec["reason"] = reason
        return rec

    cfg = get_config(arch)
    if shape.kind != "train":
        cfg = cfg.replace(param_dtype="bfloat16")  # serving runs bf16 weights
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    model = build_model(cfg, remat=remat)
    params_sds, axes = model.abstract_params()

    t0 = time.time()
    with mesh, activation_rules(mesh, act_rules):
        if shape.kind == "train":
            pspecs = param_pspecs(params_sds, axes, mesh, mode="train", fsdp=fsdp)
            opt = AdamW(moment_dtype=moment_dtype)
            opt_sds = opt.abstract_state(params_sds)
            opt_pspecs = AdamWState(P(), pspecs, pspecs)
            bspecs = batch_pspecs(cfg, "train", shape.global_batch, mesh)
            batch_sds = input_specs(cfg, shape)
            def lowered_fn():
                # fresh fn: no jit trace-cache reuse
                if microbatches > 1:
                    step = make_grad_accum_train_step(model, opt, microbatches)
                else:
                    step = make_train_step(model, opt)
                return jax.jit(
                    step,
                    in_shardings=(
                        shardings_of(pspecs, mesh),
                        shardings_of(opt_pspecs, mesh),
                        shardings_of(bspecs, mesh),
                    ),
                    donate_argnums=(0, 1),
                ).lower(params_sds, opt_sds, batch_sds)
        elif shape.kind == "prefill":
            pspecs = param_pspecs(params_sds, axes, mesh, mode="serve" if not serve_fsdp else "train", fsdp=serve_fsdp)
            bspecs = batch_pspecs(cfg, "prefill", shape.global_batch, mesh)
            batch_sds = input_specs(cfg, shape)

            def lowered_fn():
                prefill = lambda p, b: model.prefill(p, b)  # fresh fn per lowering
                return jax.jit(
                    prefill,
                    in_shardings=(shardings_of(pspecs, mesh), shardings_of(bspecs, mesh)),
                ).lower(params_sds, batch_sds)
        else:  # decode
            pspecs = param_pspecs(params_sds, axes, mesh, mode="serve" if not serve_fsdp else "train", fsdp=serve_fsdp)
            b = shape.global_batch
            slots = shape.seq_len
            enc_slots = shape.seq_len if cfg.is_encdec else 0
            self_slots = min(cfg.decoder_slots, 448) if cfg.is_encdec else slots
            cache_sds = model.abstract_cache(b, self_slots, enc_slots)
            cspecs = cache_pspecs(cfg, mesh, b, self_slots, enc_slots)
            tokens_sds = jax.ShapeDtypeStruct((b, 1), jnp.int32)
            pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
            def lowered_fn():
                step = make_serve_step(model)  # fresh fn per lowering
                return jax.jit(
                    step,
                    in_shardings=(
                        shardings_of(pspecs, mesh),
                        shardings_of(cspecs, mesh),
                        NamedSharding(mesh, P(None, None)),
                        NamedSharding(mesh, P()),
                    ),
                    donate_argnums=(1,),
                ).lower(params_sds, cache_sds, tokens_sds, pos_sds)
        lowered = lowered_fn()
        compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 2)

    ma = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())

    arg_b = int(getattr(ma, "argument_size_in_bytes", 0))
    out_b = int(getattr(ma, "output_size_in_bytes", 0))
    tmp_b = int(getattr(ma, "temp_size_in_bytes", 0))
    alias_b = int(getattr(ma, "alias_size_in_bytes", 0))
    peak = arg_b + out_b + tmp_b - alias_b

    # roofline terms: analytic model (repro.launch.analytic) — XLA cost
    # analysis counts scanned bodies once, so HLO numbers are kept only as
    # per-body lower bounds.
    terms = analytic_terms(
        cfg, shape.kind, shape.seq_len, shape.global_batch,
        dict(mesh.shape), remat=remat, fsdp=fsdp, moment_dtype=moment_dtype,
        serve_fsdp=serve_fsdp, grad_compress=grad_compress,
        kv_dedup_factor=kv_dedup_factor, act_rules=act_rules,
    )

    # tokens processed globally this step
    if shape.kind == "train":
        tokens = shape.global_batch * (min(cfg.decoder_slots, 448) if cfg.is_encdec else shape.seq_len)
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
    else:
        tokens = shape.global_batch
    n_active = cfg.active_params_per_token_matmul()
    model_flops = (6 if shape.kind == "train" else 2) * n_active * tokens

    rec.update(
        status="ok",
        bytes_per_device={"args": arg_b, "out": out_b, "temp": tmp_b, "alias": alias_b, "peak": peak},
        fits_hbm=bool(peak <= HBM_BYTES),
        flops_per_device=terms.flops,
        bytes_accessed_per_device=terms.hbm_bytes,
        collective_wire_bytes_per_device=terms.wire_bytes,
        analytic_detail={k: float(v) for k, v in terms.detail.items()},
        hlo_body_flops=float(cost.get("flops", 0.0)),
        hlo_body_bytes=float(cost.get("bytes accessed", 0.0)),
        collectives=coll,
        compute_s=terms.flops / PEAK_FLOPS,
        memory_s=terms.hbm_bytes / HBM_BW,
        collective_s=terms.wire_bytes / ICI_BW,
        model_flops_global=model_flops,
        useful_flops_ratio=(model_flops / chips) / terms.flops if terms.flops else 0.0,
        chips=chips,
        total_params=cfg.total_params(),
        active_params=n_active,
    )
    terms = {"compute": rec["compute_s"], "memory": rec["memory_s"], "collective": rec["collective_s"]}
    rec["dominant"] = max(terms, key=terms.get)
    if verbose:
        print(
            f"[{rec['mesh']}] {arch:28s} {shape_name:12s} compile={rec['compile_s']:6.1f}s "
            f"peak/dev={peak/2**30:7.2f}GiB fits={rec['fits_hbm']} "
            f"C/M/N={rec['compute_s']*1e3:8.2f}/{rec['memory_s']*1e3:8.2f}/{rec['collective_s']*1e3:8.2f} ms "
            f"dom={rec['dominant']}"
        )
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES.keys()))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true", help="run every (arch x shape) cell")
    ap.add_argument("--remat", default="dots", choices=["none", "dots", "full"])
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--moment-dtype", default="float32")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    cells = []
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES.keys()) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                try:
                    cells.append(
                        run_cell(
                            arch, shape, multi_pod=mp, remat=args.remat,
                            fsdp=not args.no_fsdp, moment_dtype=args.moment_dtype,
                        )
                    )
                except Exception as e:  # a failing cell is a bug: record + continue
                    print(f"FAILED {arch} {shape} multi_pod={mp}: {e}")
                    cells.append(
                        {"arch": arch, "shape": shape, "mesh": "2x16x16" if mp else "16x16",
                         "status": "failed", "error": str(e)[:2000]}
                    )
    ok = sum(1 for c in cells if c.get("status") == "ok")
    sk = sum(1 for c in cells if c.get("status") == "skipped")
    fail = sum(1 for c in cells if c.get("status") == "failed")
    print(f"\n{ok} ok / {sk} skipped / {fail} failed")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(cells, f, indent=1)
        print(f"wrote {args.out}")
    if fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
