"""Multi-device semantics (8 faked host devices, subprocess-isolated):
sharded MoE == local MoE; compressed psum == exact psum; elastic restore
across mesh shapes; sharded train step == single-device train step."""

import os
import subprocess
import sys
import textwrap


ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=420,
    )
    assert out.returncode == 0, out.stdout + "\n" + out.stderr


def test_moe_shardmap_matches_local():
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.act_sharding import activation_rules
        from jax.sharding import AxisType
        from repro.models.moe import init_moe, moe_apply
        from repro.models.layers import ParamFactory, unzip_params
        mesh = jax.make_mesh((4,2), ("data","model"), axis_types=(AxisType.Auto,) * 2)
        for E in (4, 3):
            pf = ParamFactory(jax.random.PRNGKey(0), jnp.float32)
            params, _ = unzip_params(init_moe(pf, 16, 32, E, "swiglu"))
            x = jnp.asarray(np.random.default_rng(0).standard_normal((4, 16, 16)), jnp.float32)
            ref, _ = moe_apply(params, x, top_k=2, capacity_factor=8.0, act="swiglu")
            with mesh, activation_rules(mesh):
                out, _ = jax.jit(lambda p, xx: moe_apply(p, xx, top_k=2, capacity_factor=8.0, act="swiglu"))(params, x)
            assert float(jnp.max(jnp.abs(out - ref))) < 1e-4
        print("ok")
    """)


def test_compressed_psum_close_to_exact():
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax.sharding import AxisType
        from repro.train.compression import compressed_psum_mean
        mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
        x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 1000)), jnp.float32)
        exact = jnp.mean(x, axis=0)
        f = jax.shard_map(lambda xs: compressed_psum_mean(xs[0], "data"),
                          mesh=mesh, in_specs=P("data", None), out_specs=P(None), check_vma=False)
        approx = jax.jit(f)(x)
        err = float(jnp.max(jnp.abs(approx - exact)))
        rng = float(jnp.max(jnp.abs(exact)) )
        assert err < 0.05 * max(rng, 1.0), (err, rng)
        print("ok")
    """)


def test_elastic_restore_across_mesh_shapes():
    _run("""
        import tempfile, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.ckpt import checkpoint as ckpt
        from jax.sharding import AxisType
        m1 = jax.make_mesh((4,2), ("data","model"), axis_types=(AxisType.Auto,) * 2)
        m2 = jax.make_mesh((2,4), ("data","model"), axis_types=(AxisType.Auto,) * 2)
        w = jnp.arange(64.0).reshape(8, 8)
        w1 = jax.device_put(w, NamedSharding(m1, P("data", "model")))
        with tempfile.TemporaryDirectory() as d:
            ckpt.save(d, 1, {"w": w1})
            out = ckpt.restore(d, 1, {"w": jax.ShapeDtypeStruct((8,8), jnp.float32)},
                               shardings={"w": NamedSharding(m2, P("data", "model"))})
        np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(w))
        assert out["w"].sharding.mesh.shape["data"] == 2
        print("ok")
    """)


def test_sharded_train_step_matches_single_device():
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models import build_model
        from repro.sharding import activation_rules, batch_pspecs, param_pspecs, shardings_of
        from repro.train.optimizer import AdamW, AdamWState
        from repro.train.train_step import make_train_step
        cfg = get_config("tinyllama-1.1b", smoke=True)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        opt = AdamW(learning_rate=1e-3, warmup_steps=1, schedule="constant")
        batch = {"inputs": jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0, cfg.vocab_size),
                 "targets": jax.random.randint(jax.random.PRNGKey(2), (8, 64), 0, cfg.vocab_size),
                 "mask": jnp.ones((8, 64), jnp.float32)}
        step = make_train_step(model, opt)
        _, _, loss_ref, _ = jax.jit(step)(params, opt.init(params), batch)

        from jax.sharding import AxisType
        mesh = jax.make_mesh((4,2), ("data","model"), axis_types=(AxisType.Auto,) * 2)
        sds, axes = model.abstract_params()
        pspecs = param_pspecs(sds, axes, mesh, mode="train", fsdp=True)
        bspecs = batch_pspecs(cfg, "train", 8, mesh)
        with mesh, activation_rules(mesh):
            f = jax.jit(step, in_shardings=(shardings_of(pspecs, mesh),
                                            shardings_of(AdamWState(P(), pspecs, pspecs), mesh),
                                            shardings_of(bspecs, mesh)))
            _, _, loss_sh, _ = f(params, opt.init(params), batch)
        assert abs(float(loss_ref) - float(loss_sh)) < 0.05, (float(loss_ref), float(loss_sh))
        print("ok")
    """)
