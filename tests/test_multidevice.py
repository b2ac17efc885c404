"""Multi-device integration tests.

These need >1 XLA device, so each runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the main test
process keeps the default single device, per the project rule that only
the dry-run sees placeholder devices).
"""
import os
import subprocess
import sys
import textwrap

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_sub(code: str, devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nERR:\n{out.stderr}"
    return out.stdout


def test_moe_ep_lcx_matches_local_oracle():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import ModelConfig
        from repro.models import init_model, apply_model
        from repro.parallel.sharding import use_mesh, param_shardings
        from repro.compat import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        f32 = dict(dtype=jnp.float32, param_dtype=jnp.float32, q_block=8)
        cfg = ModelConfig(name="m", family="moe", n_layers=2, d_model=64,
                          n_heads=4, n_kv_heads=4, d_ff=128, vocab=97,
                          n_experts=8, n_experts_per_tok=2, moe_d_ff=96,
                          moe_backend="lcx", capacity_factor=16.0, **f32)
        ref_cfg = dataclasses.replace(cfg, moe_backend="sort")
        params, dims = init_model(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 97)
        ref, _ = apply_model(ref_cfg, params, toks)
        with use_mesh(mesh):
            ps = param_shardings(dims, params, mesh)
            params_s = jax.device_put(params, ps)
            toks_s = jax.device_put(toks, NamedSharding(mesh, P("data", None)))
            out, _ = jax.jit(lambda p, t: apply_model(cfg, p, t))(params_s, toks_s)
        err = float(np.abs(np.asarray(out) - np.asarray(ref)).max())
        assert err < 5e-5, err
        print("ok", err)
        """)


def test_ring_allgather_pallas_kernel():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.kernels.ring_allgather import ring_all_gather
        from repro.compat import make_mesh, shard_map
        mesh = make_mesh((8,), ("x",))
        x = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16)
        f = shard_map(lambda s: ring_all_gather(s, "x", axis_size=8,
                                                 interpret=True),
                      mesh, in_specs=P("x", None),
                      out_specs=P("x", None))
        out = jax.jit(f)(x)
        got = np.asarray(out).reshape(8, 8, 16)
        assert (got == np.asarray(x)[None]).all()
        print("ok")
        """)


def test_train_step_sharded_matches_single_device():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import ModelConfig
        from repro.runtime import Trainer, TrainConfig
        from repro.compat import make_mesh
        cfg = ModelConfig(name="t", n_layers=2, d_model=64, n_heads=4,
                          n_kv_heads=2, d_ff=128, vocab=211,
                          dtype=jnp.float32, param_dtype=jnp.float32,
                          remat="none", q_block=8)
        tcfg = TrainConfig(lr=1e-3, warmup=0, total_steps=4, seq_len=32,
                           global_batch=8, donate=False)
        mesh = make_mesh((2, 4), ("data", "model"))
        tr_m = Trainer(cfg, tcfg, mesh=mesh)
        tr_1 = Trainer(cfg, tcfg, mesh=None)
        tr_m._run_until(2)
        tr_1._run_until(2)
        a = np.concatenate([np.asarray(x).ravel()
                            for x in jax.tree.leaves(tr_m.params)])
        b = np.concatenate([np.asarray(x).ravel()
                            for x in jax.tree.leaves(tr_1.params)])
        err = np.abs(a - b).max()
        assert err < 2e-4, err
        print("ok", err)
        """)


def test_elastic_remesh_preserves_state():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import ModelConfig
        from repro.runtime import Trainer, TrainConfig
        from repro.compat import make_mesh
        cfg = ModelConfig(name="t", n_layers=2, d_model=64, n_heads=4,
                          n_kv_heads=2, d_ff=128, vocab=211,
                          dtype=jnp.float32, param_dtype=jnp.float32,
                          remat="none", q_block=8)
        tcfg = TrainConfig(lr=1e-3, warmup=0, total_steps=8, seq_len=32,
                           global_batch=8, donate=False)
        mesh8 = make_mesh((4, 2), ("data", "model"))
        mesh4 = make_mesh((2, 2), ("data", "model"))
        tr = Trainer(cfg, tcfg, mesh=mesh8)
        tr._run_until(2)
        before = np.concatenate([np.asarray(x).ravel()
                                 for x in jax.tree.leaves(tr.params)])
        # simulate losing half the data-parallel hosts
        tr.remesh(mesh4)
        after = np.concatenate([np.asarray(x).ravel()
                                for x in jax.tree.leaves(tr.params)])
        np.testing.assert_array_equal(before, after)
        tr._run_until(4)   # keeps training on the shrunken mesh
        assert tr.step_count == 4
        print("ok")
        """)


def test_seq_sharded_decode_paths():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import ModelConfig
        from repro.models import (init_model, init_cache, prefill,
                                  decode_step)
        from repro.parallel.sharding import use_mesh, param_shardings
        from repro.compat import make_mesh
        from repro.launch.steps import cache_dims, decode_rules
        mesh = make_mesh((2, 4), ("data", "model"))
        f32 = dict(dtype=jnp.float32, param_dtype=jnp.float32, q_block=8)
        cfg = ModelConfig(name="g", n_layers=2, d_model=64, n_heads=6,
                          n_kv_heads=2, d_ff=128, vocab=97, **f32)
        params, dims = init_model(jax.random.PRNGKey(0), cfg)
        B, S, SMAX = 4, 16, 32
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, 97)
        caches = init_cache(cfg, B, SMAX)
        lg, caches = prefill(cfg, params, toks, caches)
        nxt = jnp.argmax(lg[:, -1], -1)[:, None]
        ref, _ = decode_step(cfg, params, nxt, caches, jnp.int32(S))
        rules = decode_rules(cfg, mesh)
        with use_mesh(mesh, rules):
            ps = param_shardings(dims, params, mesh)
            cproto = jax.eval_shape(lambda: init_cache(cfg, B, SMAX))
            cs = param_shardings(cache_dims(cfg, cproto), cproto, mesh)
            step = jax.jit(lambda p, t, c, l: decode_step(cfg, p, t, c, l),
                           in_shardings=(ps, NamedSharding(mesh, P("data", None)),
                                         cs, NamedSharding(mesh, P())),
                           out_shardings=(None, cs))
            got, _ = step(jax.device_put(params, ps),
                          jax.device_put(nxt, NamedSharding(mesh, P("data", None))),
                          jax.device_put(caches, cs), jnp.int32(S))
        err = float(np.abs(np.asarray(got) - np.asarray(ref)).max())
        assert err < 1e-4, err
        print("ok", err)
        """)


def test_resident_expert_decode_matches_oracle():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import ModelConfig
        from repro.models import init_model, init_cache, prefill, decode_step
        from repro.parallel.sharding import use_mesh, param_shardings
        from repro.compat import make_mesh
        from repro.launch.steps import cache_dims, decode_rules
        mesh = make_mesh((2, 4), ("data", "model"))
        f32 = dict(dtype=jnp.float32, param_dtype=jnp.float32, q_block=8)
        cfg = ModelConfig(name="m", family="moe", n_layers=2, d_model=64,
                          n_heads=4, n_kv_heads=4, d_ff=128, vocab=97,
                          n_experts=8, n_experts_per_tok=2, moe_d_ff=96,
                          moe_backend="lcx", capacity_factor=8.0,
                          n_shared_experts=1, **f32)
        params, dims = init_model(jax.random.PRNGKey(0), cfg)
        B, S, SMAX = 4, 16, 32
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, 97)
        ref_cfg = dataclasses.replace(cfg, moe_backend="sort")
        caches = init_cache(cfg, B, SMAX)
        lg, caches2 = prefill(ref_cfg, params, toks, caches)
        nxt = jnp.argmax(lg[:, -1], -1)[:, None]
        ref, _ = decode_step(ref_cfg, params, nxt, caches2, jnp.int32(S))
        rules = decode_rules(cfg, mesh)
        assert set(rules.get("experts", ())) == {"data", "model"}, rules
        with use_mesh(mesh, rules):
            psh = param_shardings(dims, params, mesh)
            cproto = jax.eval_shape(lambda: init_cache(cfg, B, SMAX))
            csh = param_shardings(cache_dims(cfg, cproto), cproto, mesh)
            step = jax.jit(lambda p, t, c, l: decode_step(cfg, p, t, c, l),
                           in_shardings=(psh, NamedSharding(mesh, P("data", None)),
                                         csh, NamedSharding(mesh, P())),
                           out_shardings=(None, csh))
            got, _ = step(jax.device_put(params, psh),
                          jax.device_put(nxt, NamedSharding(mesh, P("data", None))),
                          jax.device_put(caches2, csh), jnp.int32(S))
        err = float(np.abs(np.asarray(got) - np.asarray(ref)).max())
        assert err < 1e-4, err
        print("ok", err)
        """)


def test_pipeline_parallel_forward_and_grads():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import ModelConfig
        from repro.models import init_model, apply_model, loss_fn
        from repro.parallel.pp import pp_apply_model, pp_loss
        from repro.parallel.sharding import use_mesh
        from repro.compat import make_mesh
        mesh = make_mesh((4, 2), ("pipe", "data"))
        cfg = ModelConfig(name="pp", n_layers=8, d_model=64, n_heads=4,
                          n_kv_heads=2, d_ff=128, vocab=97,
                          dtype=jnp.float32, param_dtype=jnp.float32,
                          q_block=8, remat="none")
        params, _ = init_model(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 97)
        batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
        ref, _ = apply_model(cfg, params, toks)
        ref_grads = jax.grad(lambda p: loss_fn(cfg, p, batch)[0])(params)
        with use_mesh(mesh):
            out = jax.jit(lambda p, t: pp_apply_model(
                cfg, p, t, mesh=mesh, n_micro=2))(params, toks)
            pg = jax.jit(jax.grad(lambda p: pp_loss(
                cfg, p, batch, mesh=mesh, n_micro=2)))(params)
        assert float(jnp.abs(out - ref).max()) < 1e-4
        ge = max(float(jnp.abs(a - b).max()) for a, b in
                 zip(jax.tree.leaves(pg), jax.tree.leaves(ref_grads)))
        assert ge < 1e-4, ge
        print("ok", ge)
        """)
