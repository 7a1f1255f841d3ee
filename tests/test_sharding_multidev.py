"""Sharded lowering + collectives on a multi-device host platform.

These tests need >1 XLA host device, which must be configured BEFORE jax
initializes — so they run in a subprocess with XLA_FLAGS set.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(body: str, n_dev: int = 8) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={n_dev}")
        import sys
        sys.path.insert(0, {SRC!r})
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


def test_tiny_train_step_compiles_and_runs_on_2x2_mesh():
    out = _run("""
        import jax, jax.numpy as jnp, dataclasses
        from repro.configs import get_tiny_config
        from repro.config import RunConfig, ShapeConfig, OptimConfig, ShardingConfig
        from repro.data.batches import make_batch
        from repro.launch.mesh import make_test_mesh
        from repro.models import init_params, param_axes
        from repro.optim import state_axes
        from repro.parallel.context import sharding_ctx
        from repro.parallel.sharding import make_ctx, tree_shardings, batch_shardings
        from repro.train.step import make_train_step, make_opt_state

        cfg = get_tiny_config('qwen3-8b').replace(remat='full')
        run = RunConfig(model=cfg, shape=ShapeConfig('t','train',16,4),
                        sharding=ShardingConfig(policy='fsdp'))
        mesh = make_test_mesh(2, 2)
        ctx = make_ctx(mesh, run.sharding)
        p = init_params(cfg, jax.random.PRNGKey(0))
        opt = make_opt_state(run, p)
        batch = make_batch(cfg, 4, 16)
        p_sh = tree_shardings(ctx, param_axes(cfg))
        o_sh = tree_shardings(ctx, state_axes(param_axes(cfg), run.optim))
        b_sh = batch_shardings(ctx, batch)
        p = jax.device_put(p, p_sh)
        opt = jax.device_put(opt, o_sh)
        batch = jax.device_put(batch, b_sh)
        with sharding_ctx(ctx):
            step = jax.jit(make_train_step(run),
                           in_shardings=(p_sh, o_sh, b_sh),
                           out_shardings=(p_sh, o_sh, None))
            p2, opt2, metrics = step(p, opt, batch)
        loss = float(metrics['loss'])
        assert loss == loss and loss > 0, loss
        print('SHARDED_OK', loss)
    """)
    assert "SHARDED_OK" in out


def test_sharded_loss_matches_single_device():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.configs import get_tiny_config
        from repro.config import RunConfig, ShapeConfig, ShardingConfig
        from repro.data.batches import make_batch
        from repro.launch.mesh import make_test_mesh
        from repro.models import init_params, param_axes, loss_fn
        from repro.parallel.context import sharding_ctx
        from repro.parallel.sharding import make_ctx, tree_shardings, batch_shardings

        cfg = get_tiny_config('qwen3-moe-30b-a3b')
        p = init_params(cfg, jax.random.PRNGKey(0))
        batch = make_batch(cfg, 4, 16)
        l0, _ = jax.jit(lambda p, b: loss_fn(cfg, p, b))(p, batch)

        run = RunConfig(model=cfg, shape=ShapeConfig('t','train',16,4),
                        sharding=ShardingConfig(policy='fsdp'))
        mesh = make_test_mesh(2, 2)
        ctx = make_ctx(mesh, run.sharding)
        p_sh = tree_shardings(ctx, param_axes(cfg))
        b_sh = batch_shardings(ctx, batch)
        with sharding_ctx(ctx):
            l1, _ = jax.jit(lambda p, b: loss_fn(cfg, p, b),
                            in_shardings=(p_sh, b_sh))(
                jax.device_put(p, p_sh), jax.device_put(batch, b_sh))
        err = abs(float(l0) - float(l1))
        assert err < 2e-2, (float(l0), float(l1))
        print('MATCH_OK', err)
    """)
    assert "MATCH_OK" in out


def test_multipod_mesh_axes_and_decode_lowering():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.configs import get_tiny_config
        from repro.config import RunConfig, ShapeConfig, ShardingConfig
        from repro.launch.mesh import make_test_mesh
        from repro.models import (init_params, param_axes, init_cache,
                                  cache_logical_axes, decode_step)
        from repro.parallel.context import sharding_ctx
        from repro.parallel.sharding import make_ctx, tree_shardings

        cfg = get_tiny_config('qwen3-8b').replace(param_dtype='bfloat16')
        mesh = make_test_mesh(2, 2, pods=2)
        assert mesh.axis_names == ('pod', 'data', 'model')
        ctx = make_ctx(mesh, ShardingConfig(policy='baseline'), decode=True)
        p = init_params(cfg, jax.random.PRNGKey(0))
        cache = init_cache(cfg, 4, 32)
        p_sh = tree_shardings(ctx, param_axes(cfg))
        c_sh = tree_shardings(ctx, cache_logical_axes(cfg))
        tok_sh = ctx.sharding(('batch', None))
        with sharding_ctx(ctx):
            fn = jax.jit(lambda p, t, c: decode_step(cfg, p, t, c),
                         in_shardings=(p_sh, tok_sh, c_sh),
                         out_shardings=(None, c_sh))
            lowered = fn.lower(
                jax.device_put(p, p_sh),
                jax.device_put(jnp.zeros((4,1), jnp.int32), tok_sh),
                jax.device_put(cache, c_sh))
            compiled = lowered.compile()
        print('DECODE_LOWER_OK', compiled.memory_analysis() is not None)
    """)
    assert "DECODE_LOWER_OK" in out


def test_hierarchical_psum_and_compressed_psum():
    out = _run("""
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_test_mesh
        from repro.parallel.collectives import hierarchical_psum, compressed_psum

        mesh = make_test_mesh(2, 2, pods=2)
        x = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16)

        def f(xs):
            return hierarchical_psum(xs, 'pod', 'data')

        y = shard_map(f, mesh=mesh, in_specs=P(('pod','data'), None),
                      out_specs=P(('pod','data'), None))(x)
        # psum over pod+data of each shard: every (pod,data) shard sums
        expect = jnp.tile(x.reshape(4, 2, 16).sum(0), (4, 1))
        np.testing.assert_allclose(np.asarray(y), np.asarray(expect),
                                   rtol=1e-6)

        def g(xs):
            return compressed_psum(xs, 'data')

        z = shard_map(g, mesh=mesh, in_specs=P(('pod','data'), None),
                      out_specs=P(('pod','data'), None))(x)
        assert z.shape == x.shape
        print('COLLECTIVES_OK')
    """)
    assert "COLLECTIVES_OK" in out
