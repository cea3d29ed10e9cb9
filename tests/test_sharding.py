"""Multi-device sharding correctness.

The main process is pinned to 1 CPU device (smoke tests must see 1 device),
so these tests spawn subprocesses with XLA_FLAGS=--xla_force_host_platform_
device_count=8 — the same mechanism dryrun.py uses for 512.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_train_step_matches_single_device():
    """Same loss + params on a 2x4 mesh as unsharded single-device."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config, reduced
        from repro.launch.mesh import make_debug_mesh
        from repro.parallel import context as pctx
        from repro.parallel.sharding import (partition_params, partition_opt,
                                             to_named)
        from repro.train.train_step import (TrainConfig, init_train_state,
                                            make_train_step)
        from repro.data.synthetic import lm_batch

        cfg = dataclasses.replace(reduced(get_config('qwen3-4b'), periods=1),
                                  dtype=jnp.float32)
        tc = TrainConfig(lr=1e-3)
        tok, lab = lm_batch(0, batch=8, seq=32, vocab=cfg.vocab_size, seed=0)
        tok, lab = jnp.asarray(tok), jnp.asarray(lab)

        # single-device reference
        s0 = init_train_state(cfg, tc, jax.random.PRNGKey(0))
        ref_state, ref_metrics = jax.jit(make_train_step(cfg, tc))(s0, tok, lab)

        # sharded
        mesh = make_debug_mesh(2, 4)
        with pctx.mesh_context(mesh, ('data',), 'model'):
            with mesh:
                pspecs = partition_params(cfg, mesh, fsdp=True)
                sshapes = jax.eval_shape(
                    lambda: init_train_state(cfg, tc, jax.random.PRNGKey(0)))
                sspecs = {'params': pspecs,
                          'opt': partition_opt(pspecs, sshapes['opt']),
                          'step': P()}
                in_sh = (to_named(mesh, sspecs),
                         NamedSharding(mesh, P('data', None)),
                         NamedSharding(mesh, P('data', None)))
                fn = jax.jit(make_train_step(cfg, tc,
                                             to_named(mesh, pspecs)),
                             in_shardings=in_sh)
                s0b = init_train_state(cfg, tc, jax.random.PRNGKey(0))
                st, metrics = fn(s0b, tok, lab)

        np.testing.assert_allclose(float(ref_metrics['loss']),
                                   float(metrics['loss']), rtol=2e-5)
        # params pass through AdamW's rsqrt at step 1, which amplifies
        # reduction-order noise; loss equality above is the tight check
        for a, b in zip(jax.tree.leaves(ref_state['params']),
                        jax.tree.leaves(st['params'])):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=1e-2, atol=1e-4)
        print('SHARDED_OK')
    """)
    assert "SHARDED_OK" in out


def test_moe_expert_parallel_matches():
    """Expert-parallel MoE forward == single-device forward."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config, reduced
        from repro.launch.mesh import make_debug_mesh
        from repro.parallel import context as pctx
        from repro.models.moe import init_moe, moe_forward

        cfg = dataclasses.replace(
            reduced(get_config('granite-moe-1b-a400m'), periods=1),
            dtype=jnp.float32, num_experts=8, top_k=2)
        params = init_moe(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))

        ref = moe_forward(params, cfg, x, capacity_factor=8.0)

        mesh = make_debug_mesh(2, 4)
        with pctx.mesh_context(mesh, ('data',), 'model'):
            with mesh:
                fn = jax.jit(lambda p, x: moe_forward(p, cfg, x,
                                                      capacity_factor=8.0),
                             in_shardings=(None,
                                           NamedSharding(mesh, P('data',
                                                                 None, None))))
                got = fn(params, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=3e-4, atol=3e-5)
        print('MOE_EP_OK')
    """)
    assert "MOE_EP_OK" in out


def test_dryrun_cell_small_mesh():
    """dryrun build_cell lowers+compiles on an 8-device mesh in-process."""
    out = run_py("""
        import jax
        from repro.configs import get_config, reduced
        from repro.configs.base import ShapeConfig
        from repro.launch.mesh import make_debug_mesh
        from repro.parallel import context as pctx
        import repro.launch.dryrun as dr

        cfg = reduced(get_config('granite-moe-1b-a400m'), periods=1)
        shape = ShapeConfig('t', 64, 8, 'train')
        mesh = make_debug_mesh(2, 4)
        with pctx.mesh_context(mesh, ('data',), 'model'):
            with mesh:
                fn, args = dr.build_cell(cfg, shape, mesh)
                compiled = fn.lower(*args).compile()
        assert compiled.cost_analysis() is not None
        print('DRYRUN_OK')
    """)
    assert "DRYRUN_OK" in out


def test_compressed_psum_multidevice():
    """int8 error-feedback psum across a real 8-way DP axis."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax.experimental.shard_map import shard_map
        from repro.optim.compression import compressed_psum, ef_init

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ('dp',))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 128))
        ef = jnp.zeros((8, 128))

        def f(g, e):
            out, new_e = compressed_psum({'w': g[0]}, {'w': e[0]}, 'dp')
            return out['w'][None], new_e['w'][None]

        out, _ = shard_map(f, mesh=mesh, in_specs=(P('dp'), P('dp')),
                           out_specs=(P('dp'), P('dp')))(g, ef)
        want = jnp.mean(g, axis=0)          # exact mean all-reduce
        got = out[0]                        # every shard holds the mean
        rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        assert rel < 0.05, rel
        print('PSUM_OK')
    """)
    assert "PSUM_OK" in out
