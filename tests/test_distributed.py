"""Multi-device tests (subprocesses with forced host device counts) +
single-process sharding-rule tests."""

import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.configs import registry
from repro.distributed import sharding as shd
from repro.models import lm

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, devices: int = 8, timeout: int = 480) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=timeout,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# -- sharding rules (no mesh needed beyond fake shapes) ------------------------


@pytest.mark.parametrize("arch", registry.ARCH_NAMES)
def test_param_pspecs_cover_tree(arch):
    """Every leaf gets a spec and specs never reuse a mesh axis."""
    cfg = registry.get_smoke(arch)
    profile = registry.get_sharding(arch)
    params = lm.abstract_params(registry.get(arch))

    class FakeMesh:
        axis_names = ("pod", "data", "model")

        class devices:
            shape = (2, 16, 16)

    specs = shd.param_pspecs(params, profile, FakeMesh)
    n_sharded = 0
    for spec, leaf in zip(jax.tree.leaves(specs), jax.tree.leaves(params)):
        seen = []
        for i, entry in enumerate(spec):
            if entry is None:
                continue
            axes = (entry,) if isinstance(entry, str) else entry
            ext = 1
            for a in axes:
                assert a not in seen, (arch, spec)
                seen.append(a)
                ext *= dict(zip(("pod", "data", "model"), (2, 16, 16)))[a]
            assert leaf.shape[i] % ext == 0, (arch, spec, leaf.shape)
            n_sharded += 1
    assert n_sharded > 0, arch  # something must actually shard


def test_big_matrices_are_sharded():
    cfg = registry.get("kimi-k2-1t-a32b")
    profile = registry.get_sharding("kimi-k2-1t-a32b")
    params = lm.abstract_params(cfg)

    class FakeMesh:
        axis_names = ("pod", "data", "model")

        class devices:
            shape = (2, 16, 16)

    specs = shd.param_pspecs(params, profile, FakeMesh)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    leaves = dict()
    for path, spec in flat:
        key = "/".join(str(getattr(p, "key", p)) for p in path)
        leaves[key] = spec
    # expert weights must shard over EP axis
    moe_spec = [s for k, s in leaves.items() if "moe" in k and "w_up" in k][0]
    assert "model" in str(moe_spec)
    assert any("data" in str(s) for s in leaves.values())  # FSDP present


# -- multi-device subprocess tests ----------------------------------------------
#
# Each runs in a child process with virtual CPU devices (XLA_FLAGS must be
# set before jax starts). Meshes come from repro.launch.mesh (Auto axes);
# set_mesh and shard_map are jax's own.


def test_ep_moe_matches_oracle_on_mesh():
    run_sub(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import ModelConfig, MoEConfig
        from repro.launch.mesh import make_mesh
        from repro.models import moe
        cfg = ModelConfig(name='t', family='moe', num_layers=1, d_model=64,
                          num_heads=4, num_kv_heads=2, head_dim=16, d_ff=32,
                          vocab_size=128,
                          moe=MoEConfig(num_experts=8, experts_per_token=2,
                                        d_ff_expert=32, capacity_factor=8.0,
                                        mode='ep'),
                          param_dtype='float32', dtype='float32')
        p = moe.moe_init(cfg, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64), jnp.float32)
        y_ref, _ = moe.moe_forward_grouped(cfg, p, x)
        mesh = make_mesh((2, 4), ('data', 'model'))
        with jax.set_mesh(mesh):
            y, _ = jax.jit(lambda p, x: moe.moe_forward_ep(
                cfg, p, x, mesh=mesh, ep_axis='model', dp_axes=('data',)))(p, x)
        err = float(jnp.abs(y - y_ref).max())
        assert err < 1e-5, err
        print('OK', err)
        """
    )


def test_pipeline_parallel_fwd_bwd():
    run_sub(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.distributed.pipeline import pipeline_apply, sequential_reference
        mesh = make_mesh((4,), ('pipe',))
        L, D, B = 8, 16, 8
        ws = jax.random.normal(jax.random.PRNGKey(0), (L, D, D)) * 0.1
        layer_fn = lambda w, h: jnp.tanh(h @ w) + h
        x = jax.random.normal(jax.random.PRNGKey(1), (B, D))
        with jax.set_mesh(mesh):
            y = jax.jit(lambda ws, x: pipeline_apply(
                layer_fn, ws, x, mesh=mesh, axis='pipe', n_microbatches=4))(ws, x)
            g = jax.jit(jax.grad(lambda ws: jnp.sum(pipeline_apply(
                layer_fn, ws, x, mesh=mesh, axis='pipe',
                n_microbatches=4)**2)))(ws)
        ref = sequential_reference(layer_fn, ws, x)
        gref = jax.grad(lambda ws: jnp.sum(
            sequential_reference(layer_fn, ws, x)**2))(ws)
        assert float(jnp.abs(y - ref).max()) < 1e-5
        assert float(jnp.abs(g - gref).max()) < 1e-3
        print('OK')
        """,
        devices=4,
    )


def test_sharded_train_step_runs_and_matches_single():
    """Tiny model: sharded (2x4 mesh) train step == single-device step."""
    run_sub(
        """
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import registry
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_mesh
        from repro.models import lm
        from repro.training.optimizer import AdamWConfig, adamw_init
        from repro.training.train_step import make_train_step
        cfg = dataclasses.replace(registry.get_smoke('olmo-1b'),
                                  dtype='float32', param_dtype='float32')
        profile = registry.get_sharding('olmo-1b')
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        opt_cfg = AdamWConfig(lr=1e-3)
        opt = adamw_init(params, opt_cfg)
        rng = np.random.RandomState(0)
        toks = rng.randint(10, 400, size=(8, 17))
        batch = {'tokens': jnp.asarray(toks[:, :-1], jnp.int32),
                 'labels': jnp.asarray(toks[:, 1:], jnp.int32)}
        # single device reference
        p1, o1, m1 = jax.jit(make_train_step(cfg, opt_cfg))(params, opt, batch)
        # sharded
        mesh = make_mesh((2, 4), ('data', 'model'))
        ctx = lm.ParallelCtx(mesh=mesh, dp_axes=('data',))
        psh = shd.to_shardings(shd.param_pspecs(params, profile, mesh), mesh)
        bsh = shd.to_shardings(shd.batch_pspecs(batch, mesh), mesh)
        osh = {'m': psh, 'v': psh,
               'step': shd.to_shardings(jax.sharding.PartitionSpec(), mesh)}
        with jax.set_mesh(mesh):
            step = jax.jit(make_train_step(cfg, opt_cfg, ctx),
                           in_shardings=(psh, osh, bsh))
            p2, o2, m2 = step(params, opt, batch)
        d = float(abs(float(m1['loss']) - float(m2['loss'])))
        assert d < 1e-4, d
        dp = max(float(jnp.abs(a - b).max())
                 for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
        assert dp < 1e-4, dp
        print('OK', d, dp)
        """
    )


def test_elastic_reshard_preserves_values():
    run_sub(
        """
        import jax, jax.numpy as jnp
        from repro.configs import registry
        from repro.distributed.elastic import reshard_tree
        from repro.launch.mesh import make_mesh
        from repro.models import lm
        cfg = registry.get_smoke('qwen2.5-3b')
        profile = registry.get_sharding('qwen2.5-3b')
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        mesh8 = make_mesh((2, 4), ('data', 'model'))
        mesh4 = make_mesh((1, 4), ('data', 'model'))
        p8 = reshard_tree(params, mesh8, profile)
        p4 = reshard_tree(p8, mesh4, profile)  # "node loss": shrink mesh
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p4)):
            assert float(jnp.abs(a.astype(jnp.float32) -
                                 b.astype(jnp.float32)).max()) == 0.0
        print('OK')
        """
    )


def test_compressed_allreduce_on_mesh():
    run_sub(
        """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.training.grad_compress import compressed_allreduce, ef_state_init
        mesh = make_mesh((8,), ('data',))
        grads = {'w': jnp.arange(8*512, dtype=jnp.float32).reshape(8, 512) / 100}
        ef = ef_state_init({'w': grads['w'][0]})
        def f(g, ef):
            return compressed_allreduce({'w': g}, ef, 'data')
        fn = jax.shard_map(f, mesh=mesh, in_specs=(P('data', None), P()),
                       out_specs=(P(), P()), check_vma=False)
        with jax.set_mesh(mesh):
            out, new_ef = fn(grads['w'], ef)
        ref = np.asarray(grads['w']).mean(0)
        err = float(np.abs(np.asarray(out['w']) - ref).max())
        rel = err / (abs(ref).max() + 1e-9)
        assert rel < 0.02, rel  # int8 quantization error bound
        print('OK', rel)
        """
    )
