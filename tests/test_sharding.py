"""Sharding rules + elastic resharding. Multi-device cases run in a
subprocess with a forced 8-device host platform (the device count must be
set before jax initializes, so it cannot run in the main pytest process)."""
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.models import build
from repro.sharding import param_specs
from repro.launch.mesh import elastic_mesh_shape


def _run_subprocess(body: str):
    code = "import os\nos.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'\n" + \
        textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-W", "ignore", "-c", code],
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_param_specs_divisible_everywhere():
    """Every spec must divide its dim by the mesh axis size — for all archs
    (this is what jax enforces at jit time on the production mesh)."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))  # structure-only mesh

    class Fake:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    for arch in ("llama3.2-1b", "qwen3-14b", "gemma-2b", "grok-1-314b",
                 "deepseek-v2-lite-16b", "granite-3-8b", "internvl2-26b",
                 "recurrentgemma-9b", "xlstm-125m", "musicgen-large"):
        cfg = get_config(arch)
        model = build(cfg)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        specs = param_specs(cfg, params, Fake())
        leaves = jax.tree_util.tree_flatten_with_path(params)[0]
        spec_leaves = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
        assert len(leaves) == len(spec_leaves)
        for (path, leaf), spec in zip(leaves, spec_leaves):
            for dim, entry in zip(leaf.shape, tuple(spec)):
                if entry is None:
                    continue
                axes = entry if isinstance(entry, tuple) else (entry,)
                size = int(np.prod([Fake.shape[a] for a in axes]))
                assert dim % size == 0, (arch, path, leaf.shape, spec)


def test_param_sharding_covers_big_tensors():
    """No >=2-D weight tensor may be fully replicated on the production mesh
    (param memory at 314B depends on it) — norms/scalars excepted."""
    class Fake:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    for arch in ("grok-1-314b", "qwen3-14b", "deepseek-v2-lite-16b"):
        cfg = get_config(arch)
        model = build(cfg)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        specs = param_specs(cfg, params, Fake())
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        spec_leaves = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
        for (path, leaf), spec in zip(flat, spec_leaves):
            n = int(np.prod(leaf.shape))
            if n >= 1_000_000:   # every big tensor must shard somewhere
                assert any(e is not None for e in tuple(spec)), (arch, path, spec)


def test_elastic_mesh_planner():
    assert elastic_mesh_shape(256) == (16, 16)
    assert elastic_mesh_shape(240) == (15, 16)   # one host of 16 lost
    assert elastic_mesh_shape(192) == (12, 16)
    assert elastic_mesh_shape(8, prefer_model=16) == (1, 8)
    assert elastic_mesh_shape(7) == (1, 7)


@pytest.mark.slow
def test_sharded_train_step_runs_on_8_devices():
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.launch.mesh import make_mesh
        from repro.models import build
        from repro.optim import AdamWConfig
        from repro.sharding import batch_specs, state_specs, to_named
        from repro.train import init_state, make_train_step

        cfg = get_config("llama3.2-1b").reduced()
        model = build(cfg)
        mesh = make_mesh((4, 2), ("data", "model"))
        opt = AdamWConfig(lr=1e-3)
        state = init_state(model, jax.random.PRNGKey(0), opt)
        st = to_named(mesh, state_specs(cfg, state, mesh))
        state = jax.device_put(state, st)
        batch = {"tokens": jnp.zeros((8, 16), jnp.int32),
                 "targets": jnp.zeros((8, 16), jnp.int32)}
        bs = to_named(mesh, batch_specs(cfg, batch, mesh))
        batch = jax.device_put(batch, bs)
        step = jax.jit(make_train_step(model, opt), in_shardings=(st, bs),
                       out_shardings=(st, None), donate_argnums=(0,))
        with mesh:
            state, m = step(state, batch)
        assert np.isfinite(float(m["loss"]))
        print("OK", float(m["loss"]))
    """)
    assert "OK" in out


@pytest.mark.slow
def test_elastic_restore_across_meshes():
    """Save on a 4x2 mesh, restore onto 2x4 and 8x1 — bit-identical params."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from repro.checkpoint import CheckpointManager
        from repro.configs import get_config
        from repro.models import build
        from repro.optim import AdamWConfig
        from repro.runtime import restore_on_mesh
        from repro.sharding import state_specs, to_named
        from repro.train import init_state

        cfg = get_config("llama3.2-1b").reduced()
        model = build(cfg)
        opt = AdamWConfig()
        state = init_state(model, jax.random.PRNGKey(3), opt)

        mesh_a = jax.make_mesh((4, 2), ("data", "model"))
        state_a = jax.device_put(state, to_named(mesh_a, state_specs(cfg, state, mesh_a)))
        d = tempfile.mkdtemp()
        ckpt = CheckpointManager(d)
        ckpt.save(7, state_a, blocking=True)

        for shape in ((2, 4), (8, 1)):
            mesh_b = jax.make_mesh(shape, ("data", "model"))
            abstract = jax.tree.map(np.zeros_like, state)
            restored = restore_on_mesh(ckpt, 7, abstract, cfg, mesh_b)
            for x, y in zip(jax.tree.leaves(state_a), jax.tree.leaves(restored)):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_moe_ep_matches_reference():
    """shard_map expert parallelism == single-device MoE in the no-drop
    regime (8 devices, experts sharded 4-way, one psum per layer)."""
    out = _run_subprocess("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models import moe as moe_lib
        from repro.models.moe_ep import moe_apply_ep

        cfg = get_config("grok-1-314b").reduced()
        cfg = dataclasses.replace(
            cfg, d_model=64,
            moe=dataclasses.replace(cfg.moe, n_experts=8, top_k=2,
                                    d_ff_expert=32, n_shared=0,
                                    capacity_factor=8.0))
        p = moe_lib.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64))

        y_ref, _aux = moe_lib.moe_apply(p, cfg, x)

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        with jax.set_mesh(mesh) if hasattr(jax, "set_mesh") else mesh:
            y_ep = jax.jit(lambda p_, x_: moe_apply_ep(p_, cfg, x_, mesh))(p, x)
        err = float(jnp.max(jnp.abs(y_ref - y_ep)))
        assert err < 2e-5, err
        print("OK", err)
    """)
    assert "OK" in out
