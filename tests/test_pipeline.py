"""Pipeline parallelism + sharding rules on a multi-device host mesh.

These run in subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count
because the parent pytest process has already locked jax to 1 CPU device.
"""
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


def _run(code: str, devices: int = 8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=420, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_gpipe_matches_sequential():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed.pipeline import pipeline_apply, stack_params_for_stages
    mesh = jax.make_mesh((4,), ("stage",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    L, d = 8, 16
    rng = np.random.RandomState(0)
    Ws = jnp.asarray(rng.randn(L, d, d) * 0.2, jnp.float32)

    def layer(w, x):
        return jnp.tanh(x @ w)

    def stage_fn(stage_params, x):
        def body(x, w):
            return layer(w, x), None
        x, _ = jax.lax.scan(body, x, stage_params)
        return x

    x = jnp.asarray(rng.randn(8, d), jnp.float32)
    # sequential reference
    ref = x
    for l in range(L):
        ref = layer(Ws[l], ref)
    staged = stack_params_for_stages({"w": Ws}, 4)["w"]
    y = pipeline_apply(stage_fn, staged, x, n_micro=4, mesh=mesh,
                       axis="stage")
    err = float(jnp.abs(y - ref).max())
    assert err < 1e-5, err
    print("gpipe ok", err)
    """)


def test_sharding_rules_lower_small_mesh():
    """Sharded train_step lowers+compiles on a host 2x4 mesh (reduced cfg)."""
    _run("""
    import jax, jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.distributed import sharding as shd
    from repro.models.registry import build
    from repro.optim.optimizers import AdamW
    from repro.train import steps as steps_lib
    from repro.configs.base import ShapeConfig

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    for arch in ["qwen3-14b", "mixtral-8x7b", "mamba2-1.3b"]:
        cfg = get_smoke_config(arch)
        model = build(cfg)
        with mesh:
            opt = AdamW(lr=1e-3)
            state_abs = steps_lib.abstract_train_state(model, opt)
            pspecs = shd.params_pspecs(state_abs.params, cfg, mesh)
            state_pspecs = steps_lib.TrainState(
                params=pspecs,
                opt=shd.opt_state_pspecs(state_abs.opt, pspecs),
                rng=jax.sharding.PartitionSpec())
            state_shard = shd.sanitized_shardings(state_pspecs, state_abs, mesh)
            shape = ShapeConfig("t", 32, 4, "train")
            batch_abs = model.batch_specs(shape)
            b_shard = shd.sanitized_shardings(
                shd.batch_pspecs(batch_abs, mesh), batch_abs, mesh)
            step = steps_lib.make_train_step(model, opt)
            compiled = jax.jit(step, in_shardings=(state_shard, b_shard),
                               out_shardings=(state_shard, None),
                               donate_argnums=(0,)).lower(
                                   state_abs, batch_abs).compile()
            assert compiled.cost_analysis() is not None
        print(arch, "compiled ok")
    """, devices=8)


def test_sharded_train_step_executes():
    """Not just compiles: run 3 real sharded steps, loss finite+decreasing."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke_config
    from repro.distributed import sharding as shd
    from repro.models.registry import build
    from repro.optim.optimizers import AdamW
    from repro.train import steps as steps_lib
    from repro.data import SyntheticTokenPipeline, TokenPipelineConfig

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = get_smoke_config("qwen3-14b")
    model = build(cfg)
    opt = AdamW(lr=5e-3)
    with mesh:
        state = steps_lib.init_train_state(model, opt, jax.random.PRNGKey(0))
        pspecs = shd.params_pspecs(state.params, cfg, mesh)
        state_pspecs = steps_lib.TrainState(
            params=pspecs, opt=shd.opt_state_pspecs(state.opt, pspecs),
            rng=jax.sharding.PartitionSpec())
        state_shard = shd.sanitized_shardings(state_pspecs, state, mesh)
        state = jax.device_put(state, state_shard)
        pipe = SyntheticTokenPipeline(TokenPipelineConfig(
            vocab_size=cfg.vocab_size, seq_len=32, global_batch=4))
        step_fn = jax.jit(steps_lib.make_train_step(model, opt),
                          donate_argnums=(0,))
        losses = []
        for i in range(6):
            b = pipe.batch(i)
            batch = {k: jnp.asarray(v) for k, v in b.items()}
            state, metrics = step_fn(state, batch)
            losses.append(float(metrics["loss"]))
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0] + 0.1, losses
        print("sharded exec ok", losses[0], "->", losses[-1])
    """, devices=8)
