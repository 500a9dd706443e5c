"""MoE grouped dispatch: routing semantics, capacity, shard-local grouping."""
import jax
from jax.sharding import AxisType
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models.moe import init_moe, moe_block


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("mixtral-8x7b")
    cfg = cfg.__class__(**{**cfg.__dict__, "compute_dtype": "float32"})
    p = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 16, cfg.d_model),
                    jnp.float32)
    return cfg, p, x


def test_lossless_capacity_matches_dense_reference(setup):
    """At capacity == T the grouped dispatch equals the explicit per-token
    dense mixture."""
    cfg, p, x = setup
    y, _ = moe_block(p, cfg, x,
                     capacity_factor=cfg.n_experts / cfg.n_experts_active)
    # dense reference
    T = x.shape[0] * x.shape[1]
    xt = x.reshape(T, -1)
    logits = xt @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gv, ei = jax.lax.top_k(probs, cfg.n_experts_active)
    gv = gv / gv.sum(-1, keepdims=True)
    ref = jnp.zeros_like(xt)
    for e in range(cfg.n_experts):
        h = jax.nn.silu(xt @ p["w_gate"][e]) * (xt @ p["w_up"][e])
        ye = h @ p["w_down"][e]
        w = jnp.sum(jnp.where(ei == e, gv, 0.0), axis=-1)
        ref = ref + ye * w[:, None]
    np.testing.assert_allclose(np.asarray(y.reshape(T, -1)), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_batch_consistency(setup):
    """Full batch == per-token application under lossless capacity."""
    cfg, p, x = setup
    cf = cfg.n_experts / cfg.n_experts_active
    full, _ = moe_block(p, cfg, x, capacity_factor=cf)
    per = jnp.concatenate(
        [moe_block(p, cfg, x[:, t:t + 1], capacity_factor=cf)[0]
         for t in range(x.shape[1])], axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(per),
                               rtol=1e-5, atol=1e-5)


def test_capacity_drops_tokens(setup):
    """Tight capacity changes outputs (GShard dropping) but stays finite."""
    cfg, p, x = setup
    tight, _ = moe_block(p, cfg, x, capacity_factor=0.25)
    loose, _ = moe_block(p, cfg, x, capacity_factor=8.0)
    assert bool(jnp.all(jnp.isfinite(tight)))
    assert float(jnp.abs(tight - loose).max()) > 0


def test_aux_loss_balanced_router(setup):
    """A uniform router gives aux ~ 1 (the balanced optimum of E*sum(f*p))."""
    cfg, p, x = setup
    p_bal = dict(p)
    p_bal["router"] = jnp.zeros_like(p["router"])
    _, aux = moe_block(p_bal, cfg, x)
    assert abs(float(aux) - 1.0) < 0.05


def test_shard_local_grouping_matches_global():
    """The data-shard-local dispatch (§Perf hillclimb 2) is numerically
    identical to single-shard dispatch under lossless capacity."""
    from repro.distributed import act_sharding as acts
    cfg = get_smoke_config("deepseek-v3-671b")
    cfg = cfg.__class__(**{**cfg.__dict__, "compute_dtype": "float32"})
    p = init_moe(jax.random.PRNGKey(1), cfg, jnp.float32)
    x = jnp.asarray(np.random.RandomState(1).randn(4, 8, cfg.d_model),
                    jnp.float32)
    cf = cfg.n_experts / cfg.n_experts_active
    y1, _ = moe_block(p, cfg, x, capacity_factor=cf)   # ds = 1 (no rules)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    try:
        acts.install(mesh, ("data",))
        y2, _ = moe_block(p, cfg, x, capacity_factor=cf)
    finally:
        acts.clear()
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-5, atol=1e-5)
