"""bench/graphs/layers.py: a layer's ``groups`` reaches its weights and
its plan, and a layer without the key is made and planned as before."""
import jax
import numpy as np
import pytest

from bench.graphs import layers as L

QUANT = L.quant_config({"bits_act": 8, "bits_weight": 8,
                        "act_granularity": "frequency",
                        "weight_granularity": "channel+frequency"})


def _layer(**kw):
    return dict(dict(name="c", index=3, kernel=3, stride=1, h=16, w=16,
                     cin=16, cout=16, algo="auto"), **kw)


def test_weights_take_cin_over_groups_from_the_same_draw():
    key = jax.random.PRNGKey(7)
    dense = L.make_params(key, [_layer()])["c"]["w"]
    same = L.make_params(key, [_layer(groups=1)])["c"]["w"]
    assert np.array_equal(np.asarray(dense), np.asarray(same))
    g4 = L.make_params(key, [_layer(groups=4)])["c"]["w"]
    dw = L.make_params(key, [_layer(groups=16)])["c"]["w"]
    assert g4.shape == (3, 3, 4, 16) and dw.shape == (3, 3, 1, 16)
    # He-normal over each group's fan-in: 9 * 4 and 9 * 1
    raw = jax.random.normal(jax.random.fold_in(key, 3), (3, 3, 1, 16))
    np.testing.assert_allclose(np.asarray(dw),
                               np.asarray(raw) * np.sqrt(2.0 / 9),
                               rtol=1e-6)


@pytest.mark.parametrize("groups,depthwise", [(None, False), (1, False),
                                              (4, False), (16, True)])
def test_plan_gets_a_depthwise_grouped_or_dense_spec(groups, depthwise):
    layer = _layer() if groups is None else _layer(groups=groups)
    spec = L.plan_layer(layer, QUANT).spec
    assert spec.depthwise is depthwise
    assert spec.groups == (1 if depthwise or groups is None else groups)
    assert (spec.in_channels, spec.out_channels) == (16, 16)
    assert spec.spatial == (16, 16) and spec.quant == QUANT
