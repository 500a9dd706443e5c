"""The plain f32 reference conv with ``groups``: the same as one dense
``lax`` conv per channel group, concatenated."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.graphs import reference as ref


@pytest.mark.parametrize("groups,stride", [(2, 1), (4, 2), (8, 1)])
def test_grouped_conv_is_one_dense_conv_per_group(groups, stride):
    cin, cout = 8, 16
    kx, kw, kb = jax.random.split(jax.random.PRNGKey(groups + stride), 3)
    x = jax.random.normal(kx, (2, 9, 9, cin), jnp.float32)
    w = jax.random.normal(kw, (3, 3, cin // groups, cout), jnp.float32)
    b = jax.random.normal(kb, (cout,), jnp.float32)
    ci, co = cin // groups, cout // groups
    dense = [jax.lax.conv_general_dilated(
        x[..., i * ci:(i + 1) * ci], w[..., i * co:(i + 1) * co],
        (stride, stride), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST) for i in range(groups)]
    want = jnp.concatenate(dense, axis=-1) + b
    got = ref.conv(x, w, b, stride, groups)
    assert got.shape == (2, -(-9 // stride), -(-9 // stride), cout)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
