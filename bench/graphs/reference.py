"""The plain f32 reference conv: ``lax`` at HIGHEST precision.

Imports nothing of the program under test.  The family modules build
their reference network from this and the same topology the program's
network uses.
"""
import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def conv(x, w, b, stride: int, groups: int = 1):
    """SAME-padded NHWC/HWIO convolution plus bias, in f32 at HIGHEST;
    with ``groups`` > 1 grouped, ``w`` of shape (R, R, C_in/groups, C_out)."""
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
        feature_group_count=groups)
    return y + b


def dense(h, w, b):
    return jnp.dot(h, w, precision=HIGHEST) + b
