"""Pallas kernels (interpret mode) vs ref.py oracles: shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import generate_sfc, conv2d_direct
from repro.core import conv2d as c2d
from repro.kernels import (fastconv2d_fp, quantize_weights,
                           quantized_fastconv2d, ref, sfc_inverse,
                           sfc_transform, sfc_transform_quantize, tdmm_int8)

ALGO_SET = [(4, 4, 3), (6, 6, 3), (6, 7, 3)]


@pytest.mark.parametrize("nmr", ALGO_SET)
@pytest.mark.parametrize("n_tiles,channels", [(1, 1), (5, 19), (16, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_transform_kernel_sweep(nmr, n_tiles, channels, dtype):
    algo = generate_sfc(*nmr)
    rng = np.random.RandomState(0)
    tiles = jnp.asarray(rng.randn(algo.L, algo.L, n_tiles, channels), dtype)
    bt = jnp.asarray(algo.bt(), dtype)
    out = sfc_transform(tiles, algo)
    want = ref.sfc_transform_ref(tiles, bt)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5, atol=1e-2)


@pytest.mark.parametrize("nmr", ALGO_SET)
def test_transform_quantize_kernel_bitexact(nmr):
    algo = generate_sfc(*nmr)
    rng = np.random.RandomState(1)
    tiles = jnp.asarray(rng.randn(algo.L, algo.L, 7, 33), jnp.float32)
    bt = jnp.asarray(algo.bt(), jnp.float32)
    scale = jnp.abs(ref.sfc_transform_ref(tiles, bt)).max(
        axis=(2, 3)) / 127 + 1e-9
    out = sfc_transform_quantize(tiles, algo, scale)
    want = ref.sfc_transform_quantize_ref(tiles, algo, scale)
    assert out.dtype == jnp.int8
    assert bool(jnp.all(out == want))


@pytest.mark.parametrize("P,T,K,N", [(4, 8, 16, 8), (7, 33, 19, 21),
                                     (9, 130, 64, 130), (1, 1, 1, 1)])
def test_tdmm_kernel_sweep(P, T, K, N):
    rng = np.random.RandomState(2)
    xq = jnp.asarray(rng.randint(-127, 128, (P, T, K)), jnp.int8)
    wq = jnp.asarray(rng.randint(-127, 128, (P, K, N)), jnp.int8)
    sx = jnp.asarray(rng.rand(P), jnp.float32)
    sw = jnp.asarray(rng.rand(P, N), jnp.float32)
    out = tdmm_int8(xq, wq, sx, sw)
    want = ref.tdmm_int8_ref(xq, wq, sx, sw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("nmr", ALGO_SET)
def test_inverse_kernel(nmr):
    algo = generate_sfc(*nmr)
    rng = np.random.RandomState(3)
    ty = jnp.asarray(rng.randn(algo.t, algo.t, 5, 21), jnp.float32)
    at = jnp.asarray(algo.at(), jnp.float32)
    out = sfc_inverse(ty, algo)
    want = ref.sfc_inverse_ref(ty, at)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_end_to_end_quantized_conv_kernel():
    """Full Pallas pipeline == ref oracle (bit-exact) and ~int8-close to fp."""
    algo = generate_sfc(6, 6, 3)
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(2, 13, 13, 16), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 16, 8) * 0.2, jnp.float32)
    tx, _ = c2d.transform_input_2d(x, algo)
    act_scale = jnp.abs(tx).max(axis=(0, 1, 2, 5)) / 127
    tw = c2d.transform_weights_2d(w, algo)
    w_scale = jnp.abs(tw).max(axis=2) / 127
    wq = quantize_weights(w, algo, w_scale)
    y = quantized_fastconv2d(x, wq, act_scale, w_scale, algo)
    yref = ref.quantized_fastconv2d_ref(x, w, algo, act_scale, w_scale)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref),
                               rtol=1e-6, atol=1e-6)
    yfp = conv2d_direct(x, w)
    rel = float(jnp.linalg.norm(y - yfp) / jnp.linalg.norm(yfp))
    assert rel < 0.03


def test_fp_kernel_path():
    algo = generate_sfc(6, 7, 3)
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(1, 14, 14, 8), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 8, 4), jnp.float32)
    y = fastconv2d_fp(x, w, algo)
    yfp = conv2d_direct(x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yfp),
                               rtol=1e-4, atol=1e-4)
