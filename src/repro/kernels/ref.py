"""Pure-jnp oracles for the Pallas kernels (the ``ref.py`` contract).

Shapes use the *kernel* layout:
  tiles     : (L, L, nT, C)      tile element (i, j) of every tile
  transform : (t, t, nT, C)
  tdmm      : X (P, T, K) int8, W (P, K, N) int8 -> (P, T, N) f32
              with per-position activation scales sx (P,) and
              per-position-per-channel weight scales sw (P, N)
  inverse   : (t, t, nT, O) -> (M, M, nT, O)

The fp oracles contract with ``einsum`` at HIGHEST precision (held to a
tolerance); the int8 oracles quantize through the shared static-coefficient
transform (:func:`repro.core.conv2d.separable_2d`) so they land on the
kernels' integer grid exactly and are held to bit-exactness.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import conv2d as c2d
from repro.core.generator import BilinearAlgorithm

_HIGHEST = jax.lax.Precision.HIGHEST


def separable_ref(mat: c2d.StaticTransform, x: jnp.ndarray) -> jnp.ndarray:
    """(n, n, ...) -> (m, m, ...) through the shared static-coefficient
    transform, whole arrays at a time."""
    out = []
    c2d.separable_2d(mat, lambda j: x[:, j],
                     lambda b, ys: out.append(jnp.stack(ys)))
    return jnp.stack(out, axis=1)


def sfc_transform_ref(tiles: jnp.ndarray, bt: jnp.ndarray) -> jnp.ndarray:
    out = jnp.einsum("ti,ijnc,uj->tunc", bt, tiles, bt, precision=_HIGHEST,
                     preferred_element_type=jnp.float32)
    return out.astype(tiles.dtype)


def sfc_transform_quantize_ref(tiles: jnp.ndarray, algo: BilinearAlgorithm,
                               scale: jnp.ndarray, bits: int = 8
                               ) -> jnp.ndarray:
    """Transform + static per-frequency quantization to intN."""
    tx = separable_ref(c2d.transform_coefficients(algo)[0], tiles)
    inv = c2d.reciprocal_scale(scale)[:, :, None, None]
    return c2d.quantize_slab(tx, inv, 2 ** (bits - 1) - 1).astype(jnp.int8)


def tdmm_int8_ref(xq: jnp.ndarray, wq: jnp.ndarray, sx: jnp.ndarray,
                  sw: jnp.ndarray) -> jnp.ndarray:
    """Transform-domain matmul: int8 x int8 -> int32 -> dequant f32."""
    acc = jnp.einsum("ptk,pkn->ptn", xq.astype(jnp.int32),
                     wq.astype(jnp.int32))
    return acc.astype(jnp.float32) * (sx[:, None, None] * sw[:, None, :])


def sfc_inverse_ref(ty: jnp.ndarray, at: jnp.ndarray) -> jnp.ndarray:
    return jnp.einsum("mt,tuno,pu->mpno", at, ty, at, precision=_HIGHEST)


def quantized_fastconv2d_ref(x: jnp.ndarray, w: jnp.ndarray,
                             algo: BilinearAlgorithm,
                             act_scale: jnp.ndarray,
                             w_scale: jnp.ndarray,
                             padding: str = "SAME") -> jnp.ndarray:
    """End-to-end oracle for the fused int8 SFC convolution pipeline.

    act_scale: (t, t) static calibrated scales; w_scale: (t, t, Cout).
    """
    B, H, W_, C = x.shape
    tx, geom = c2d.transform_input_2d(x, algo, padding)
    nH, nW = geom[2], geom[3]
    t, M = algo.t, algo.M
    qmax = 127
    inv = c2d.reciprocal_scale(act_scale)
    xq = c2d.quantize_slab(tx, inv[:, :, None], qmax).astype(jnp.int8)
    tw = c2d.transform_weights_2d(w, algo)
    wq = jnp.clip(jnp.round(tw / w_scale[:, :, None, :]),
                  -qmax, qmax).astype(jnp.int8)
    P = t * t
    X = jnp.transpose(xq.reshape(B * nH * nW, P, C), (1, 0, 2))
    Y = tdmm_int8_ref(X, wq.reshape(P, C, -1), act_scale.reshape(P),
                      w_scale.reshape(P, -1))              # (P, T, O)
    O = Y.shape[-1]
    y = separable_ref(c2d.transform_coefficients(algo)[1],
                      Y.reshape(t, t, B * nH * nW, O))     # (M, M, T, O)
    y = y.reshape(M, M, B, nH, nW, O)
    y = jnp.transpose(y, (2, 3, 0, 4, 1, 5)).reshape(B, nH * M, nW * M, O)
    return y[:, :geom[0], :geom[1], :]
