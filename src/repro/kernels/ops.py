"""Jit'd public wrappers assembling the Pallas SFC kernels end-to-end.

``quantized_fastconv2d`` is the deployment path of the paper's pipeline:

  tile -> [Pallas: transform + per-frequency int8 quant]   (additions only)
       -> [Pallas: t^2-position int8 MXU matmul + dequant]
       -> [Pallas: inverse transform incl. correction terms]
       -> untile

Scales are static (PTQ-calibrated): act_scale (t, t), w_scale (t, t, Cout).
Layouts keep the tile index on sublanes and channels on lanes: tiles
(L, L, nT, C) -> transform (t, t, nT, C) = X (P, nT, C) for the matmul ->
inverse (M, M, nT, O).  ``interpret=None`` compiles the kernels on a TPU
and runs them in the Pallas interpreter elsewhere
(:func:`repro.runtime.resolve_interpret`).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import conv2d as c2d
from repro.core.generator import BilinearAlgorithm
from repro.kernels.sfc_transform import sfc_transform, sfc_transform_quantize
from repro.kernels.sfc_tdmm import tdmm_int8, tdmm_int8_depthwise
from repro.kernels.sfc_inverse import sfc_inverse


def extract_tiles(x: jnp.ndarray, algo: BilinearAlgorithm,
                  padding: str = "SAME") -> Tuple[jnp.ndarray, Tuple]:
    """(B,H,W,C) -> tiles (L, L, B*nH*nW, C) + geometry.

    Tile element (i, j) of every tile is one stride-M slice of the padded
    input, so the gather is L^2 strided slices (no index arrays)."""
    B, H, W, C = x.shape
    M, R, L = algo.M, algo.R, algo.L
    lo_h, hi_h, out_h = c2d.pad_amounts(H, M, R, padding)
    lo_w, hi_w, out_w = c2d.pad_amounts(W, M, R, padding)
    xp = jnp.pad(x, ((0, 0), (lo_h, hi_h), (lo_w, hi_w), (0, 0)))
    nH = (xp.shape[1] - (R - 1)) // M
    nW = (xp.shape[2] - (R - 1)) // M
    tiles = jnp.stack([jnp.stack(
        [xp[:, i:i + M * (nH - 1) + 1:M, j:j + M * (nW - 1) + 1:M, :]
         .reshape(B * nH * nW, C) for j in range(L)]) for i in range(L)])
    return tiles, (B, out_h, out_w, nH, nW)


def untile(y_tiles: jnp.ndarray, algo: BilinearAlgorithm,
           geom: Tuple) -> jnp.ndarray:
    """(M, M, B*nH*nW, O) -> (B, H', W', O)."""
    B, out_h, out_w, nH, nW = geom
    M = algo.M
    O = y_tiles.shape[-1]
    y = y_tiles.reshape(M, M, B, nH, nW, O)
    y = jnp.transpose(y, (2, 3, 0, 4, 1, 5)).reshape(B, nH * M, nW * M, O)
    return y[:, :out_h, :out_w, :]


def quantize_weights(w: jnp.ndarray, algo: BilinearAlgorithm,
                     w_scale: jnp.ndarray, bits: int = 8) -> jnp.ndarray:
    """(R,R,Cin,Cout) f32 -> (t^2, Cin, Cout) int8 — offline, once."""
    from repro.quant.fake_quant import quantize_transformed_weights
    tw = c2d.transform_weights_2d(w, algo)            # (t,t,Cin,Cout)
    return quantize_transformed_weights(tw, w_scale, bits)


@functools.partial(jax.jit, static_argnames=("algo", "padding", "bits",
                                             "interpret", "k_block",
                                             "tile_block", "chan_block"))
def quantized_fastconv2d(x: jnp.ndarray, wq: jnp.ndarray,
                         act_scale: jnp.ndarray, w_scale: jnp.ndarray,
                         algo: BilinearAlgorithm, *,
                         padding: str = "SAME", bits: int = 8,
                         interpret: Optional[bool] = None,
                         k_block: Optional[int] = None,
                         tile_block: int = 32,
                         chan_block: int = 128) -> jnp.ndarray:
    """int8 SFC convolution with pre-quantized weights (staged pipeline).

    x (B,H,W,Cin) f32; wq (t^2, Cin, Cout) int8; act_scale (t,t);
    w_scale (t,t,Cout) -> (B,H',W',Cout) f32.  ``bits`` sets the
    activation clipping grid (sub-int8 policies run on the int8 carrier);
    ``k_block`` bounds the C_in VMEM residency of the transform-domain
    matmul (see ``tdmm_int8``); ``tile_block``/``chan_block`` block the
    transform/inverse stages.
    """
    t = algo.t
    tiles, geom = extract_tiles(x, algo, padding)
    xq = sfc_transform_quantize(tiles, algo, act_scale, bits=bits,
                                interpret=interpret, tile_block=tile_block,
                                chan_block=chan_block)
    T, C = xq.shape[2], xq.shape[3]
    Y = tdmm_int8(xq.reshape(t * t, T, C), wq, act_scale.reshape(t * t),
                  w_scale.reshape(t * t, -1), interpret=interpret,
                  k_block=k_block)
    y_tiles = sfc_inverse(Y.reshape(t, t, T, -1), algo, interpret=interpret,
                          tile_block=tile_block, chan_block=chan_block)
    return untile(y_tiles, algo, geom)


@functools.partial(jax.jit, static_argnames=("algo", "padding", "bits",
                                             "interpret", "tile_block",
                                             "chan_block"))
def quantized_fastconv2d_depthwise(x: jnp.ndarray, wq: jnp.ndarray,
                                   act_scale: jnp.ndarray,
                                   w_scale: jnp.ndarray,
                                   algo: BilinearAlgorithm, *,
                                   padding: str = "SAME", bits: int = 8,
                                   interpret: Optional[bool] = None,
                                   tile_block: int = 32,
                                   chan_block: int = 128) -> jnp.ndarray:
    """int8 depthwise SFC convolution (staged pipeline).

    x (B,H,W,C) f32; wq (t^2, 1, C) int8; act_scale (t,t); w_scale
    (t,t,C) -> (B,H',W',C) f32.  Same three stages as the dense
    ``quantized_fastconv2d`` with the t^2 GEMMs replaced by the
    transform-domain elementwise product (``tdmm_int8_depthwise``) —
    there is no channel contraction, so no k-blocking either.
    """
    t = algo.t
    tiles, geom = extract_tiles(x, algo, padding)
    xq = sfc_transform_quantize(tiles, algo, act_scale, bits=bits,
                                interpret=interpret, tile_block=tile_block,
                                chan_block=chan_block)
    T, C = xq.shape[2], xq.shape[3]
    Y = tdmm_int8_depthwise(xq.reshape(t * t, T, C), wq.reshape(t * t, C),
                            act_scale.reshape(t * t),
                            w_scale.reshape(t * t, C), interpret=interpret)
    y_tiles = sfc_inverse(Y.reshape(t, t, T, C), algo, interpret=interpret,
                          tile_block=tile_block, chan_block=chan_block)
    return untile(y_tiles, algo, geom)


@functools.partial(jax.jit, static_argnames=("algo", "padding", "interpret"))
def fastconv2d_fp(x: jnp.ndarray, w: jnp.ndarray, algo: BilinearAlgorithm, *,
                  padding: str = "SAME", interpret: Optional[bool] = None
                  ) -> jnp.ndarray:
    """Unquantized kernel path (transform -> f32 tdmm -> inverse)."""
    tiles, geom = extract_tiles(x, algo, padding)
    tx = sfc_transform(tiles, algo, interpret=interpret)
    tw = c2d.transform_weights_2d(w, algo)
    ty = jnp.einsum("tunc,tuco->tuno", tx, tw,
                    precision=jax.lax.Precision.HIGHEST)
    y_tiles = sfc_inverse(ty, algo, interpret=interpret)
    return untile(y_tiles, algo, geom)
