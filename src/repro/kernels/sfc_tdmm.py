"""Pallas TPU kernel: transform-domain int8 matmul with fused dequant.

The MXU hot spot of the staged SFC pipeline: for each transform-domain
position p in [0, t^2) an independent GEMM

    Y[p] = dequant( X[p] @ W[p] )        X: (T, K) int8, W: (K, N) int8

accumulated in int32 on the MXU and dequantized with the per-frequency
activation scale sx[p] and per-frequency-per-channel weight scales sw[p, :]
(paper Eq. 17).  Compared to direct int8 convolution, this stage runs
t^2 / (M^2 R^2) = 1/3.24x fewer MACs for SFC-6(6x6,3x3).

Depthwise 2-D convs have no channel contraction at all, so their
"matmul" collapses to a VPU elementwise product per position —
:func:`tdmm_int8_depthwise` is that stage (the lowering layer routes
``groups == C`` specs here instead of the t^2 GEMMs).

Blocking: grid (P, T/bt, N/bn[, K/bk]).  With ``k_block=None`` the full K
(C_in) dimension is resident per step — for bt = bn = 128, K = 2048:
256 KiB int8 X + 256 KiB W + 64 KiB int32 acc, comfortably within a v5e
core's 16 MiB VMEM, but K much beyond that blows the budget.  Passing
``k_block`` adds an innermost reduction grid dimension that accumulates
partial products into an int32 VMEM scratch and dequantizes on the last
k step, bounding VMEM residency at O(bt*bk + bk*bn) regardless of C_in.
MXU dims (bt, bk, bn) should be 128-multiples on real hardware.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import resolve_interpret

T_BLOCK = 128
N_BLOCK = 128


def _tdmm_kernel(x_ref, w_ref, s_ref, o_ref):
    x = x_ref[0]                                     # (bt, K) int8
    w = w_ref[0]                                     # (K, bn) int8
    acc = jnp.dot(x, w, preferred_element_type=jnp.int32)   # (bt, bn)
    o_ref[0] = acc.astype(jnp.float32) * s_ref[0]   # (1, bn) f32 scale


def _tdmm_kblock_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *,
                        n_k: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                                     # (bt, bk) int8
    w = w_ref[0]                                     # (bk, bn) int8
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _dequant():
        o_ref[0] = acc_ref[...].astype(jnp.float32) * s_ref[0]


def _tdmm_dw_kernel(x_ref, w_ref, s_ref, o_ref):
    x = x_ref[0].astype(jnp.int32)                   # (bt, bc)
    w = w_ref[0].astype(jnp.int32)                   # (1, bc)
    prod = x * w                                     # exact int32 products
    o_ref[0] = prod.astype(jnp.float32) * s_ref[0]  # (1, bc) f32 scale


def _pad_to(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    width = [(0, 0)] * x.ndim
    width[axis] = (0, pad)
    return jnp.pad(x, width)


def _scale_blocks(sx, sw, n_block):
    """(P, 1, Np) combined dequant scale ``sx[p] * sw[p, n]`` — the same
    product :func:`repro.core.conv2d.dequant_scale` gives the fused kernel;
    its (1, 1, n_block) blocks are tiling-legal on the chip."""
    s = sx.astype(jnp.float32)[:, None] * sw.astype(jnp.float32)
    return _pad_to(s, 1, n_block)[:, None, :]


@functools.partial(jax.jit, static_argnames=("interpret", "t_block",
                                             "n_block", "k_block"))
def tdmm_int8(xq: jnp.ndarray, wq: jnp.ndarray, sx: jnp.ndarray,
              sw: jnp.ndarray, *, interpret: Optional[bool] = None,
              t_block: int = T_BLOCK, n_block: int = N_BLOCK,
              k_block: Optional[int] = None) -> jnp.ndarray:
    """X (P, T, K) int8 x W (P, K, N) int8 -> (P, T, N) f32."""
    interpret = resolve_interpret(interpret)
    P, T, K = xq.shape
    _, _, N = wq.shape
    assert wq.shape == (P, K, N) and sx.shape == (P,) and sw.shape == (P, N)
    xq = _pad_to(xq, 1, t_block)
    wq = _pad_to(wq, 2, n_block)
    scale = _scale_blocks(sx, sw, n_block)
    Tp, Np = xq.shape[1], wq.shape[2]
    if k_block is None or k_block >= K:
        out = pl.pallas_call(
            _tdmm_kernel,
            grid=(P, Tp // t_block, Np // n_block),
            in_specs=[
                pl.BlockSpec((1, t_block, K), lambda p, i, j: (p, i, 0)),
                pl.BlockSpec((1, K, n_block), lambda p, i, j: (p, 0, j)),
                pl.BlockSpec((1, 1, n_block), lambda p, i, j: (p, 0, j)),
            ],
            out_specs=pl.BlockSpec((1, t_block, n_block),
                                   lambda p, i, j: (p, i, j)),
            out_shape=jax.ShapeDtypeStruct((P, Tp, Np), jnp.float32),
            interpret=interpret,
            name="sfc_tdmm",
        )(xq, wq, scale)
        return out[:, :T, :N]
    # k-blocked reduction: zero-padded K tail contributes nothing
    xq = _pad_to(xq, 2, k_block)
    wq = _pad_to(wq, 1, k_block)
    Kp = xq.shape[2]
    n_k = Kp // k_block
    kern = functools.partial(_tdmm_kblock_kernel, n_k=n_k)
    out = pl.pallas_call(
        kern,
        grid=(P, Tp // t_block, Np // n_block, n_k),
        in_specs=[
            pl.BlockSpec((1, t_block, k_block),
                         lambda p, i, j, k: (p, i, k)),
            pl.BlockSpec((1, k_block, n_block),
                         lambda p, i, j, k: (p, k, j)),
            pl.BlockSpec((1, 1, n_block), lambda p, i, j, k: (p, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, t_block, n_block),
                               lambda p, i, j, k: (p, i, j)),
        out_shape=jax.ShapeDtypeStruct((P, Tp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((t_block, n_block), jnp.int32)],
        interpret=interpret,
        name="sfc_tdmm_kblock",
    )(xq, wq, scale)
    return out[:, :T, :N]


@functools.partial(jax.jit, static_argnames=("interpret", "t_block",
                                             "n_block"))
def tdmm_int8_depthwise(xq: jnp.ndarray, wq: jnp.ndarray, sx: jnp.ndarray,
                        sw: jnp.ndarray, *, interpret: Optional[bool] = None,
                        t_block: int = T_BLOCK,
                        n_block: int = N_BLOCK) -> jnp.ndarray:
    """X (P, T, C) int8 x W (P, C) int8 -> (P, T, C) f32, elementwise.

    The depthwise element-wise stage: no C_in contraction, so each
    transform-domain position is a broadcast int32 product dequantized
    with sx[p] * sw[p, c] — VPU work, no MXU, no reduction grid dim.
    """
    P, T, C = xq.shape
    assert wq.shape == (P, C) and sx.shape == (P,) and sw.shape == (P, C), \
        (xq.shape, wq.shape, sx.shape, sw.shape)
    xq = _pad_to(xq, 1, t_block)
    xq = _pad_to(xq, 2, n_block)
    wq_p = _pad_to(wq, 1, n_block)[:, None, :]
    scale = _scale_blocks(sx, sw, n_block)
    Tp, Cp = xq.shape[1], xq.shape[2]
    out = pl.pallas_call(
        _tdmm_dw_kernel,
        grid=(P, Tp // t_block, Cp // n_block),
        in_specs=[
            pl.BlockSpec((1, t_block, n_block), lambda p, i, j: (p, i, j)),
            pl.BlockSpec((1, 1, n_block), lambda p, i, j: (p, 0, j)),
            pl.BlockSpec((1, 1, n_block), lambda p, i, j: (p, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, t_block, n_block),
                               lambda p, i, j: (p, i, j)),
        out_shape=jax.ShapeDtypeStruct((P, Tp, Cp), jnp.float32),
        interpret=resolve_interpret(interpret),
        name="sfc_tdmm_depthwise",
    )(xq, wq_p, scale)
    return out[:, :T, :C]
