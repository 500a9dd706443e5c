"""Tiled fast convolution execution in JAX (2-D NHWC and 1-D depthwise).

Implements the three-stage bilinear flow (paper Eq. 1) for any
``BilinearAlgorithm`` (SFC, Winograd, direct):

    Y = A^T [ (G W G^T) (.) (B^T X B) ] A

vectorized over batch x tiles x channels. The transform-domain contraction
(stage 2 amortized over C_in/C_out) is the MXU hot spot; a Pallas kernel
version lives in ``repro.kernels`` — this module is the reference/portable
path and the oracle for those kernels.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from fractions import Fraction
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.generator import BilinearAlgorithm


# --------------------------------------------------------------------------
# Transform-matrix cache
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def transform_matrices(algo: BilinearAlgorithm, dtype: str = "float32"
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Device-resident ``(bt, g, at)`` for ``algo`` at ``dtype``, cached.

    The dtype cast of the exact transform matrices is prepare-time work:
    every kernel wrapper and backend used to rebuild ``jnp.asarray(
    algo.bt(), dtype)`` (and ``sfc_transform`` re-cast per call) on each
    invocation of the hot path.  Algorithms are frozen, hashable
    dataclasses and the registry memoizes instances, so one cache entry
    serves every plan/apply for a given (algorithm, dtype).
    """
    dt = jnp.dtype(dtype)
    # the first call for a given (algo, dtype) can land inside a jit /
    # scan / checkpoint trace; force eager construction so the cache
    # holds concrete arrays, never tracers
    with jax.ensure_compile_time_eval():
        return (jnp.asarray(algo.bt(), dt), jnp.asarray(algo.g(), dt),
                jnp.asarray(algo.at(), dt))


# --------------------------------------------------------------------------
# Static-coefficient transforms: the one arithmetic every int8 path shares
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class StaticTransform:
    """A transform matrix as integer rows over one common denominator:
    ``mat = rows / den``.  Static and hashable, so kernels unroll it into
    adds with the coefficients baked in."""

    rows: Tuple[Tuple[int, ...], ...]
    den: int = 1

    @classmethod
    def of(cls, mat) -> "StaticTransform":
        den = math.lcm(*(Fraction(c).denominator for row in mat for c in row))
        return cls(tuple(tuple(int(Fraction(c) * den) for c in row)
                         for row in mat), den)


@functools.lru_cache(maxsize=None)
def transform_coefficients(algo: BilinearAlgorithm
                           ) -> Tuple[StaticTransform, StaticTransform]:
    """``(B^T, A^T)`` of ``algo`` as :class:`StaticTransform` (SFC's B^T is
    all 0/+-1 — additions only; its A^T is 1/N times an integer matrix)."""
    return StaticTransform.of(algo.BT), StaticTransform.of(algo.AT)


def _multiple(c: int, v: jnp.ndarray) -> jnp.ndarray:
    """``c * v`` for an integer ``c > 0`` from exact power-of-two scalings
    and adds: no rounded product ever feeds an add, so a compiler that
    contracts multiply-add into FMA cannot change the result."""
    acc, bit = None, 0
    while c:
        if c & 1:
            term = v if bit == 0 else v * float(1 << bit)
            acc = term if acc is None else acc + term
        c >>= 1
        bit += 1
    return acc


def combine(row: Tuple[int, ...], term: Callable[[int], jnp.ndarray]
            ) -> jnp.ndarray:
    """``sum_j row[j] * term(j)`` for integer ``row``, in ascending ``j``:
    zero coefficients are skipped, +-1 become an add or a subtract.

    Every datapath (fused and staged kernels, the reference int8
    simulation) evaluates its transforms through this one function, so
    they round identically and quantize onto the same integer grid.
    """
    acc = None
    for j, c in enumerate(row):
        if c == 0:
            continue
        v = _multiple(abs(c), term(j))
        if acc is None:
            acc = v if c > 0 else -v
        else:
            acc = acc + v if c > 0 else acc - v
    if acc is None:
        raise ValueError(f"all-zero transform row {row}")
    return acc


def separable_2d(mat: StaticTransform,
                 load_col: Callable[[int], jnp.ndarray],
                 emit_col: Callable[[int, List[jnp.ndarray]], None]
                 ) -> None:
    """Evaluate ``Y = mat @ X @ mat^T`` one output column at a time.

    ``load_col(j)`` returns input column ``X[:, j]`` stacked on a leading
    axis, ``(n_in, ...)`` — typically lane-dense ``(columns, channels)``
    slabs, one per tile row.  ``emit_col(b, ys)`` receives output column
    ``b`` as the list ``ys[a] = Y[a, b]``.  Each input column is read
    once; the column transform combines whole columns, the row transform
    combines their leading-axis slices.  A denominator is applied once,
    as the last operation before ``emit_col``.
    """
    rows = mat.rows
    n_in = len(rows[0])
    norm = 1.0 / float(mat.den * mat.den)
    cols = {}

    def col_in(j):
        if j not in cols:
            cols[j] = load_col(j)
        return cols[j]

    for b in range(len(rows)):
        xb = combine(rows[b], col_in)                 # (n_in, ...)
        parts = [xb[i] for i in range(n_in)]
        ys = [combine(row, parts.__getitem__) for row in rows]
        emit_col(b, ys if mat.den == 1 else [y * norm for y in ys])


def quantize_slab(v: jnp.ndarray, inv_scale, qmax: int) -> jnp.ndarray:
    """Static per-frequency quantization onto the integer grid (values stay
    float).  Multiplies by a reciprocal computed once per call
    (:func:`reciprocal_scale`), which rounds the same way on every device."""
    return jnp.clip(jnp.round(v * inv_scale), -qmax, qmax)


def reciprocal_scale(act_scale: jnp.ndarray) -> jnp.ndarray:
    """``1 / act_scale`` in f32: the multiplier :func:`quantize_slab` takes."""
    return 1.0 / jnp.asarray(act_scale, jnp.float32)


def dequant_scale(act_scale: jnp.ndarray, w_scale: jnp.ndarray
                  ) -> jnp.ndarray:
    """``(P, Cout)`` combined dequantization scale ``s_x[p] * s_w[p, o]``
    from act_scale ``(t, t)`` and w_scale ``(t, t, Cout)``."""
    P = act_scale.shape[0] * act_scale.shape[1]
    return (jnp.asarray(act_scale, jnp.float32).reshape(P, 1)
            * jnp.asarray(w_scale, jnp.float32).reshape(P, -1))


# --------------------------------------------------------------------------
# Tiling helpers
# --------------------------------------------------------------------------
def _overlap_tiles_1d(n_tiles: int, M: int, L: int) -> np.ndarray:
    """Row indices (n_tiles, L) of overlapping tiles with stride M."""
    return np.arange(n_tiles)[:, None] * M + np.arange(L)[None, :]


def pad_amounts(size: int, M: int, R: int, padding: str) -> Tuple[int, int, int]:
    """(lo_pad, hi_pad, out_size) for one spatial dim."""
    if padding == "SAME":
        out = size
        lo = (R - 1) // 2
    elif padding == "VALID":
        out = size - R + 1
        lo = 0
    else:
        raise ValueError(f"padding must be SAME or VALID, got {padding}")
    n_tiles = -(-out // M)  # ceil
    padded_needed = n_tiles * M + R - 1
    hi = padded_needed - size - lo
    return lo, hi, out


# --------------------------------------------------------------------------
# 2-D convolution (NHWC, HWIO weights, stride 1)
# --------------------------------------------------------------------------
def transform_input_2d(x: jnp.ndarray, algo: BilinearAlgorithm,
                       padding: str = "SAME") -> Tuple[jnp.ndarray, Tuple]:
    """(B,H,W,C) -> transform-domain tiles (B, nH, nW, t, t, C)."""
    B, H, W, C = x.shape
    M, R, L = algo.M, algo.R, algo.L
    lo_h, hi_h, out_h = pad_amounts(H, M, R, padding)
    lo_w, hi_w, out_w = pad_amounts(W, M, R, padding)
    xp = jnp.pad(x, ((0, 0), (lo_h, hi_h), (lo_w, hi_w), (0, 0)))
    nH = (xp.shape[1] - (R - 1)) // M
    nW = (xp.shape[2] - (R - 1)) // M
    return _input_transform(xp, algo, nH, nW), (out_h, out_w, nH, nW)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _input_transform(xp: jnp.ndarray, algo: BilinearAlgorithm, nH: int,
                     nW: int) -> jnp.ndarray:
    """Padded (B, Hp, Wp, C) -> (B, nH, nW, t, t, C) through
    :func:`separable_2d`: element (i, j) of every tile is one stride-M
    slice of ``xp``."""
    M = algo.M
    bt = transform_coefficients(algo)[0]
    out = []

    def load_col(j):
        return jnp.stack([xp[:, i:i + M * (nH - 1) + 1:M,
                             j:j + M * (nW - 1) + 1:M, :]
                          for i in range(algo.L)])

    separable_2d(bt, load_col, lambda b, ys: out.append(jnp.stack(ys, -2)))
    return jnp.stack(out, axis=-2)                # (B, nH, nW, t_a, t_b, C)


def transform_weights_2d(w: jnp.ndarray, algo: BilinearAlgorithm) -> jnp.ndarray:
    """(R,R,Cin,Cout) -> (t,t,Cin,Cout)."""
    g = transform_matrices(algo, w.dtype.name)[1]
    return jnp.einsum("ti,ijco,uj->tuco", g, w, g)


def transform_domain_matmul(tx: jnp.ndarray, tw: jnp.ndarray,
                            precision=jax.lax.Precision.HIGHEST) -> jnp.ndarray:
    """(B,nH,nW,t,t,Cin) x (t,t,Cin,Cout) -> (B,nH,nW,t,t,Cout).

    The hot loop: t^2 independent GEMMs of shape
    (B*nH*nW, Cin) x (Cin, Cout), one per transform-domain position.
    """
    return jnp.einsum("bnwtuc,tuco->bnwtuo", tx, tw, precision=precision)


def inverse_transform_2d(ty: jnp.ndarray, algo: BilinearAlgorithm,
                         geom: Tuple) -> jnp.ndarray:
    """(B,nH,nW,t,t,Cout) -> (B,H_out,W_out,Cout)."""
    out_h, out_w, nH, nW = geom
    at = transform_matrices(algo, ty.dtype.name)[2]
    y = jnp.einsum("mt,bnwtuo,pu->bnwmpo", at, ty, at,
                   precision=jax.lax.Precision.HIGHEST)  # (B,nH,nW,M,M,O)
    B = y.shape[0]
    O = y.shape[-1]
    M = algo.M
    y = jnp.transpose(y, (0, 1, 3, 2, 4, 5)).reshape(B, nH * M, nW * M, O)
    return y[:, :out_h, :out_w, :]


def fastconv2d(x: jnp.ndarray, w: jnp.ndarray, algo: BilinearAlgorithm,
               padding: str = "SAME",
               bias: Optional[jnp.ndarray] = None,
               elementwise_hook: Optional[Callable] = None) -> jnp.ndarray:
    """Fast 2-D convolution (cross-correlation, as in ML convention).

    ``elementwise_hook(tx, tw) -> (tx, tw)`` lets the quantization layer
    inject the transform-domain fake-quantization (paper Eq. 17).
    """
    assert w.shape[0] == w.shape[1] == algo.R, (w.shape, algo.R)
    tx, geom = transform_input_2d(x, algo, padding)
    tw = transform_weights_2d(w, algo)
    if elementwise_hook is not None:
        tx, tw = elementwise_hook(tx, tw)
    ty = transform_domain_matmul(tx, tw)
    y = inverse_transform_2d(ty, algo, geom)
    if bias is not None:
        y = y + bias
    return y


def conv2d_direct(x: jnp.ndarray, w: jnp.ndarray,
                  padding: str = "SAME",
                  bias: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Reference direct convolution via lax (NHWC, HWIO, stride 1)."""
    y = jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), window_strides=(1, 1), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if bias is not None:
        y = y + bias
    return y


# --------------------------------------------------------------------------
# 1-D depthwise causal convolution (Mamba2 / Zamba2 short conv)
# --------------------------------------------------------------------------
def fastconv1d_depthwise_causal(x: jnp.ndarray, w: jnp.ndarray,
                                algo: BilinearAlgorithm) -> jnp.ndarray:
    """Causal depthwise conv1d: x (B, T, C), w (R, C) -> (B, T, C).

    y[b, t, c] = sum_r x[b, t - (R-1) + r, c] * w[r, c]   (left-padded)

    Depthwise has no channel contraction, so the element-wise stage is a
    true element-wise product — exactly the regime the paper's
    multiplication counting addresses (t/M mults per output vs R direct).
    """
    assert w.shape == (algo.R, x.shape[-1]), (w.shape, algo.R, x.shape)
    g = transform_matrices(algo, w.dtype.name)[1]
    tw = jnp.einsum("tr,rc->tc", g, w)
    return fastconv1d_depthwise_causal_pretransformed(x, tw, algo)


def fastconv1d_depthwise_causal_pretransformed(
        x: jnp.ndarray, tw: jnp.ndarray, algo: BilinearAlgorithm
        ) -> jnp.ndarray:
    """Same flow with offline-transformed weights tw (t, C) — the form
    ``repro.api`` prepared weights feed."""
    B, T, C = x.shape
    assert tw.shape == (algo.t, C), (tw.shape, algo.t, x.shape)
    R, M, L = algo.R, algo.M, algo.L
    n_tiles = -(-T // M)
    xp = jnp.pad(x, ((0, 0), (R - 1, n_tiles * M - T), (0, 0)))
    idx = _overlap_tiles_1d(n_tiles, M, L)
    tiles = xp[:, idx, :]                                   # (B, nT, L, C)
    bt, _, at = transform_matrices(algo, x.dtype.name)
    tx = jnp.einsum("ti,bnic->bntc", bt, tiles)
    ty = tx * tw[None, None, :, :]
    y = jnp.einsum("mt,bntc->bnmc", at, ty)                 # (B,nT,M,C)
    y = y.reshape(B, n_tiles * M, C)
    return y[:, :T, :]


def conv1d_depthwise_causal_direct(x: jnp.ndarray, w: jnp.ndarray
                                   ) -> jnp.ndarray:
    """Oracle for the depthwise causal conv1d."""
    B, T, C = x.shape
    R = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (R - 1, 0), (0, 0)))
    out = jnp.zeros((B, T, C), dtype=x.dtype)
    for r in range(R):
        out = out + xp[:, r:r + T, :] * w[r][None, None, :]
    return out
