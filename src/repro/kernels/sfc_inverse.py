"""Pallas TPU kernel: SFC inverse transform A^T Y A.

Maps dequantized transform-domain outputs (t, t, nT, O) back to spatial
output tiles (M, M, nT, O).  A^T carries the correction-term columns, so the
circular->linear conversion of paper §4.2 happens inside this same pass —
no separate correction pass or extra HBM traffic.  The arithmetic is the
static-coefficient :func:`repro.core.conv2d.separable_2d` the fused kernel
runs, on lane-dense (tile_block, chan_block) slabs.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import conv2d as c2d
from repro.core.generator import BilinearAlgorithm
from repro.runtime import resolve_interpret

TILE_BLOCK = 32
CHAN_BLOCK = 128


def _inverse_kernel(y_ref, o_ref, *, at):
    def emit_col(n, zs):
        o_ref[:, n] = jnp.stack(zs).astype(o_ref.dtype)
    c2d.separable_2d(at, lambda b: y_ref[:, b].astype(jnp.float32), emit_col)


def _pad_to(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    width = [(0, 0)] * x.ndim
    width[axis] = (0, pad)
    return jnp.pad(x, width)


@functools.partial(jax.jit, static_argnames=("algo", "interpret",
                                             "tile_block", "chan_block"))
def sfc_inverse(ty: jnp.ndarray, algo: BilinearAlgorithm, *,
                interpret: Optional[bool] = None,
                tile_block: int = TILE_BLOCK,
                chan_block: int = CHAN_BLOCK) -> jnp.ndarray:
    """(t, t, nT, O) -> (M, M, nT, O)."""
    t, _, nT, O = ty.shape
    M = algo.M
    ty = _pad_to(_pad_to(ty, 2, tile_block), 3, chan_block)
    nTp, Op = ty.shape[2], ty.shape[3]
    kern = functools.partial(_inverse_kernel,
                             at=c2d.transform_coefficients(algo)[1])
    out = pl.pallas_call(
        kern,
        grid=(nTp // tile_block, Op // chan_block),
        in_specs=[pl.BlockSpec((t, t, tile_block, chan_block),
                               lambda i, j: (0, 0, i, j))],
        out_specs=pl.BlockSpec((M, M, tile_block, chan_block),
                               lambda i, j: (0, 0, i, j)),
        out_shape=jax.ShapeDtypeStruct((M, M, nTp, Op), ty.dtype),
        interpret=resolve_interpret(interpret),
        name="sfc_inverse",
    )(ty)
    return out[:, :, :nT, :O]
