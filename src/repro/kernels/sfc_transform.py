"""Pallas TPU kernel: SFC input tile transform (+ fused quantization).

Computes TX[a, b, n, c] = (B^T X_n B)[a, b] for a block of tiles and
channels per grid step.  The transform runs as static-coefficient adds
(:func:`repro.core.conv2d.separable_2d`, SFC's B^T is all 0/+-1 — the
paper's additions-only SFT) on lane-dense (tile_block, chan_block) slabs;
the fused variant also applies static per-frequency scales and emits int8,
saving an HBM round-trip of the f32 transform-domain tensor (the dominant
memory term of the SFC pipeline — see EXPERIMENTS.md §Perf).

Layouts put the tile index on sublanes and channels on lanes:
  tiles (L, L, nT, C) -> (t, t, nT, C)

VMEM budget per grid step (defaults TILE_BLOCK=32, CHAN_BLOCK=128, L<=14):
  in  : 14 * 14 * 32 * 128 * 4B  = 3.1 MiB  (x2 pipelined)
  out : 14 * 14 * 32 * 128 * 1..4B <= 3.1 MiB  (x2 pipelined, fits 16 MiB)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import conv2d as c2d
from repro.core.generator import BilinearAlgorithm
from repro.runtime import resolve_interpret

# int8 outputs tile (32, 128): a 32-tile block keeps every store aligned
TILE_BLOCK = 32
CHAN_BLOCK = 128


def _transform_kernel(x_ref, o_ref, *, bt):
    def emit_col(b, ys):
        o_ref[:, b] = jnp.stack(ys).astype(o_ref.dtype)
    c2d.separable_2d(bt, lambda j: x_ref[:, j].astype(jnp.float32), emit_col)


def _transform_quant_kernel(inv_ref, x_ref, o_ref, *, bt, qmax: int):
    t = len(bt.rows)

    def emit_col(b, ys):
        o_ref[:, b] = jnp.stack(
            [c2d.quantize_slab(y, inv_ref[a * t + b], qmax)
             for a, y in enumerate(ys)]).astype(o_ref.dtype)
    c2d.separable_2d(bt, lambda j: x_ref[:, j], emit_col)


def _pad_to(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    width = [(0, 0)] * x.ndim
    width[axis] = (0, pad)
    return jnp.pad(x, width)


def _launch(kern, tiles, algo, out_dtype, extra_in, *, interpret,
            tile_block, chan_block):
    L, _, nT, C = tiles.shape
    t = algo.t
    tiles = _pad_to(_pad_to(tiles, 2, tile_block), 3, chan_block)
    nTp, Cp = tiles.shape[2], tiles.shape[3]
    out = pl.pallas_call(
        kern,
        grid=(nTp // tile_block, Cp // chan_block),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * len(extra_in)
        + [pl.BlockSpec((L, L, tile_block, chan_block),
                        lambda i, j: (0, 0, i, j))],
        out_specs=pl.BlockSpec((t, t, tile_block, chan_block),
                               lambda i, j: (0, 0, i, j)),
        out_shape=jax.ShapeDtypeStruct((t, t, nTp, Cp), out_dtype),
        interpret=resolve_interpret(interpret),
        name="sfc_transform",
    )(*extra_in, tiles)
    return out[:, :, :nT, :C]


@functools.partial(jax.jit, static_argnames=("algo", "interpret",
                                             "tile_block", "chan_block"))
def sfc_transform(tiles: jnp.ndarray, algo: BilinearAlgorithm, *,
                  interpret: Optional[bool] = None,
                  tile_block: int = TILE_BLOCK,
                  chan_block: int = CHAN_BLOCK) -> jnp.ndarray:
    """tiles (L, L, nT, C) -> (t, t, nT, C) in the tiles' dtype (f32 adds)."""
    kern = functools.partial(_transform_kernel,
                             bt=c2d.transform_coefficients(algo)[0])
    return _launch(kern, tiles, algo, tiles.dtype, (), interpret=interpret,
                   tile_block=tile_block, chan_block=chan_block)


@functools.partial(jax.jit, static_argnames=("algo", "bits", "interpret",
                                             "tile_block", "chan_block"))
def sfc_transform_quantize(tiles: jnp.ndarray, algo: BilinearAlgorithm,
                           scale: jnp.ndarray, *, bits: int = 8,
                           interpret: Optional[bool] = None,
                           tile_block: int = TILE_BLOCK,
                           chan_block: int = CHAN_BLOCK) -> jnp.ndarray:
    """tiles (L, L, nT, C) f32 -> int8 (t, t, nT, C), static per-frequency
    quantization with ``scale`` (t, t)."""
    kern = functools.partial(_transform_quant_kernel,
                             bt=c2d.transform_coefficients(algo)[0],
                             qmax=2 ** (bits - 1) - 1)
    inv = c2d.reciprocal_scale(scale).reshape(-1)
    return _launch(kern, tiles.astype(jnp.float32), algo, jnp.int8, (inv,),
                   interpret=interpret, tile_block=tile_block,
                   chan_block=chan_block)
