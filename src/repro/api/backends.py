"""Execution backends behind ``ConvPlan.apply``.

Two backends ship today, both consuming the same ``PreparedWeights``:

  * ``reference`` — pure jnp, built from the ``repro.core.conv2d``
    primitives.  Supports elementwise hooks (dynamic fake quantization,
    PTQ calibration observers) and is the numerical oracle.
  * ``pallas``    — the ``repro.kernels`` TPU kernels, compiled on a TPU
    and run in the Pallas interpreter elsewhere (``ConvPlan.interpret``,
    derived from the platform).  Static precision only: fp, or int8 with
    PTQ-calibrated scales baked into the prepared weights.

2-D depthwise specs run the transform-domain *elementwise* stage instead
of the t^2 matmuls on both backends (jnp broadcast on ``reference``; the
``tdmm_int8_depthwise`` / fused depthwise kernels on ``pallas``).  Both
backends degrade identically: the direct path (pointwise 1x1, taps
mismatch, non-profitable lowerings — strided/grouped shapes are first
rewritten by ``repro.api.lowering``) runs XLA's native convolution
(grouped/depthwise via ``feature_group_count``) — already optimal there,
so the Pallas backend deliberately reuses it rather than shipping a worse
kernel.  The registry is open so future backends (GPU pallas, sharded,
batched serving) plug in via :func:`register_backend` without touching
call sites.

Each backend runs the datapath it chose under
``jax.named_scope("plan.<datapath>")`` (``fused``, ``staged``, ``direct``
or ``reference``), so a device trace names the datapath of every conv
op, a resilience fallback level included.  The scope is HLO metadata
only.  A fused launch traced into a program also appends one
buffer-only ``kernels.fused_grouping`` record (:mod:`repro.tracing`)
with the grouping the kernel resolves: ``imgs``, ``rows``, ``cols`` and
``grid_steps``; an eager launch records nothing.
"""
from __future__ import annotations

import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro import faults, tracing
from repro.core import conv2d as c2d
import repro.quant.fake_quant as fq


def _add_bias(y: jnp.ndarray, bias) -> jnp.ndarray:
    return y if bias is None else y + bias


def _scope(datapath: str):
    return jax.named_scope(f"plan.{datapath}")


def _check_hook_supported(plan, elementwise_hook, prep) -> None:
    if elementwise_hook is None:
        return
    if plan.algorithm is None:
        raise ValueError(
            "elementwise_hook requires the fast path; this plan resolved "
            f"to direct ({plan.spec})")
    if prep.quantized:
        raise ValueError("elementwise_hook cannot be combined with "
                         "static-int8 prepared weights")


def _direct(plan, x, prep, bias) -> jnp.ndarray:
    spec = plan.spec
    if spec.rank == 1:
        return _add_bias(
            c2d.conv1d_depthwise_causal_direct(x, prep.w), bias)
    # grouped / depthwise run through lax's feature_group_count; depthwise
    # derives the count from the weight tensor (R, R, 1, C) rather than
    # the spec so shard-local slices under the SPMD backend stay correct
    fgc = prep.w.shape[-1] if spec.depthwise else spec.groups
    y = jax.lax.conv_general_dilated(
        x, prep.w.astype(x.dtype), (spec.stride, spec.stride), spec.padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=fgc)
    return _add_bias(y, bias)


class ReferenceBackend:
    """Portable jnp path (the oracle); full hook support."""

    name = "reference"

    def apply(self, plan, x, prep, *, bias=None, elementwise_hook=None):
        faults.maybe_fault(faults.APPLY_REFERENCE, detail=plan)
        _check_hook_supported(plan, elementwise_hook, prep)
        if plan.algorithm is None:
            with _scope("direct"):
                return _direct(plan, x, prep, bias)
        with _scope("reference"):
            return self._fast(plan, x, prep, bias, elementwise_hook)

    @staticmethod
    def _fast(plan, x, prep, bias, elementwise_hook):
        algo = plan.algorithm
        if plan.spec.rank == 1:
            if elementwise_hook is not None:
                raise NotImplementedError(
                    "elementwise_hook is not supported on the rank-1 "
                    "depthwise fast path")
            return _add_bias(c2d.fastconv1d_depthwise_causal_pretransformed(
                x, prep.tw, algo), bias)
        tx, geom = c2d.transform_input_2d(x, algo, plan.spec.padding)
        tw = prep.tw
        if prep.quantized:
            # static-int8 simulation with the same scales/integer grid as
            # the Pallas datapath: quantize tx with the calibrated
            # frequency scales (the kernels' reciprocal multiply), use the
            # offline-quantized weights.
            qc = plan.spec.quant
            s_act = prep.act_scale[None, None, None, :, :, None]
            inv = c2d.reciprocal_scale(prep.act_scale)
            tx = fq.dequantize(c2d.quantize_slab(
                tx, inv[None, None, None, :, :, None],
                fq.qmax_for_bits(qc.bits_act)), s_act)
            tw = (prep.wq.astype(jnp.float32).reshape(tw.shape)
                  * prep.w_scale[:, :, None, :]).astype(tx.dtype)
        elif elementwise_hook is not None:
            tx, tw = elementwise_hook(tx, tw)
        if plan.spec.depthwise:
            # 2-D depthwise: no channel contraction — the element-wise
            # stage is a true transform-domain elementwise product
            # (tw (t, t, 1, C) broadcast over batch x tiles)
            ty = tx * tw[None, None, None, :, :, 0, :].astype(tx.dtype)
        else:
            ty = c2d.transform_domain_matmul(tx, tw)
        return _add_bias(c2d.inverse_transform_2d(ty, algo, geom), bias)


class PallasBackend:
    """``repro.kernels`` datapath; static precision, no hooks.

    The int8 path defaults to the fused single-``pallas_call`` kernel
    (``repro.kernels.sfc_fused``) — the transform-domain tensor never
    touches HBM.  A plan carrying a measured ``KernelConfig`` (from
    ``repro.api.tuning``) can instead select the staged three-kernel
    pipeline, override the block sizes, batch multiple tile-rows per grid
    step (``rows_per_step``), or DMA-pipeline the input strip reads
    (``double_buffer``).
    """

    name = "pallas"
    # real int8 x int8 -> int32 accumulation: the planner runs the
    # repro.analysis.ranges overflow pre-flight against this backend
    # (the reference backend fake-quantizes in f32 and cannot wrap).
    integer_datapath = True

    def apply(self, plan, x, prep, *, bias=None, elementwise_hook=None):
        if elementwise_hook is not None:
            raise ValueError(
                "the pallas backend takes no elementwise_hook; bake "
                "quantization into the plan (spec.quant + calibrated "
                "prepare_weights) or use backend='reference'")
        if plan.algorithm is None or plan.spec.rank == 1:
            # no Pallas kernels for these; the reference impls are optimal
            # (XLA native conv) or trivially bandwidth-bound.
            return _REFERENCE.apply(plan, x, prep, bias=bias)
        if not prep.quantized:
            with _scope("staged"):
                return _add_bias(self._fp(plan, x, prep), bias)
        from repro.api import tuning
        cfg = plan.config or tuning.DEFAULT_FUSED
        with _scope(cfg.datapath):
            if cfg.datapath == "staged":
                y = self._staged_int8(plan, x, prep, cfg)
            else:
                y = self._fused_int8(plan, x, prep, cfg)
            return _add_bias(y, bias)

    @staticmethod
    def _staged_int8(plan, x, prep, cfg):
        from repro.kernels import ops
        faults.maybe_fault(faults.APPLY_STAGED, detail=plan)
        bits = plan.spec.quant.bits_act
        if plan.spec.depthwise:
            y = ops.quantized_fastconv2d_depthwise(
                x, prep.wq, prep.act_scale, prep.w_scale, plan.algorithm,
                padding=plan.spec.padding, bits=bits,
                interpret=plan.interpret, tile_block=cfg.tile_block,
                chan_block=cfg.chan_block)
        else:
            y = ops.quantized_fastconv2d(
                x, prep.wq, prep.act_scale, prep.w_scale, plan.algorithm,
                padding=plan.spec.padding, bits=bits,
                interpret=plan.interpret, k_block=cfg.k_block,
                tile_block=cfg.tile_block, chan_block=cfg.chan_block)
        return faults.maybe_corrupt(faults.APPLY_STAGED, y, detail=plan)

    @staticmethod
    def _fused_int8(plan, x, prep, cfg):
        from repro.kernels.sfc_fused import fused_geometry, sfc_fused_conv2d
        faults.maybe_fault(faults.APPLY_FUSED, detail=plan)
        launch = dict(padding=plan.spec.padding,
                      depthwise=plan.spec.depthwise, k_block=cfg.k_block,
                      cout_block=cfg.cout_block,
                      rows_per_step=cfg.rows_per_step,
                      double_buffer=cfg.double_buffer)
        if isinstance(x, jax.core.Tracer):
            # one record per launch traced into a program, made here: the
            # kernel's own jit traces a repeated shape only once
            g = fused_geometry(plan.algorithm, *x.shape,
                               prep.wq.shape[2], **launch)
            now = time.perf_counter()
            tracing.record("kernels.fused_grouping", now, now, imgs=g.imgs,
                           rows=g.rows, cols=g.cols,
                           grid_steps=g.grid_steps)
        y = sfc_fused_conv2d(
            x, prep.wq, prep.act_scale, prep.w_scale, plan.algorithm,
            bits=plan.spec.quant.bits_act, interpret=plan.interpret,
            **launch)
        return faults.maybe_corrupt(faults.APPLY_FUSED, y, detail=plan)

    @staticmethod
    def _fp(plan, x, prep):
        from repro.kernels import ops
        from repro.kernels.sfc_inverse import sfc_inverse
        from repro.kernels.sfc_transform import sfc_transform
        algo = plan.algorithm
        tiles, geom = ops.extract_tiles(x, algo, plan.spec.padding)
        tx = sfc_transform(tiles, algo, interpret=plan.interpret)
        if plan.spec.depthwise:
            # transform-domain elementwise stage (tw (t, t, 1, C))
            ty = tx * prep.tw[:, :, 0, None, :].astype(x.dtype)
        else:
            ty = jnp.einsum("tunc,tuco->tuno", tx, prep.tw.astype(x.dtype),
                            precision=jax.lax.Precision.HIGHEST)
        y_tiles = sfc_inverse(ty, algo, interpret=plan.interpret)
        return ops.untile(y_tiles, algo, geom)


_REFERENCE = ReferenceBackend()
_BACKENDS: Dict[str, object] = {
    "reference": _REFERENCE,
    "pallas": PallasBackend(),
}


_SPMD_IMPORT_ERROR: Optional[ImportError] = None


def _register_spmd() -> None:
    # conv_spmd keeps its repro.api imports lazy (either side may load
    # first); mesh resolution stays lazy too — importing repro.api must
    # not touch jax device state.  When THIS import lands inside
    # conv_spmd's own import chain (e.g. `import repro.distributed` ->
    # sharding -> configs -> CNNConfig validation -> repro.api), the
    # module is only partially initialized — skip now and let
    # get_backend/list_backends finish the registration on first lookup,
    # by which point the cycle has resolved.  The exception is kept so a
    # GENUINE import failure (not the cycle) still surfaces: the lazy
    # retry fails again and get_backend chains it into its KeyError.
    global _SPMD_IMPORT_ERROR
    try:
        from repro.distributed.conv_spmd import SpmdPallasBackend
    except ImportError as e:
        _SPMD_IMPORT_ERROR = e
        return
    _SPMD_IMPORT_ERROR = None
    _BACKENDS.setdefault("pallas_spmd", SpmdPallasBackend())


_register_spmd()


def register_backend(name: str, backend, overwrite: bool = False) -> None:
    """Add (or with ``overwrite``, replace) an execution backend.

    Registration invalidates memoized plans: a ``ConvPlan`` records only
    the backend *name*, but its kernel config and prepared-weight cache
    were resolved against whatever object held that name at planning time
    (an overwritten backend may shard or place weights differently).
    """
    if name in _BACKENDS and not overwrite:
        raise ValueError(f"backend {name!r} already registered")
    _BACKENDS[name] = backend
    from repro.api import planner       # late: avoids import cycle
    planner.invalidate_plan_cache()


def get_backend(name: str):
    if name not in _BACKENDS and name == "pallas_spmd":
        _register_spmd()               # deferred past an import cycle
        if name not in _BACKENDS:
            # not the cycle: a real import failure — keep its traceback
            raise KeyError(
                "backend 'pallas_spmd' failed to register; see the "
                "chained ImportError") from _SPMD_IMPORT_ERROR
    try:
        return _BACKENDS[name]
    except KeyError:
        raise KeyError(f"unknown backend {name!r}; "
                       f"registered: {sorted(_BACKENDS)}") from None


def list_backends():
    if "pallas_spmd" not in _BACKENDS:
        _register_spmd()
    return tuple(sorted(_BACKENDS))
