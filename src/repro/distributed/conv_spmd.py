"""Sharded SPMD conv backend: ``shard_map`` over the production mesh.

``pallas_spmd`` scales the single-device Pallas datapath across a device
mesh without touching any call site — it is a ``repro.api`` backend like
the others, registered under ``repro.api.register_backend`` and resolved
by name from ``plan(spec, backend="pallas_spmd")``.

Sharding layout (the conv analogue of ``distributed/sharding.py``):

  * batch over ``('pod', 'data')`` — SFC tiling is *halo-free across
    images*: every (L, L) input tile lives entirely inside one image, so
    splitting the batch ships whole images and needs no neighbour
    exchange (unlike spatial partitioning of a convolution, which must
    exchange R-1 boundary rows);
  * C_out over ``'model'`` — transform-domain output channels are
    independent: each shard holds its own (t^2, C_in, C_out/m) int8
    weight block plus the matching per-frequency dequant scales, and the
    fused kernel runs unchanged on the local block.

Both axes compose, and both are **bit-identical** to the single-device
backend: no cross-shard reduction exists anywhere in the datapath (the
C_in contraction stays intact per shard), so not a single float is
accumulated in a different order.

Kernel configs ride the plan through ``shard_map`` unchanged: a
``KernelConfig`` with ``rows_per_step``/``double_buffer`` (the batched,
DMA-pipelined fused grid) executes per shard exactly as on one device —
and ``rows_per_step=None`` auto-resolution sees the *local* batch (the
data axis shrinks B before the kernel wrapper runs), so a sharded small
batch folds whole images per step precisely when the shard, not the
global batch, is small.  Grouping only ever folds divisors of the local
batch, so every data-shard layout remains bit-identical.

Axes that do not divide the corresponding extent are dropped per
:func:`repro.distributed.sharding.sanitize_pspec` — batch-1 decode on a
multi-way data axis, ragged C_out — and that dimension is computed
replicated instead: graceful degradation, never an error.

Lowered (composite) plans need nothing special here: the planner's
lowering pass hands every polyphase/grouped sub-problem to ``plan(...,
backend="pallas_spmd")``, so each sub-plan is its own shard_map-wrapped
apply with its own ``place_prepared`` placement — sub-plans inherit the
shard layout by construction.  2-D depthwise specs shard their single
channel axis over 'model' on input and weights alike (the elementwise
path has no contraction to split).

:meth:`SpmdPallasBackend.place_prepared` is the offline half:
``ConvPlan.prepare_weights`` routes prepared tensors through it, so
``wq``/``w_scale`` (and fp ``tw``) land on the mesh C_out-sharded once,
ahead of traffic, instead of being broadcast at every apply.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.sharding import batch_axes, sanitize_pspec

# NOTE: repro.api imports are late (inside methods) — repro.api.backends
# imports this module at its own bottom to register the backend, so a
# top-level import here would be circular whichever side loads first.


class SpmdPallasBackend:
    """``shard_map``-wrapped Pallas datapath; one mesh per backend object.

    The default mesh is whatever the host exposes
    (``launch.mesh.make_host_mesh``: all devices on 'data', 'model' = 1);
    production launchers and the scale-out benchmarks install an explicit
    mesh with :meth:`set_mesh`.
    """

    name = "pallas_spmd"
    # same int8 x int8 -> int32 datapath as PallasBackend: the planner's
    # overflow pre-flight applies (sharding C_in does not relax the
    # bound — each shard still accumulates its full local contraction,
    # and the psum joins in int32).
    integer_datapath = True

    def __init__(self, mesh: Optional[Mesh] = None):
        self._mesh = mesh

    @property
    def mesh(self) -> Mesh:
        if self._mesh is None:
            from repro.launch.mesh import make_host_mesh
            self._mesh = make_host_mesh()
        return self._mesh

    def set_mesh(self, mesh: Optional[Mesh]) -> None:
        """Install an execution mesh (None re-resolves the host default).

        Invalidates memoized plans: their prepared-weight caches hold
        placements for the previous mesh.
        """
        self._mesh = mesh
        from repro.api import planner
        planner.invalidate_plan_cache()

    # ------------------------------------------------------------------
    # offline: prepared-weight placement (ConvPlan.prepare_weights hook)
    # ------------------------------------------------------------------
    def place_prepared(self, plan, prep):
        """Device-shard prepared weights: C_out over 'model', rest
        replicated.  Non-divisible extents degrade to replication.

        Grouped direct specs stay replicated: slicing C_out across shards
        would misalign the group <-> input-block correspondence of
        ``feature_group_count`` (grouped specs normally never get here —
        the lowering pass splits them into per-group dense sub-plans,
        each of which shards its own C_out/g — only a lowering-rejected
        grouped direct plan lands on this path).  Depthwise shards its
        single channel axis: ``apply`` co-shards the input channels.
        """
        if plan.spec.rank != 2 or plan.spec.groups > 1:
            return prep
        mesh = self.mesh

        def put(a, spec):
            if a is None:
                return None
            s = sanitize_pspec(spec, a.shape, mesh)
            return jax.device_put(a, NamedSharding(mesh, s))

        return dataclasses.replace(
            prep,
            tw=put(prep.tw, P(None, None, None, "model")),
            wq=put(prep.wq, P(None, None, "model")),
            w_scale=put(prep.w_scale, P(None, None, "model")),
            act_scale=put(prep.act_scale, P(None, None)))

    # ------------------------------------------------------------------
    # online: execution
    # ------------------------------------------------------------------
    def apply(self, plan, x, prep, *, bias=None, elementwise_hook=None):
        if elementwise_hook is not None:
            raise ValueError(
                "the pallas_spmd backend takes no elementwise_hook; bake "
                "quantization into the plan (spec.quant + calibrated "
                "prepare_weights) or use backend='reference'")
        from repro.api.backends import get_backend
        from repro.api.plan import PreparedWeights
        inner = get_backend("pallas")
        if plan.spec.rank != 2:
            # rank-1 depthwise: bandwidth-bound reference impl, replicated
            return inner.apply(plan, x, prep, bias=bias)
        mesh = self.mesh
        b_ax = batch_axes(mesh)

        # depthwise: in == out channels, so the channel axis shards over
        # 'model' on BOTH the input and the weights (each shard runs the
        # elementwise path on its channel block — still no cross-shard
        # reduction, still bit-identical).  Grouped direct stays
        # replicated on C_out: a shard slice would misalign
        # feature_group_count's group <-> input-block pairing.
        dw = plan.spec.depthwise
        c_ax = "model" if dw else None
        o_ax = None if plan.spec.groups > 1 else "model"

        operands = {"x": x}
        specs = {"x": P(b_ax, None, None, c_ax)}
        if prep.quantized:
            operands.update(wq=prep.wq, w_scale=prep.w_scale,
                            act_scale=prep.act_scale)
            specs.update(wq=P(None, None, o_ax),
                         w_scale=P(None, None, o_ax),
                         act_scale=P(None, None))
            w_key = "wq"
        elif plan.algorithm is not None:
            operands["tw"] = prep.tw
            specs["tw"] = P(None, None, None, o_ax)
            w_key = "tw"
        else:
            # direct path: HWIO weights; output channels stay independent
            operands["w"] = prep.w
            specs["w"] = P(None, None, None, o_ax)
            w_key = "w"
        if bias is not None:
            operands["bias"] = jnp.asarray(bias)
            specs["bias"] = P(o_ax)
        specs = {k: sanitize_pspec(s, jnp.shape(operands[k]), mesh)
                 for k, s in specs.items()}
        out_spec = P(specs["x"][0], None, None, specs[w_key][-1])

        def _local(ops):
            lp = PreparedWeights(w=ops.get("w"), tw=ops.get("tw"),
                                 wq=ops.get("wq"),
                                 w_scale=ops.get("w_scale"),
                                 act_scale=ops.get("act_scale"))
            return inner.apply(plan, ops["x"], lp, bias=ops.get("bias"))

        # check_vma=False: pallas_call is opaque to shard_map's replication
        # checker; replication of the dropped (non-divisible) axes is
        # guaranteed by construction — every shard sees identical operands.
        return jax.shard_map(_local, mesh=mesh, in_specs=(specs,),
                             out_specs=out_spec, check_vma=False)(operands)
