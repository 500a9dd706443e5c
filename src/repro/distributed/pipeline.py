"""Pipeline parallelism over a mesh axis (GPipe schedule, shard_map).

Maps a homogeneous layer stack onto ``n_stages`` groups along a mesh axis
(the 'pod' axis in the multi-pod mesh — an alternative to treating pods as
extra data parallelism; inter-pod links carry only (micro_batch, seq, d)
activations once per microbatch per step, which is what makes PP the right
choice when inter-pod bandwidth << intra-pod bandwidth).

``pipeline_apply`` runs the classic GPipe fill/drain schedule with
``collective_permute`` hops between neighbouring stages:

    tick t: stage s processes microbatch (t - s) if 0 <= t-s < M

Activations enter at stage 0, exit at stage S-1, and are returned to every
device with a final broadcast-style psum (masked), so the caller can
compute the loss uniformly.  Correctness is tested against the sequential
stack in tests/test_pipeline.py.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def stack_params_for_stages(params_stacked, n_stages: int):
    """(L, ...) stacked layer params -> (n_stages, L/S, ...)."""
    def reshape(a):
        L = a.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return a.reshape((n_stages, L // n_stages) + a.shape[1:])
    return jax.tree_util.tree_map(reshape, params_stacked)


def pipeline_apply(stage_fn: Callable, params_staged, x: jnp.ndarray,
                   n_micro: int, mesh: Mesh, axis: str = "stage"
                   ) -> jnp.ndarray:
    """Run x (B, ...) through S pipeline stages with M microbatches.

    stage_fn(stage_params, x_micro) -> x_micro  (the per-stage layer scan);
    params_staged leaves have leading dim S (sharded over ``axis``).
    """
    S = mesh.shape[axis]
    B = x.shape[0]
    assert B % n_micro == 0
    mb = B // n_micro
    x_micro = x.reshape((n_micro, mb) + x.shape[1:])

    param_specs = jax.tree_util.tree_map(
        lambda a: P(axis, *([None] * (a.ndim - 1))), params_staged)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(param_specs, P(*([None] * (x_micro.ndim)))),
        out_specs=P(*([None] * x_micro.ndim)),
        check_vma=False)
    def run(params_local, xm):
        stage = jax.lax.axis_index(axis)
        sp = jax.tree_util.tree_map(lambda a: a[0], params_local)
        buf = jnp.zeros_like(xm[0])              # inter-stage register
        outs = jnp.zeros_like(xm)
        fwd_perm = [(i, i + 1) for i in range(S - 1)]

        def tick(t, carry):
            buf, outs = carry
            micro_idx = t - stage
            active = (micro_idx >= 0) & (micro_idx < n_micro)
            # stage 0 reads its microbatch from the input stream
            inject = jax.lax.dynamic_index_in_dim(
                xm, jnp.clip(micro_idx, 0, n_micro - 1), 0, keepdims=False)
            inp = jnp.where(stage == 0, inject, buf)
            out = stage_fn(sp, inp)
            out = jnp.where(active, out, buf)
            # last stage records its finished microbatch
            record = (stage == S - 1) & active
            outs = jax.lax.dynamic_update_index_in_dim(
                outs,
                jnp.where(record, out,
                          jax.lax.dynamic_index_in_dim(
                              outs, jnp.clip(micro_idx, 0, n_micro - 1), 0,
                              keepdims=False)),
                jnp.clip(micro_idx, 0, n_micro - 1), 0)
            # ship activations to the next stage
            buf_next = jax.lax.ppermute(out, axis, fwd_perm)
            return (buf_next, outs)

        buf, outs = jax.lax.fori_loop(0, n_micro + S - 1, tick, (buf, outs))
        # broadcast final outputs from the last stage to all stages
        mask = (stage == S - 1).astype(outs.dtype)
        outs = jax.lax.psum(outs * mask, axis)
        return outs

    y = run(params_staged, x_micro)
    return y.reshape((B,) + y.shape[2:])
