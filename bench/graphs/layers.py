"""The per-layer helper both families share: make weights from the seed,
then plan, calibrate, prepare and apply a conv layer through ``repro.api``.

A layer is a dict: ``name``, ``kernel``, ``stride``, ``cin``, ``cout``,
``h``/``w`` (its input extent), ``algo`` (the ``plan()`` request) and,
optionally, ``groups`` (default 1; ``cin`` and ``cout`` both divide by
it).  Each group convolves ``cin // groups`` input channels into
``cout // groups`` outputs, and the weights are ``lax``'s grouped HWIO,
``(kernel, kernel, cin // groups, cout)``.  A layer is depthwise when
``groups == cin == cout`` (more than one channel), which is read from
the shape: there is no separate key for it.

Prepared weights cross ``jax.jit`` as plain dicts of arrays
(:func:`prepared_arrays` / :func:`apply`), so that one jitted call
prepares every layer and the forward takes them as arguments rather than
as constants baked into the program.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from bench.work import groups
from repro.api import ConvSpec, plan
from repro.api.lowering import CompositePrepared
from repro.api.plan import PreparedWeights
from repro.api.tuning import calibrate_act_scale
from repro.quant.fake_quant import QuantConfig


def seed_key(seed: int):
    """A PRNG key that keeps every bit of a seed of any size."""
    key = jax.random.PRNGKey(0)
    for word in (seed >> 64, (seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF):
        key = jax.random.fold_in(key, word)
    return key


def quant_config(q: Dict) -> QuantConfig:
    return QuantConfig(q["bits_act"], q["bits_weight"],
                       q["act_granularity"], q["weight_granularity"])


def is_depthwise(layer: Dict) -> bool:
    return 1 < groups(layer) == layer["cin"] == layer["cout"]


def make_params(key, layers: Sequence[Dict], head: Optional[Dict] = None):
    """He-normal conv weights and zero biases for ``layers`` (layer i from
    ``fold_in(key, i)``, so a layer's weights do not depend on which other
    layers are made), plus a dense head ``{"cin", "cout"}`` if given.
    One jitted call, the key an argument: the program is the same for
    every seed and comes from the compile cache."""
    def make(key):
        p = {}
        for l in layers:
            cin = l["cin"] // groups(l)
            fan = l["kernel"] ** 2 * cin
            w = jax.random.normal(jax.random.fold_in(key, l["index"]),
                                  (l["kernel"], l["kernel"], cin,
                                   l["cout"]), jnp.float32)
            p[l["name"]] = {"w": w * jnp.sqrt(2.0 / fan),
                            "b": jnp.zeros((l["cout"],), jnp.float32)}
        if head is not None:
            w = jax.random.normal(jax.random.fold_in(key, 10_000),
                                  (head["cin"], head["cout"]), jnp.float32)
            p["fc"] = {"w": w * jnp.sqrt(1.0 / head["cin"]),
                       "b": jnp.zeros((head["cout"],), jnp.float32)}
        return p
    return jax.jit(make)(key)


def make_images(key, shapes: Sequence[tuple]) -> List[jax.Array]:
    """Standard-normal f32 arrays of the given shapes, in one jitted call."""
    def make(key):
        return [jax.random.normal(jax.random.fold_in(key, i), s, jnp.float32)
                for i, s in enumerate(shapes)]
    return jax.jit(make)(key)


def plan_layer(layer: Dict, quant: QuantConfig):
    """The layer's plan: a depthwise spec for a depthwise layer, a grouped
    one for any other with ``groups`` > 1."""
    k, g = layer["kernel"], groups(layer)
    x_shape = (1, layer["h"], layer["w"], layer["cin"])
    w_shape = (k, k, layer["cin"] // g, layer["cout"])
    if is_depthwise(layer):
        spec = ConvSpec.for_conv2d_depthwise(
            x_shape, w_shape, stride=layer["stride"], quant=quant)
    else:
        spec = ConvSpec.for_conv2d(x_shape, w_shape, stride=layer["stride"],
                                   groups=g, quant=quant)
    return plan(spec, backend="pallas", algo=layer["algo"])


def act_scale(p, x):
    """Calibrated activation scales of plan ``p`` on its input ``x``
    (per sub-problem for a lowered plan, None for a direct one)."""
    if p.path == "lowered":
        return p.calibrate(x)
    if p.path == "fast":
        return calibrate_act_scale(x, p.algorithm, p.spec.quant,
                                   p.spec.padding)
    return None


def _arrays(prep):
    if isinstance(prep, CompositePrepared):
        return {"subs": [_arrays(s) for s in prep.subs]}
    if prep.wq is None:
        return {"w": prep.w}
    return {"wq": prep.wq, "w_scale": prep.w_scale,
            "act_scale": prep.act_scale}


def _prepared(a):
    if "subs" in a:
        return CompositePrepared(w=None,
                                 subs=tuple(_prepared(s) for s in a["subs"]))
    if "w" in a:
        return PreparedWeights(w=a["w"])
    return PreparedWeights(w=None, wq=a["wq"], w_scale=a["w_scale"],
                           act_scale=a["act_scale"])


def prepared_arrays(p, w, x_calib):
    """``p.prepare_weights`` with scales calibrated on ``x_calib``, as a
    dict of arrays (traceable: call it under ``jax.jit``)."""
    return _arrays(p.prepare_weights(w, act_scale=act_scale(p, x_calib)))


def apply(p, arrays, x, bias):
    """``p.apply`` on prepared weights given as :func:`prepared_arrays`."""
    return p.apply(x, _prepared(arrays), bias=bias)


def conv_layers(family, cfg: Dict) -> List[Dict]:
    """The family's conv layers, each with its position ``index``; a layer
    whose channels do not divide by its ``groups`` raises."""
    out = family.layers(cfg)
    for i, l in enumerate(out):
        g = groups(l)
        if g < 1 or l["cin"] % g or l["cout"] % g:
            raise ValueError(f"layer {l['name']}: cin {l['cin']} and cout "
                             f"{l['cout']} must both divide by groups {g}")
        l["index"] = i
    return out
