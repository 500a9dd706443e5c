"""Offline traffic: batches of images through one jitted network forward.

A ring of ``ring`` seeded device-resident input batches feeds the
forward with at most ``in_flight`` forwards enqueued.  The window ends
when the last forward is ready; ``images_per_s`` is every image of every
forward in the window over the window's wall time.

The forward is the program's normal path, one ``jax.jit`` over the
network: per conv ``plan(..., backend="pallas")`` -> ``prepare_weights``
(scales calibrated on the plain f32 reference's activations) ->
``apply``, each under a ``jax.named_scope`` of the layer's name.  Besides
its output it returns every conv layer's output for the batch's first
image, which the check compares layer by layer.

After the window the program's state is freed and the plain f32
reference runs over each ring batch in blocks of ``ref_block`` images.
The forward last run on each ring batch is compared with it: each
image's output (``out_rel_err``, the largest relative L2 error of an
image) and each layer's output for the first image (``layer_rel_err``).
"""
from __future__ import annotations

import collections
import time
from typing import Dict

import jax
import numpy as np

from bench import work
from bench.graphs import layers as L
from bench.graphs import reference as ref


def _rel(a, b) -> np.ndarray:
    """Relative L2 error of each leading-axis slice of ``a`` against ``b``."""
    a = np.asarray(a, np.float64).reshape(a.shape[0], -1)
    b = np.asarray(b, np.float64).reshape(b.shape[0], -1)
    return np.linalg.norm(a - b, axis=1) / np.linalg.norm(b, axis=1)


def reference_fn(cfg: Dict, family, convs):
    """The plain f32 reference network: ``(params, x, record=None) ->
    (out, {layer: output of the first image})``; with ``record`` it also
    fills in each conv's input."""
    by_name = {l["name"]: l for l in convs}

    def reference(p, x, record=None):
        taps = {}

        def conv(name, hh):
            if record is not None:
                record[name] = hh
            layer = by_name[name]
            y = ref.conv(hh, p[name]["w"], p[name]["b"], layer["stride"],
                         work.groups(layer))
            taps[name] = y[:1]
            return y
        out = family.network(cfg, conv, lambda hh: ref.dense(
            hh, p["fc"]["w"], p["fc"]["b"]), x)
        return out, taps
    return reference


def forward_fn(cfg: Dict, family, plans: Dict):
    """The program's network: ``(arrays, biases, fc, x) ->
    (out, {layer: output of the first image})``, each conv under a
    ``jax.named_scope`` of its name."""
    def forward(arrays, biases, fc, x):
        taps = {}

        def conv(name, hh):
            with jax.named_scope(name):
                y = L.apply(plans[name], arrays[name], hh, biases[name])
            taps[name] = y[:1]
            return y
        out = family.network(cfg, conv, lambda hh: ref.dense(
            hh, fc["w"], fc["b"]), x)
        return out, taps
    return forward


def prepare_fn(plans: Dict):
    """``(params, conv inputs) -> prepared arrays`` for every layer."""
    return lambda p, a: {n: L.prepared_arrays(plans[n], p[n]["w"], a[n])
                         for n in plans}


def build(h) -> Dict:
    """Set-up: weights, inputs, calibration, plans, prepared weights and
    the warmed forward.  ``h`` is the harness's run context."""
    cfg, family = h.cfg, h.family
    B, ring = h.traffic["batch"], h.traffic["ring"]
    convs = L.conv_layers(family, cfg)
    key = L.seed_key(h.seed)
    params = L.make_params(jax.random.fold_in(key, 1), convs,
                           family.head(cfg))
    img = (cfg["image_size"], cfg["image_size"], cfg["in_channels"])
    batches = L.make_images(jax.random.fold_in(key, 2),
                            [(B,) + img] * ring
                            + [(cfg["calib_images"],) + img])
    xs, x_calib = batches[:ring], batches[ring]
    reference = reference_fn(cfg, family, convs)

    def conv_inputs(p, x):
        rec = {}
        reference(p, x, rec)
        return rec

    jax.block_until_ready(batches)
    h.phase("weights and inputs")
    quant = L.quant_config(h.quant)
    plans = {l["name"]: L.plan_layer(l, quant) for l in convs}
    h.log("plans: " + ", ".join(f"{n} {p.path} {p.algo_name}"
                                for n, p in plans.items()))
    h.phase("plans")
    acts = jax.jit(conv_inputs)(params, x_calib)
    arrays = jax.block_until_ready(jax.jit(prepare_fn(plans))(params, acts))
    del acts
    h.phase("calibrate and prepare")
    biases = {n: params[n]["b"] for n in plans}
    fc = params.get("fc")
    # lowered once: tracing the Pallas kernels is most of the set-up
    fwd = jax.jit(forward_fn(cfg, family, plans)).lower(
        arrays, biases, fc, xs[0]).compile()
    h.phase("trace, lower and compile (or load) the forward")
    for x in xs[:2]:
        jax.block_until_ready(fwd(arrays, biases, fc, x))
        h.phase("warm forward")
    return dict(convs=convs, plans=plans, params=params, xs=xs,
                run=lambda x: fwd(arrays, biases, fc, x),
                hlo=fwd.as_text, reference=jax.jit(reference))


def window(h, state: Dict) -> Dict:
    """The measured window: returns counts, wall time and the last output
    of each ring slot."""
    B, ring = h.traffic["batch"], h.traffic["ring"]
    in_flight = h.traffic["in_flight"]
    run, xs = state["run"], state["xs"]
    pending = collections.deque()
    last = {}
    done = failed = enqueued = 0
    annotate = jax.profiler.TraceAnnotation

    def retire():
        nonlocal done, failed
        slot, out = pending.popleft()
        try:
            with annotate("bench.block"):
                jax.block_until_ready(out)
            done += B
            last[slot] = out
        except Exception as e:   # a forward that raises counts as failed
            failed += B
            h.log(f"forward failed: {e!r}")

    t0 = time.perf_counter()
    with annotate("bench.window"):
        while time.perf_counter() - t0 < h.seconds:
            slot = enqueued % ring
            try:
                with annotate("bench.forward_enqueue"):
                    pending.append((slot, run(xs[slot])))
            except Exception as e:
                failed += B
                h.log(f"forward failed to enqueue: {e!r}")
            enqueued += 1
            while len(pending) >= in_flight:
                retire()
        while pending:
            retire()
    t1 = time.perf_counter()
    return dict(attempted=enqueued * B, done=done, failed=failed,
                forwards=enqueued, wall_s=t1 - t0, last=last)


def check(h, state: Dict, win: Dict) -> Dict:
    """Compare each ring slot's last output with the plain reference."""
    block = h.cfg["ref_block"]
    out_err, layer_err = 0.0, 0.0
    per_layer = {}
    for slot, (out, taps) in sorted(win["last"].items()):
        x = state["xs"][slot]
        refs, ref_taps = [], None
        for i in range(0, x.shape[0], block):
            r_out, r_taps = state["reference"](state["params"],
                                               x[i:i + block])
            refs.append(np.asarray(r_out))
            if ref_taps is None:
                ref_taps = r_taps
        out_err = max(out_err, float(_rel(out, np.concatenate(refs)).max()))
        for name, t in taps.items():
            e = float(_rel(t, ref_taps[name])[0])
            per_layer[name] = max(per_layer.get(name, 0.0), e)
            layer_err = max(layer_err, e)
    h.log(f"layer_rel_err per layer: {per_layer}")
    limits = h.cfg["limits"]
    return {"out_rel_err": (out_err, limits["out_rel_err"]),
            "layer_rel_err": (layer_err, limits["layer_rel_err"])}


def record(h, state: Dict, win: Dict) -> Dict:
    """What the per-layer readers read, besides the trace."""
    ca = h.cfg["counted_algo"]
    B = h.traffic["batch"]
    ops_per_image = sum(work.counted_ops(l, ca) for l in state["convs"])
    return dict(
        kind="offline", batch=B, forwards=win["forwards"],
        images_per_s=win["done"] / win["wall_s"],
        ops_per_image=ops_per_image,
        layers={l["name"]: dict(l, path=state["plans"][l["name"]].path)
                for l in state["convs"]},
        counted_algo=ca)


def e2e(win: Dict) -> Dict[str, float]:
    return {"images_per_s": win["done"] / win["wall_s"]}
