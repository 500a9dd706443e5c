#!/usr/bin/env python3
"""Bring-up check: the int8 SFC conv path runs on a TPU through its entry
points, at the published widths of VGG-16 at 224x224.

    python3 chip_smoke.py              # one chip: phases (a), (b), (c)
    python3 chip_smoke.py --chips 4    # four chips: phase (d) only

Phases, all in this one process:

  (a) the 13-conv VGG-16 stack at 224x224, batch 8, through
      ``plan(spec, backend="pallas", algo="sfc6_6")`` with int8
      ``INT8_FREQ`` and per-layer ``calibrate_act_scale`` scales, ReLU and
      2x2 max-pool between stages.  Each layer's pallas output must match
      the ``reference`` backend's int8 simulation on the same prepared
      weights within ``repro.testing.DEFAULT_TOL``; the final features must
      stay within the int8 envelope of an f32 ``lax`` stack at HIGHEST;
  (b) two ResNet-18 layers that the planner lowers or routes specially:
      the stride-2 transition s1tos2 (56x56, 64->128, polyphase composite)
      and the 3x3 depthwise layer (28x28, 256 channels), same check;
  (c) a ``repro.serve.Engine`` over the VGG layer 56x56 256->256 (warm
      compile, rounded batches, EDF) answering 32 seeded requests, each
      bit-identical to a per-request apply;
  (d) ``--chips 4``: the VGG-16 stack through ``pallas_spmd`` on a (4, 1)
      data mesh and a (1, 4) model mesh, bit-identical to single-device
      ``pallas`` on the first chip.

Every shape is compiled (warmed) before anything is checked.  Any
``resilience_apply_failure`` or ``resilience_fallback_*`` counter fails the
run: a fallback would hide a kernel that does not run on the chip.  The
script refuses to run without a TPU.  Wall-clock times it prints are
informative only, not a benchmark.  The last line of standard output is
one JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import ConvSpec, plan, resilience  # noqa: E402
from repro.api.tuning import calibrate_act_scale  # noqa: E402
from repro.configs.resnet18 import VGG16  # noqa: E402
from repro.models.cnn import init_vgg  # noqa: E402
from repro.quant.fake_quant import INT8_FREQ  # noqa: E402
from repro.testing import DEFAULT_TOL, calibrated_prep  # noqa: E402

ALGO = "sfc6_6"
# relative L2 error of the int8 stack against f32, the envelope the CPU
# tests accept for an int8 SFC network (tests/test_cnn.py)
INT8_REL_ENVELOPE = 0.15


def log(name: str, value) -> None:
    print(f"{name}: {value}", flush=True)


def _relu_pool(h, pool: bool):
    h = jax.nn.relu(h)
    if pool:
        h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                  (1, 2, 2, 1), (1, 2, 2, 1), "SAME")
    return h


def vgg_layers(cfg=VGG16, seed: int = 0):
    """[(name, w, pool_after)] of the VGG stack, He-initialised from
    ``seed`` by the repo's own ``init_vgg``."""
    params = init_vgg(jax.random.PRNGKey(seed), cfg)
    out = []
    for si, n_convs in enumerate(cfg.stages):
        for ci in range(n_convs):
            out.append((f"s{si}c{ci}", params[f"s{si}c{ci}"]["w"],
                        ci == n_convs - 1))
    return out


def _max_err(a, b) -> float:
    return float(jnp.max(jnp.abs(a - b)))


def _check_close(name: str, y_pal, y_ref) -> float:
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=DEFAULT_TOL, atol=DEFAULT_TOL,
                               err_msg=f"{name}: pallas vs reference int8")
    return _max_err(y_pal, y_ref)


# ---------------------------------------------------------------------------
# (a) VGG-16
# ---------------------------------------------------------------------------
def prepare_vgg(layers, x0, *, backend: str = "pallas",
                reference: bool = True):
    """Plan, calibrate and prepare every layer on the seeded batch ``x0``,
    running the stack once (which compiles every layer, and with
    ``reference`` the reference simulation too).  Returns the per-layer
    (name, plan, reference plan, prep, pool) list."""
    stack = []
    h = x0
    for name, w, pool in layers:
        spec = ConvSpec.for_conv2d(h.shape, w.shape, quant=INT8_FREQ)
        p = plan(spec, backend=backend, algo=ALGO)
        p_ref = plan(spec, backend="reference", algo=ALGO)
        act = calibrate_act_scale(h, p.algorithm, spec.quant)
        prep = p.prepare_weights(w, act_scale=act)
        y = p.apply(h, prep)
        if reference:
            p_ref.apply(h, prep).block_until_ready()
        stack.append((name, p, p_ref, prep, pool))
        h = _relu_pool(y, pool)
    h.block_until_ready()
    return stack


def run_stack(stack, x0, *, check: bool):
    """One forward pass; with ``check`` every layer is compared with the
    reference simulation on the same input.  Returns (features,
    {layer: max abs error})."""
    errs = {}
    h = x0
    for name, p, p_ref, prep, pool in stack:
        y = p.apply(h, prep)
        if check:
            errs[name] = _check_close(name, y, p_ref.apply(h, prep))
        h = _relu_pool(y, pool)
    return h.block_until_ready(), errs


def f32_stack(layers):
    """The plain f32 reference network: ``lax`` convs at HIGHEST."""
    ws = [w for _, w, _ in layers]
    pools = [pool for _, _, pool in layers]

    @jax.jit
    def fwd(x):
        h = x
        for w, pool in zip(ws, pools):
            h = jax.lax.conv_general_dilated(
                h, w, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=jax.lax.Precision.HIGHEST)
            h = _relu_pool(h, pool)
        return h
    return fwd


def rel_err(y, y_ref) -> float:
    return float(jnp.linalg.norm(y - y_ref) / jnp.linalg.norm(y_ref))


def prepare_vgg_phase(*, batch: int = 8, image: int = 224, cfg=VGG16,
                      seed: int = 0):
    layers = vgg_layers(cfg, seed)
    x0 = jax.random.normal(jax.random.PRNGKey(seed + 1),
                           (batch, image, image, 3), jnp.float32)
    stack = prepare_vgg(layers, x0)
    fwd32 = f32_stack(layers)
    fwd32(x0).block_until_ready()

    def check():
        t0 = time.perf_counter()
        feats, _ = run_stack(stack, x0, check=False)
        wall = time.perf_counter() - t0
        feats, errs = run_stack(stack, x0, check=True)
        rel = rel_err(feats, fwd32(x0))
        assert rel < INT8_REL_ENVELOPE, \
            f"VGG int8 vs f32 relative error {rel} >= {INT8_REL_ENVELOPE}"
        return {"vgg_layer_max_err": errs, "vgg_rel_err_vs_f32": rel,
                "vgg_forward_wall_s_informative": wall}
    return check


# ---------------------------------------------------------------------------
# (b) ResNet-18 lowered layers
# ---------------------------------------------------------------------------
RESNET_LAYERS = (
    # (name, HxW, C_in, C_out, stride, depthwise, expected plan path)
    ("s1tos2", 56, 64, 128, 2, False, "lowered"),
    ("dw3x3", 28, 256, 256, 1, True, "fast"),
)


def prepare_resnet_phase(*, batch: int = 8, layers=RESNET_LAYERS,
                         seed: int = 2):
    cases = []
    for i, (name, hw, cin, cout, stride, dw, path) in enumerate(layers):
        kx, kw = jax.random.split(jax.random.PRNGKey(seed + i))
        x = jax.random.normal(kx, (batch, hw, hw, cin), jnp.float32)
        fan = 9 * (1 if dw else cin)
        w = jax.random.normal(kw, (3, 3, 1 if dw else cin, cout),
                              jnp.float32) * np.sqrt(2.0 / fan)
        if dw:
            spec = ConvSpec.for_conv2d_depthwise(x.shape, w.shape,
                                                 quant=INT8_FREQ)
        else:
            spec = ConvSpec.for_conv2d(x.shape, w.shape, stride=stride,
                                       quant=INT8_FREQ)
        p_ref, p_pal, prep = calibrated_prep(x, w, spec, ALGO)
        assert p_pal.path == path, f"{name}: plan path {p_pal.path} != {path}"
        p_pal.apply(x, prep).block_until_ready()
        p_ref.apply(x, prep).block_until_ready()
        cases.append((name, p_pal, p_ref, x, prep))

    def check():
        return {"resnet_layer_max_err": {
            name: _check_close(name, p.apply(x, prep), p_ref.apply(x, prep))
            for name, p, p_ref, x, prep in cases}}
    return check


# ---------------------------------------------------------------------------
# (c) serving
# ---------------------------------------------------------------------------
def prepare_serve_phase(*, hw: int = 56, channels: int = 256,
                        n_requests: int = 32, max_batch: int = 8,
                        seed: int = 3):
    from repro.serve import BucketTable, Engine
    from repro.serve.batcher import SchedulerPolicy
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    w = jax.random.normal(kw, (3, 3, channels, channels), jnp.float32) \
        * np.sqrt(2.0 / (9 * channels))
    xs = list(jax.random.normal(kx, (n_requests, hw, hw, channels),
                                jnp.float32))
    table = BucketTable.for_workload([(hw, hw)], kernel_size=3,
                                     in_channels=channels,
                                     out_channels=channels, quant=INT8_FREQ)
    bucket = table.buckets[0]
    eng = Engine(w, table, algo=ALGO, max_batch=max_batch,
                 round_batches=True, warm_compile=True,
                 scheduler=SchedulerPolicy(kind="edf"))
    # the engine's own warmed (plan, prepared weights) for the bucket
    p, prep = eng.cache.get(bucket.spec, w, backend=eng.backend, algo=ALGO,
                            key=("serve", bucket.name))
    p.apply(xs[0][None], prep).block_until_ready()

    def check():
        t0 = time.perf_counter()
        eng.start()
        futures = [eng.submit(x) for x in xs]
        assert eng.drain(timeout=600), "engine did not drain"
        wall = time.perf_counter() - t0
        eng.stop(raise_on_error=True)
        for i, (f, x) in enumerate(zip(futures, xs)):
            y = f.result().y
            want = p.apply(x[None], prep)[0]
            assert np.array_equal(np.asarray(y), np.asarray(want)), \
                f"request {i}: engine result differs from a per-request apply"
        snap = eng.snapshot()
        assert snap["loop_errors"] == 0, snap["last_loop_error"]
        bad = fallback_events(snap["counters"])
        assert not bad, f"engine resilience events: {bad}"
        return {"requests_served": len(futures),
                "serve_loop_errors": snap["loop_errors"],
                "serve_wall_s_informative": wall}
    return check


# ---------------------------------------------------------------------------
# (d) four chips: pallas_spmd vs single-device pallas
# ---------------------------------------------------------------------------
def prepare_spmd_phase(*, n_chips: int = 4, batch: int = 8, image: int = 224,
                       cfg=VGG16, seed: int = 0):
    from repro.api import backends
    from repro.launch.mesh import make_forced_host_mesh
    devices = jax.devices()[:n_chips]
    layers = vgg_layers(cfg, seed)
    # uncommitted, so that shard_map may place it on each mesh
    x0 = jax.random.normal(jax.random.PRNGKey(seed + 1),
                           (batch, image, image, 3), jnp.float32)
    with jax.default_device(devices[0]):
        single = prepare_vgg(layers, x0, reference=False)
    spmd = backends.get_backend("pallas_spmd")
    meshes = {"data4x1": (n_chips, 1), "model1x4": (1, n_chips)}
    sharded = {}
    for name, shape in meshes.items():
        spmd.set_mesh(make_forced_host_mesh(shape))
        sharded[name] = (shape, prepare_vgg(layers, x0, reference=False,
                                            backend="pallas_spmd"))

    def check():
        with jax.default_device(devices[0]):
            want, _ = run_stack(single, x0, check=False)
        want = np.asarray(want)
        out = {}
        for name, (shape, stack) in sharded.items():
            spmd.set_mesh(make_forced_host_mesh(shape))
            got, _ = run_stack(stack, x0, check=False)
            n_dev = len(got.sharding.device_set)
            assert n_dev == n_chips, \
                f"pallas_spmd {name}: output on {n_dev} of {n_chips} devices"
            assert np.array_equal(np.asarray(got), want), \
                f"pallas_spmd {name} is not bit-identical to pallas"
            out[f"spmd_{name}_bit_identical"] = True
            out[f"spmd_{name}_output_devices"] = n_dev
        return out
    return check


# ---------------------------------------------------------------------------
def fallback_events(counters=None) -> dict:
    """The failure and fallback counters of the degradation chain
    (process-wide ``resilience.stats()`` unless ``counters`` is given)."""
    counters = resilience.stats() if counters is None else counters
    return {k: v for k, v in counters.items()
            if v and (k == "resilience_apply_failure"
                      or k.startswith("resilience_fallback_"))}


def last_line(device_count: int) -> str:
    d = jax.devices()[0]
    return json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": device_count}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the pallas_spmd phase on four chips")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    from repro.runtime import use_compilation_cache
    log("device_kind", devices[0].device_kind)
    log("device_count_used", args.chips)
    log("compile_cache_dir", use_compilation_cache(HERE))

    t0 = time.perf_counter()
    if args.chips == 4:
        checks = [prepare_spmd_phase()]
    else:
        checks = [prepare_vgg_phase(), prepare_resnet_phase(),
                  prepare_serve_phase()]
    log("compile_and_warm_s", round(time.perf_counter() - t0, 3))

    results = {}
    for check in checks:
        results.update(check())
    for k, v in results.items():
        log(k, v)
    events = fallback_events()
    log("resilience_fallback_counters", events)
    assert not events, f"the degradation chain absorbed failures: {events}"
    print(last_line(args.chips), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
