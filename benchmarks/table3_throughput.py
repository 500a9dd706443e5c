"""Paper Table 3 surrogate: compute-efficiency of the SFC datapath.

The paper's Table 3 is an FPGA synthesis (DSP counts); on TPU the analogue
is (a) the multiplication/BOPs reduction of the transform-domain pipeline
and (b) measured wall-clock of the jitted conv paths on this host (CPU
numbers are indicative only; the roofline analysis in EXPERIMENTS.md covers
the TPU target).  VGG-16's conv stack (all 3x3 stride-1, the paper's pick)
is the workload.

Besides the human-readable log this module emits ``BENCH_conv.json``: a
machine-readable per-layer wall-clock sweep of the five datapaths

  direct  — XLA native convolution, fp32
  staged  — three-kernel Pallas int8 pipeline (transform+quant / tdmm /
            inverse, two HBM round-trips of the transform-domain tensor)
  fused   — single-``pallas_call`` int8 pipeline (``sfc_fused``),
            one tile-row per grid step (``rows_per_step=1``)
  batched — the fused kernel at its default, shape-resolved grouping
            (tile-rows, then whole images, folded per grid step)
  int8    — reference-backend static-int8 simulation (jnp)

plus the ``resnet_lowered`` rows: ResNet-18's stride-2 stem and stage
transitions and a 2-D depthwise conv — the workloads the lowering layer
(``repro.api.lowering``) opened up — each timed direct-vs-``lowered``
(the per-run ``lowered_totals_ms`` ride the trajectory entries).
The perf trajectory is tracked from PR 2 onward (EXPERIMENTS.md §Perf).
The artifact is ACCUMULATED, not overwritten: existing keys written by
other suites (``scaleout``) survive, and every run appends a timestamped,
git-SHA-tagged entry to ``trajectory`` so the CI artifact carries the
cross-PR perf history.  Spatial extents are scaled by
``REPRO_BENCH_SPATIAL_CAP`` (default 28 — interpret-mode Pallas on CPU
makes full 224x224 sweeps impractically slow; channel counts, the
dimension that decides datapath ranking, stay full).
"""
import dataclasses
import datetime
import json
import os
import subprocess

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import ConvSpec, get_algorithm, plan
from repro.api.tuning import (DEFAULT_FUSED, DEFAULT_STAGED, KernelConfig,
                              calibrate_act_scale, time_fn)
from repro.quant import ConvWorkload, bops_reduction, INT8_FREQ

# VGG-16 conv layers (HxW, Cin, Cout) at 224 input — per paper §6.2
VGG_LAYERS = [(224, 3, 64), (224, 64, 64), (112, 64, 128), (112, 128, 128),
              (56, 128, 256), (56, 256, 256), (56, 256, 256),
              (28, 256, 512), (28, 512, 512), (28, 512, 512),
              (14, 512, 512), (14, 512, 512), (14, 512, 512)]

# The workloads the lowering layer opened up (ISSUE 5): ResNet-18's
# stride-2 stem + stage transitions (polyphase onto stride-1 SFC
# sub-convs) and a MobileNet-style 2-D depthwise conv (transform-domain
# elementwise path).  (name, HxW, Cin, Cout, R, stride, depthwise) at 224.
RESNET_LOWERED_LAYERS = [
    ("stem7x7s2", 224, 3, 64, 7, 2, False),
    ("s1tos2", 56, 64, 128, 3, 2, False),
    ("s2tos3", 28, 128, 256, 3, 2, False),
    ("s3tos4", 14, 256, 512, 3, 2, False),
    ("dw3x3", 28, 256, 256, 3, 1, True),
]

BENCH_PATH = os.environ.get("REPRO_BENCH_OUT", "BENCH_conv.json")


# one warmup (compile) call, then mean over reps — the tuner's protocol
_time = time_fn


def _scaled_layers(cap: int):
    """VGG stack with spatial extents capped (channels stay full)."""
    out = []
    for hw, cin, cout in VGG_LAYERS:
        hw_s = max(round(hw * cap / 224), 7) if cap < 224 else hw
        out.append((hw_s, cin, cout))
    return out


def _layer_sweep(layers, algo_name: str, reps: int, log) -> list:
    """Per-layer wall-clock of direct / staged / fused / int8-sim paths."""
    rng = np.random.RandomState(0)
    rows = []
    for hw, cin, cout in layers:
        x = jnp.asarray(rng.randn(1, hw, hw, cin), jnp.float32)
        w = jnp.asarray(rng.randn(3, 3, cin, cout) * 0.1, jnp.float32)
        spec = ConvSpec.for_conv2d(x.shape, w.shape, quant=INT8_FREQ)
        p_direct = plan(spec, algo="direct")
        p_fused = plan(spec, backend="pallas", algo=algo_name)
        p_ref = plan(spec, backend="reference", algo=algo_name)
        act = calibrate_act_scale(x, p_fused.algorithm, spec.quant)
        prep = p_fused.prepare_weights(w, act_scale=act)
        # every path timed under one jax.jit, so the comparison measures
        # the datapath rather than eager dispatch overhead
        fns = {
            "direct": jax.jit(lambda a: p_direct.apply(a, w)),
            "fused": jax.jit(
                lambda a, _p=dataclasses.replace(
                    p_fused, config=KernelConfig(rows_per_step=1)):
                _p.apply(a, prep)),
            "batched": jax.jit(
                lambda a, _p=dataclasses.replace(p_fused,
                                                 config=DEFAULT_FUSED):
                _p.apply(a, prep)),
            "staged": jax.jit(
                lambda a, _p=dataclasses.replace(p_fused,
                                                 config=DEFAULT_STAGED):
                _p.apply(a, prep)),
            "int8": jax.jit(lambda a: p_ref.apply(a, prep)),
        }
        row = {"hw": hw, "cin": cin, "cout": cout}
        for key, fn in fns.items():
            row[f"{key}_ms"] = _time(fn, x, reps=reps) * 1e3
        rows.append(row)
        log(f"layer{hw}x{hw}x{cin}->{cout},"
            f"direct={row['direct_ms']:.2f}ms,"
            f"staged={row['staged_ms']:.2f}ms,"
            f"fused={row['fused_ms']:.2f}ms,"
            f"batched={row['batched_ms']:.2f}ms,"
            f"int8sim={row['int8_ms']:.2f}ms")
    return rows


def _lowered_sweep(cap: int, reps: int, log) -> list:
    """Wall-clock of the lowered datapaths vs strided/grouped direct.

    One row per :data:`RESNET_LOWERED_LAYERS` entry with a ``lowered_ms``
    column: the int8 plan the planner resolves for the workload (polyphase
    composite over fused sub-kernels for stride-2; the transform-domain
    elementwise kernel for depthwise) against the XLA strided direct
    baseline.  ``algo='sfc6_6'`` forces lowering even at reduced bench
    shapes where the BOPs model would keep tiny workloads direct — the
    row's ``path`` records what ``algo='auto'`` would have picked.
    """
    from repro.api.tuning import calibrate_act_scale as _cal
    rng = np.random.RandomState(1)
    rows = []
    for name, hw, cin, cout, r, stride, dw in RESNET_LOWERED_LAYERS:
        hw_s = max(round(hw * cap / 224), 7) if cap < 224 else hw
        x = jnp.asarray(rng.randn(1, hw_s, hw_s, cin), jnp.float32)
        w = jnp.asarray(rng.randn(r, r, 1 if dw else cin, cout) * 0.1,
                        jnp.float32)
        if dw:
            spec = ConvSpec.for_conv2d_depthwise(x.shape, w.shape,
                                                 quant=INT8_FREQ)
        else:
            spec = ConvSpec.for_conv2d(x.shape, w.shape, stride=stride,
                                       quant=INT8_FREQ)
        p_direct = plan(spec, algo="direct")
        p_fast = plan(spec, backend="pallas", algo="sfc6_6")
        if p_fast.path == "lowered":
            prep = p_fast.prepare_weights(w, act_scale=p_fast.calibrate(x))
        else:
            act = _cal(x, p_fast.algorithm, spec.quant, spec.padding)
            prep = p_fast.prepare_weights(w, act_scale=act)
        row = {"layer": name, "hw": hw_s, "cin": cin, "cout": cout,
               "kernel": r, "stride": stride, "depthwise": dw,
               "path": p_fast.path,
               # auto's verdict for the backend actually benchmarked (its
               # tuning-cache entries are keyed per backend)
               "auto_path": plan(spec, backend="pallas", algo="auto").path}
        fns = {
            "direct": jax.jit(lambda a, _p=p_direct: _p.apply(a, w)),
            "lowered": jax.jit(lambda a, _p=p_fast, _pr=prep:
                               _p.apply(a, _pr)),
        }
        for key, fn in fns.items():
            row[f"{key}_ms"] = _time(fn, x, reps=reps) * 1e3
        rows.append(row)
        log(f"lowered {name} {hw_s}x{hw_s}x{cin}->{cout}"
            f"{'dw' if dw else ''}s{stride},"
            f"direct={row['direct_ms']:.2f}ms,"
            f"lowered={row['lowered_ms']:.2f}ms,path={row['path']}")
    return rows


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, check=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__))).stdout.strip()
    except Exception:
        return "unknown"


def run(log=print, bench_path: str = None, reps: int = None,
        spatial_cap: int = None):
    algo = get_algorithm("sfc6_7")
    total_direct_bops = total_sfc_bops = 0.0
    for hw, cin, cout in VGG_LAYERS:
        wl = ConvWorkload(hw, hw, cin, cout, 3)
        total_direct_bops += wl.H * wl.W * wl.C_out * wl.R**2 * wl.C_in
        total_sfc_bops += (wl.H * wl.W * wl.C_out * wl.R**2 * wl.C_in
                           / bops_reduction(wl, algo))
    log(f"vgg16_bops_reduction,{total_direct_bops/total_sfc_bops:.2f}x")

    # per-layer wall-clock sweep of the four datapaths -> BENCH_conv.json
    bench_path = bench_path or BENCH_PATH
    reps = reps or int(os.environ.get("REPRO_BENCH_REPS", "2"))
    spatial_cap = spatial_cap or int(
        os.environ.get("REPRO_BENCH_SPATIAL_CAP", "28"))
    layers = _scaled_layers(spatial_cap)
    rows = _layer_sweep(layers, "sfc6_6", reps, log)
    totals = {k: sum(r[f"{k}_ms"] for r in rows)
              for k in ("direct", "staged", "fused", "batched", "int8")}
    for k, v in totals.items():
        log(f"vgg16_stack_{k}_ms,{v:.2f}")
    small = [r for r in rows if r["hw"] <= 14]
    if small:
        gain = sum(r["fused_ms"] for r in small) \
            / max(sum(r["batched_ms"] for r in small), 1e-9)
        log(f"small_image_batched_speedup_hw_le_14,{gain:.2f}x")

    # the lowered workloads: ResNet-18 stride-2 + depthwise rows
    lowered_rows = _lowered_sweep(spatial_cap, reps, log)
    lowered_totals = {k: sum(r[f"{k}_ms"] for r in lowered_rows)
                      for k in ("direct", "lowered")}
    for k, v in lowered_totals.items():
        log(f"resnet18_lowered_stack_{k}_ms,{v:.2f}")

    # accumulate, never overwrite: other suites' keys (scaleout) and the
    # cross-PR trajectory survive this run
    bench = {}
    if os.path.exists(bench_path):
        try:
            with open(bench_path) as f:
                bench = json.load(f)
        except ValueError:
            bench = {}
    if not isinstance(bench, dict):      # valid JSON but not an object
        bench = {}
    bench.update({
        "host": {"platform": jax.default_backend(), "jax": jax.__version__,
                 "interpret": True},
        "workload": "vgg16_conv_stack", "algo": "sfc6_6", "batch": 1,
        "spatial_cap": spatial_cap, "reps": reps,
        "layers": rows,
        "totals_ms": totals,
        "resnet_lowered": lowered_rows,
    })
    entry = {
        "ts": datetime.datetime.now(datetime.timezone.utc)
        .isoformat(timespec="seconds"),
        "git_sha": _git_sha(),
        "platform": jax.default_backend(), "jax": jax.__version__,
        "spatial_cap": spatial_cap, "reps": reps,
        "totals_ms": totals,
        "lowered_totals_ms": lowered_totals,
    }
    bench.setdefault("trajectory", []).append(entry)
    with open(bench_path, "w") as f:
        json.dump(bench, f, indent=1)
    log(f"bench_artifact,{bench_path} "
        f"(trajectory: {len(bench['trajectory'])} entries)")

    # paper's GOPs/DSP analogue: mults per output
    log(f"mults_per_output_direct,{9*64}")
    log(f"mults_per_output_sfc,{algo.mults_2d/algo.M**2*64:.1f}")
    return {"bops_reduction": total_direct_bops / total_sfc_bops,
            "bench_path": bench_path, "totals_ms": totals,
            "lowered_totals_ms": lowered_totals}


if __name__ == "__main__":
    run()
