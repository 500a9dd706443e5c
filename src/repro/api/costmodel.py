"""Calibrated analytic cost model — the planner's middle tier (ROADMAP
item 1: predict the winning kernel config for *unseen* specs instead of
timing every candidate exhaustively).

The BOPs model (``repro.quant.bops``) prices arithmetic only; measured
timings (``repro.api.tuning``) price everything but need a sweep per
spec.  This module sits between them: an analytic per-candidate latency
predictor in the roofline style,

    t_pred(candidate) = k0 + k1 * grid_steps + k2 * roof_s
    roof_s            = max(compute_s, memory_s)

where ``compute_s`` (int8 MXU matmul volume of the t^2 transform-domain
matmuls plus transform/inverse VPU work) and ``memory_s`` (HBM strip
reads, weight k-block traffic, output writeback) are derived from the
kernel's own single-sourced launch geometry — ``FusedGeometry``'s
``compute_ops()`` / ``hbm_bytes()`` accessors, resolved through
``repro.analysis.kernel_checks.geometry_for`` — and from the BOPs
workload model for the staged/direct datapaths.  The model NEVER
re-derives strip or VMEM arithmetic from shapes (lint rule COST001):
the geometry is the one place launch work is counted.

The (k0, k1, k2) overhead coefficients are *measured*, not assumed:
:func:`fit_coefficients` times a handful of probe specs (one short run,
not a per-spec sweep) and least-squares fits one coefficient set per
datapath (fused / staged / direct), so host realities the analytic
terms cannot see — interpret-mode emulation cost, dispatch overhead,
cache behaviour — are absorbed into the calibration.  Coefficients
persist next to the timing cache (``REPRO_COSTMODEL_CACHE`` env var,
default ``~/.cache/repro/costmodel.json``) keyed on backend x device x
interpret mode, so one calibration serves every later process.

Consumers (wired in ``planner`` / ``tuning`` / ``serve.engine``):

  * ``planner.select_algorithm``: measured timings first (unchanged),
    then this model, then raw BOPs;
  * ``tuning.autotune(top_k=...)``: rank all launchable candidates here
    and measure only the top-k, recording predicted-vs-measured into
    the timing cache so the model self-validates;
  * serve engine warm-up: model-predicted configs for buckets with no
    timing entry (see ``benchmarks/roofline.py run_costmodel`` for the
    validation cell feeding ``BENCH_conv.json["costmodel"]``).
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import threading
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.api.spec import ConvSpec
from repro.quant.bops import direct_conv_bops, fastconv_bops
from repro.runtime import resolve_interpret

_ENV_CACHE = "REPRO_COSTMODEL_CACHE"
_DEFAULT_CACHE = os.path.join(os.path.expanduser("~"), ".cache", "repro",
                              "costmodel.json")

# Nominal at-peak rates used ONLY to normalise the analytic work terms
# into comparable "at-peak seconds" before the roofline max(); the
# fitted k2 coefficient rescales them to the actual host (on the CPU
# container, interpret-mode emulation is orders of magnitude off these
# peaks — that gap lands in the coefficients, the *ranking* information
# lives in the relative feature magnitudes).  HBM matches
# benchmarks/roofline.py's v5e figure.
PEAK_MXU_INT8_MACS = 197e12     # int8 MXU multiply-accumulates / s
PEAK_VPU_FLOPS = 3.9e12         # f32 VPU elementwise ops / s
PEAK_HBM_BYTES = 819e9          # HBM bytes / s
PEAK_BOPS = PEAK_MXU_INT8_MACS * 64.0   # bit-ops/s at 8x8-bit pricing

# feature-vector width per datapath: (1, grid_steps, roof_s) for the
# pallas datapaths, (1, roof_s) for direct (no grid)
N_FEATURES = {"fused": 3, "staged": 3, "direct": 2}

_LOCK = threading.RLock()
_STORE: Optional[Dict[str, Dict]] = None
_PATH_OVERRIDE: Optional[str] = None


# --------------------------------------------------------------------------
# coefficient store (same shape/locking discipline as the timing cache)
# --------------------------------------------------------------------------
def cache_path() -> str:
    return _PATH_OVERRIDE or os.environ.get(_ENV_CACHE, _DEFAULT_CACHE)


def set_cache_path(path: Optional[str]) -> None:
    """Point the coefficient store somewhere else (tests); None restores
    the env/default resolution."""
    global _PATH_OVERRIDE, _STORE
    with _LOCK:
        _PATH_OVERRIDE = path
        _STORE = None
    _invalidate_plans()


def clear() -> None:
    """Drop in-memory coefficients (the cache file is left untouched)."""
    global _STORE
    with _LOCK:
        _STORE = {}
    _invalidate_plans()


def _invalidate_plans() -> None:
    # memoized plans may embed configs/algorithms this model selected
    from repro.api import planner
    planner.invalidate_plan_cache()


def _load() -> Dict[str, Dict]:
    global _STORE
    with _LOCK:
        if _STORE is None:
            try:
                with open(cache_path()) as f:
                    _STORE = json.load(f)
            except (OSError, ValueError):
                _STORE = {}
        return _STORE


_WRITE_WARNED = False


def _save() -> None:
    global _WRITE_WARNED
    with _LOCK:
        snapshot = json.loads(json.dumps(_STORE or {}))
        path = cache_path()
    try:
        if os.path.dirname(path):
            os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(snapshot, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
        _WRITE_WARNED = False
    except OSError as e:
        if not _WRITE_WARNED:
            _WRITE_WARNED = True
            warnings.warn(
                f"cost-model coefficients not persisted to {path!r} ({e}); "
                f"the fit remains in-memory for this process only",
                RuntimeWarning, stacklevel=3)


def _key(backend: str, interpret: Optional[bool]) -> str:
    # device platform is part of the key for the same reason as the
    # timing cache: interpret-mode CPU coefficients must never price
    # compiled-TPU plans
    return (f"{backend}|{jax.default_backend()}"
            f"|i{int(resolve_interpret(interpret))}")


def coefficients(backend: str = "pallas", interpret: Optional[bool] = None
                 ) -> Optional[Dict[str, List[float]]]:
    """Fitted per-datapath coefficient vectors, or None when unfitted."""
    entry = _load().get(_key(backend, interpret))
    if not entry:
        return None
    return {dp: list(map(float, entry[dp]))
            for dp in N_FEATURES if dp in entry}


def is_fitted(backend: str = "pallas",
              interpret: Optional[bool] = None) -> bool:
    return bool(coefficients(backend, interpret))


def set_coefficients(coefs: Dict[str, Sequence[float]],
                     backend: str = "pallas", *,
                     interpret: Optional[bool] = None,
                     persist: bool = True, meta: Optional[Dict] = None
                     ) -> None:
    """Install coefficient vectors (fit output, tests, offline calib).

    ``coefs`` maps datapath -> vector sized per :data:`N_FEATURES`.
    """
    for dp, vec in coefs.items():
        if dp not in N_FEATURES:
            raise ValueError(f"unknown datapath {dp!r}")
        if len(vec) != N_FEATURES[dp]:
            raise ValueError(
                f"{dp} coefficient vector has {len(vec)} entries, "
                f"expected {N_FEATURES[dp]}")
    with _LOCK:
        store = _load()
        entry = {dp: [float(v) for v in vec] for dp, vec in coefs.items()}
        if meta:
            entry["meta"] = meta
        store[_key(backend, interpret)] = entry
        if persist:
            _save()
    _invalidate_plans()


# --------------------------------------------------------------------------
# analytic features
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class CostFeatures:
    """Analytic work terms of one (spec, algorithm, config) candidate."""

    datapath: str          # 'fused' | 'staged' | 'direct'
    grid_steps: int        # per-step overhead quanta (0 for direct)
    compute_s: float       # arithmetic at nominal peak, seconds
    memory_s: float        # HBM traffic at nominal peak, seconds
    hbm_bytes: int         # total HBM traffic, bytes
    vmem_bytes: int        # per-grid-step VMEM residency (fused only)

    @property
    def roof_s(self) -> float:
        """Roofline: the launch cannot beat its slower resource."""
        return max(self.compute_s, self.memory_s)

    def vector(self) -> Tuple[float, ...]:
        if self.datapath == "direct":
            return (1.0, self.roof_s)
        return (1.0, float(self.grid_steps), self.roof_s)


def _direct_features(spec: ConvSpec, batch: int) -> CostFeatures:
    from repro.api import planner
    wl = planner._workload(spec)
    H, W = spec.spatial
    cin_w = 1 if spec.depthwise else spec.in_channels // spec.groups
    out_sp = wl.n_outputs_spatial
    hbm = batch * (H * W * spec.in_channels + out_sp * spec.out_channels) \
        * 4 + spec.kernel_size ** 2 * cin_w * spec.out_channels * 4
    return CostFeatures(
        datapath="direct", grid_steps=0,
        compute_s=batch * direct_conv_bops(wl) / PEAK_BOPS,
        memory_s=hbm / PEAK_HBM_BYTES, hbm_bytes=hbm, vmem_bytes=0)


def _staged_features(spec: ConvSpec, algo, config, geom,
                     batch: int) -> CostFeatures:
    """Staged 3-kernel pipeline: arithmetic priced by the BOPs workload
    model, memory by the transform-domain tensor's HBM round trips (the
    traffic the fused kernel exists to eliminate).  Tile counts come
    from the resolved geometry — never re-derived."""
    from repro.api import planner
    wl = planner._workload(spec)
    H, W = spec.spatial
    C, Cout = spec.in_channels, spec.out_channels
    n_tiles = batch * geom.nH * geom.nW
    t, P, M = geom.t, geom.P, geom.M
    # input/output round trips + the int8 transform tensor (write by the
    # transform kernel, read by tdmm) + the int32 product tensor (write
    # by tdmm, read by the inverse) + int8 weights
    hbm = (batch * H * W * C * 4
           + n_tiles * P * C * 2
           + n_tiles * P * Cout * 8
           + P * C * Cout
           + batch * geom.out_h * geom.out_w * Cout * 4)
    tb, cbk = config.tile_block, config.chan_block
    n_k = 1 if config.k_block is None else math.ceil(C / config.k_block)
    steps = (math.ceil(n_tiles / tb) * math.ceil(C / cbk)          # transform
             + P * math.ceil(n_tiles / 128)                        # tdmm
             * math.ceil(Cout / 128) * n_k
             + math.ceil(n_tiles / tb) * math.ceil(Cout / cbk))    # inverse
    return CostFeatures(
        datapath="staged", grid_steps=steps,
        compute_s=batch * fastconv_bops(wl, algo) / PEAK_BOPS,
        memory_s=hbm / PEAK_HBM_BYTES, hbm_bytes=hbm,
        vmem_bytes=0)


def _fused_features(geom) -> CostFeatures:
    ops = geom.compute_ops()
    hbm = geom.hbm_bytes()
    vpu = ops["vpu_transform"] + ops["vpu_inverse"] + ops["vpu_ew"]
    return CostFeatures(
        datapath="fused", grid_steps=geom.grid_steps,
        compute_s=ops["mxu_macs"] / PEAK_MXU_INT8_MACS
        + vpu / PEAK_VPU_FLOPS,
        memory_s=hbm["total"] / PEAK_HBM_BYTES, hbm_bytes=hbm["total"],
        vmem_bytes=geom.vmem_bytes())


def features_for(spec: ConvSpec, algo, config, *,
                 batch: int = 1) -> Optional[CostFeatures]:
    """Analytic features of one candidate, or None when the model cannot
    price it (shape hints missing, or a fast-path request the geometry
    cannot resolve natively — lowered/strided/grouped specs are priced
    per sub-spec by their own plans, not here)."""
    if spec.rank != 2 or spec.spatial is None \
            or spec.in_channels is None or spec.out_channels is None:
        return None
    if algo is None:
        return _direct_features(spec, batch)
    if spec.stride != 1 or (spec.groups != 1 and not spec.depthwise):
        return None
    if algo.R != spec.kernel_size:
        return None
    from repro.analysis import kernel_checks
    H, W = spec.spatial
    geom = kernel_checks.geometry_for(
        algo, config, batch, H, W, spec.in_channels, spec.out_channels,
        padding=spec.padding, depthwise=spec.depthwise)
    if getattr(config, "datapath", "fused") == "staged":
        return _staged_features(spec, algo, config, geom, batch)
    return _fused_features(geom)


# --------------------------------------------------------------------------
# prediction / ranking
# --------------------------------------------------------------------------
def predict_time(spec: ConvSpec, algo, config, *, backend: str = "pallas",
                 interpret: Optional[bool] = None, batch: int = 1
                 ) -> Optional[float]:
    """Predicted wall-clock seconds, or None when unfitted/unpriceable."""
    coefs = coefficients(backend, interpret)
    if coefs is None:
        return None
    feats = features_for(spec, algo, config, batch=batch)
    if feats is None:
        return None
    c = coefs.get(feats.datapath)
    if c is None:
        return None
    v = feats.vector()
    return max(float(np.dot(np.asarray(c), np.asarray(v))), 0.0)


def rank_candidates(spec: ConvSpec, algo, candidates=None, *,
                    backend: str = "pallas", interpret: Optional[bool] = None,
                    batch: int = 1
                    ) -> Optional[List[Tuple[object, float]]]:
    """Launchable candidates sorted by predicted time (fastest first).

    Pre-flights candidates through ``kernel_checks.check_candidates``
    exactly as the autotuner does, so the ranking never proposes a
    config the kernel would reject.  Returns None when the model is
    unfitted or any launchable candidate cannot be priced — a partial
    ranking must not hide a candidate from the measured sweep.
    """
    from repro.analysis import kernel_checks
    from repro.api import tuning
    if candidates is None:
        candidates = tuning.DEFAULT_CANDIDATES
    launchable, _ = kernel_checks.check_candidates(
        spec, algo, candidates, batch=batch)
    if not launchable:
        return None
    ranked = []
    for cfg in launchable:
        pred = predict_time(spec, algo, cfg, backend=backend,
                            interpret=interpret, batch=batch)
        if pred is None:
            return None
        ranked.append((cfg, pred))
    ranked.sort(key=lambda cp: cp[1])
    return ranked


def best_config(spec: ConvSpec, backend: str, algo_name: str,
                interpret: Optional[bool] = None):
    """Model-predicted best ``KernelConfig`` for one algorithm, or None.

    The planner's fallback when the timing cache has no entry — cold
    specs get a near-optimal config without a blocking sweep.
    """
    from repro.api import registry
    algo = registry.get_algorithm(algo_name)
    if algo is None:                       # direct path carries no config
        return None
    ranked = rank_candidates(spec, algo, backend=backend,
                             interpret=interpret)
    return ranked[0][0] if ranked else None


def select_algorithm(spec: ConvSpec, names: Sequence[str],
                     backend: str, interpret: Optional[bool] = None
                     ) -> Optional[str]:
    """Model-predicted fastest among ``names`` (each at its predicted
    best config), or None when any candidate cannot be priced.

    All-or-nothing on purpose — the same partial-knowledge rule as the
    planner's measured branch: a model that can price only some
    eligible candidates must not hide the others, so selection falls
    back to BOPs instead.
    """
    from repro.api import registry
    best_name, best_pred = None, None
    for name in names:
        algo = registry.get_algorithm(name)
        if algo is None:
            pred = predict_time(spec, None, None, backend=backend,
                                interpret=interpret)
        else:
            ranked = rank_candidates(spec, algo, backend=backend,
                                     interpret=interpret)
            pred = ranked[0][1] if ranked else None
        if pred is None:
            return None
        if best_pred is None or pred < best_pred:
            best_name, best_pred = name, pred
    return best_name


# --------------------------------------------------------------------------
# calibration
# --------------------------------------------------------------------------
def default_probe_specs() -> List[ConvSpec]:
    """Small, shape-diverse probe set: one memory-bound small image, one
    larger-spatial, two channel-heavy — enough spread in (grid_steps,
    roof_s) to condition the 3-coefficient fit without a full sweep.

    The 512-channel probe is load-bearing: below ~256 channels every
    ``k_block`` candidate clamps to the same resolved geometry, so the
    per-grid-step coefficient is unidentifiable from small probes alone
    (total HBM bytes are invariant to k-blocking — only step count
    varies, and only at large C_in)."""
    from repro.quant.fake_quant import QuantConfig
    q = QuantConfig(enabled=True, bits_act=8, bits_weight=8)
    return [
        ConvSpec(kernel_size=3, in_channels=32, out_channels=32,
                 spatial=(14, 14), quant=q),
        ConvSpec(kernel_size=3, in_channels=64, out_channels=128,
                 spatial=(28, 28), quant=q),
        ConvSpec(kernel_size=3, in_channels=256, out_channels=256,
                 spatial=(7, 7), quant=q),
        ConvSpec(kernel_size=3, in_channels=512, out_channels=512,
                 spatial=(7, 7), quant=q),
    ]


def _fit_nonneg(X: np.ndarray, y: np.ndarray) -> List[float]:
    """Deterministic least squares with an active-set non-negativity
    pass: negative coefficients (unphysical — more work can't be
    faster) are zeroed most-negative-first and the rest refitted."""
    n = X.shape[1]
    active = list(range(n))
    coefs = np.zeros(n)
    while active:
        sol, *_ = np.linalg.lstsq(X[:, active], y, rcond=None)
        if np.all(sol >= -1e-18):
            coefs[:] = 0.0
            coefs[active] = np.maximum(sol, 0.0)
            break
        del active[int(np.argmin(sol))]
    return [float(c) for c in coefs]


def fit_coefficients(probe_specs: Optional[Sequence[ConvSpec]] = None,
                     backend: str = "pallas", *,
                     interpret: Optional[bool] = None, reps: int = 3,
                     persist: bool = True,
                     log=None) -> Dict:
    """Calibrate the model from a handful of probe runs and install the
    per-datapath coefficients.

    For each probe spec: measures the direct plan plus every launchable
    ``DEFAULT_CANDIDATES`` config of the BOPs-best fast algorithm
    (through the same ``tuning._measure_plan`` protocol the autotuner
    uses), then least-squares fits (k0, k1, k2) per datapath.  Returns
    the fit report that also lands in the coefficient store's ``meta``.
    """
    from repro.analysis import kernel_checks, ranges
    from repro.api import planner, tuning
    if probe_specs is None:
        probe_specs = default_probe_specs()
    samples: Dict[str, List[Tuple[Tuple[float, ...], float]]] = {
        dp: [] for dp in N_FEATURES}
    for spec in probe_specs:
        x, w = tuning._synthetic_operands(spec)
        p_direct = planner.plan(spec, backend=backend, algo="direct",
                                interpret=interpret)
        dt = tuning._measure_plan(p_direct, x, w, reps)
        feats = features_for(spec, None, None, batch=x.shape[0])
        if feats is not None:
            samples["direct"].append((feats.vector(), dt))
        if log:
            log(f"costmodel probe {spec.spatial} ci{spec.in_channels}"
                f"co{spec.out_channels} direct: {dt*1e3:.2f}ms")
        name = planner.select_algorithm(spec)    # pure BOPs ranking
        from repro.api import registry
        algo = registry.get_algorithm(name)
        if algo is None:
            continue
        try:
            p0 = planner.plan(spec, backend=backend, algo=name,
                              interpret=interpret)
        except ranges.AccumulatorOverflowError:
            continue
        if p0.path != "fast":
            continue
        launchable, _ = kernel_checks.check_candidates(
            spec, algo, tuning.DEFAULT_CANDIDATES, batch=x.shape[0])
        for cfg in launchable:
            p = p0.with_config(cfg)
            t = tuning._measure_plan(p, x, w, reps)
            feats = features_for(spec, algo, cfg, batch=x.shape[0])
            if feats is None:
                continue
            samples[cfg.datapath].append((feats.vector(), t))
            if log:
                log(f"costmodel probe {spec.spatial} {cfg.datapath}"
                    f"(k={cfg.k_block},r={cfg.rows_per_step}): "
                    f"{t*1e3:.2f}ms")
    coefs: Dict[str, List[float]] = {}
    report: Dict = {"backend": backend, "interpret": interpret,
                    "device": jax.default_backend(),
                    "samples": {dp: len(s) for dp, s in samples.items()},
                    "probe_specs": len(list(probe_specs))}
    for dp, rows in samples.items():
        if not rows:
            continue
        X = np.asarray([v for v, _ in rows])
        y = np.asarray([t for _, t in rows])
        coefs[dp] = _fit_nonneg(X, y)
        pred = X @ np.asarray(coefs[dp])
        err = np.abs(pred - y) / np.maximum(y, 1e-12)
        report.setdefault("fit_error", {})[dp] = {
            "mean_rel": float(err.mean()), "max_rel": float(err.max())}
    if not coefs:
        raise ValueError("no probe spec produced a measurable sample; "
                         "cannot fit cost-model coefficients")
    report["coefficients"] = {dp: list(v) for dp, v in coefs.items()}
    set_coefficients(coefs, backend, interpret=interpret, persist=persist,
                     meta={k: v for k, v in report.items()
                           if k != "coefficients"})
    return report
