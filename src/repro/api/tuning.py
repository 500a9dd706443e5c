"""Measured-latency autotuner feeding the planner (ROADMAP: cost model
informed by measured timings rather than BOPs alone).

The BOPs cost model ranks algorithms by arithmetic, which is blind to the
memory behaviour that dominates deployed latency (HBM round-trips, padding
waste, VMEM residency).  This module closes the loop:

  * :func:`autotune` times candidate :class:`KernelConfig` s — fused vs
    staged datapath and their block sizes — for one (ConvSpec, backend)
    on the *actual* host, per registered algorithm (plus direct);
  * results persist in a JSON timing cache (``REPRO_TUNING_CACHE`` env var,
    default ``~/.cache/repro/tuning.json``) keyed on spec x backend x
    device platform, so one calibration run serves every later process;
  * ``planner.select_algorithm`` / ``plan`` consult :func:`lookup` /
    :func:`get_config` AHEAD of the BOPs model whenever measurements
    exist — measured wall-clock overrides the analytic ranking, and the
    winning kernel config rides on the resulting ``ConvPlan``.

Nothing here requires TPU: on the CPU container the kernels run in
interpret mode and the measured numbers rank the same code paths the TPU
executes (see EXPERIMENTS.md §Perf for methodology caveats).
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
import warnings
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.spec import ConvSpec
from repro.runtime import resolve_interpret

_ENV_CACHE = "REPRO_TUNING_CACHE"
_DEFAULT_CACHE = os.path.join(os.path.expanduser("~"), ".cache", "repro",
                              "tuning.json")


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """One executable configuration of the pallas int8 datapath."""

    datapath: str = "fused"       # 'fused' | 'staged'
    tile_block: int = 32          # staged transform/inverse tile block
    chan_block: int = 128         # staged transform/inverse channel block
    k_block: Optional[int] = 128  # C_in reduction block (None = full K)
    cout_block: int = 128         # fused C_out block
    # fused grid batching: tile-rows (then whole images) folded per grid
    # step; None resolves from the launch shape (sfc_fused.
    # auto_rows_per_step: the fewest grid steps the VMEM budget allows);
    # an explicit value (the serving fold's) wins
    rows_per_step: Optional[int] = None
    # fused DMA pipelining: prefetch the next input strip group into a
    # second VMEM slot while the current one is transformed and matmul'd
    double_buffer: bool = False

    def to_json(self) -> Dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: Dict) -> "KernelConfig":
        # unknown keys are dropped, missing ones default: cache entries
        # written before a knob existed stay loadable
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})


DEFAULT_FUSED = KernelConfig()
DEFAULT_STAGED = KernelConfig(datapath="staged", k_block=None)

# default candidate sweep: the fused datapath at a few block shapes
# (including full-K: single k-block, no reduction grid dim), each at the
# shape-resolved grouping, with and without DMA double-buffering, plus
# the staged pipeline (full-K and k-blocked) as fallback candidates
DEFAULT_CANDIDATES = (
    DEFAULT_FUSED,
    KernelConfig(datapath="fused", k_block=256, cout_block=128),
    KernelConfig(datapath="fused", k_block=128, cout_block=256),
    KernelConfig(datapath="fused", k_block=None),
    KernelConfig(datapath="fused", double_buffer=True),
    KernelConfig(datapath="staged", k_block=None),
    KernelConfig(datapath="staged", k_block=128),
)

_LOCK = threading.RLock()
_STORE: Optional[Dict[str, Dict]] = None   # cache-file image, lazily loaded
_PATH_OVERRIDE: Optional[str] = None


def cache_path() -> str:
    return _PATH_OVERRIDE or os.environ.get(_ENV_CACHE, _DEFAULT_CACHE)


def set_cache_path(path: Optional[str]) -> None:
    """Point the timing cache somewhere else (tests); None restores env."""
    global _PATH_OVERRIDE, _STORE
    with _LOCK:
        _PATH_OVERRIDE = path
        _STORE = None
    _invalidate_plans()


def clear() -> None:
    """Drop in-memory measurements (the cache file is left untouched)."""
    global _STORE
    with _LOCK:
        _STORE = {}
    _invalidate_plans()


def _invalidate_plans() -> None:
    # memoized plans may have consulted stale measurements (late import:
    # planner imports this module inside its functions)
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


def _snapshot_locked() -> Dict[str, Dict]:
    """Deep copy of the store (JSON-native values) — callers hold _LOCK."""
    return json.loads(json.dumps(_STORE or {}))


_WRITE_WARNED = False


def _write(path: str, snapshot: Dict[str, Dict]) -> None:
    global _WRITE_WARNED
    try:
        if os.path.dirname(path):
            os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(snapshot, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
        _WRITE_WARNED = False         # a later success re-arms the warning
    except OSError as e:
        # read-only host: measurements keep serving from memory, but say
        # so ONCE — silently dropping every record hides a fleet that
        # re-tunes from scratch each process, while warning per record
        # would flood a serving log
        if not _WRITE_WARNED:
            _WRITE_WARNED = True
            warnings.warn(
                f"tuning cache not persisted to {path!r} ({e}); "
                f"measurements remain in-memory for this process only",
                RuntimeWarning, stacklevel=3)


def _save() -> None:
    # write under the lock: concurrent snapshots must reach the file in
    # mutation order, or a stale image can overwrite a newer one
    with _LOCK:
        _write(cache_path(), _snapshot_locked())


def spec_key(spec: ConvSpec, backend: str,
             interpret: Optional[bool] = None) -> str:
    """Stable cache key: (workload, backend, device, interpret mode).

    ``interpret`` is part of the key — interpret-mode (CPU emulation)
    timings rank completely differently from compiled TPU kernels and
    must never govern non-interpret plans; ``None`` follows the platform
    (:func:`repro.runtime.resolve_interpret`).

    New spec fields append tokens only at their NON-default values
    (``g{groups}`` for grouped, ``dw`` for 2-D depthwise) — the same
    tolerance pattern as ``KernelConfig.from_json``: every timing-cache
    entry written before a field existed keys a default-valued spec, so
    old JSON caches keep resolving unchanged.
    """
    q = spec.quant
    qk = (f"a{q.bits_act}w{q.bits_weight}{q.act_granularity}"
          f"-{q.weight_granularity}" if q.enabled else "fp32")
    extra = ""
    if spec.groups != 1:
        extra += f"g{spec.groups}"
    if spec.rank == 2 and spec.depthwise:
        extra += "dw"
    return (f"r{spec.rank}k{spec.kernel_size}s{spec.stride}"
            f"p{spec.padding}ci{spec.in_channels}co{spec.out_channels}"
            f"sp{spec.spatial}q{qk}{extra}|{backend}|{jax.default_backend()}"
            f"|i{int(resolve_interpret(interpret))}")


def lookup(spec: ConvSpec, backend: str,
           interpret: Optional[bool] = None) -> Dict[str, Dict]:
    """Measured entries for (spec, backend): {algo_name: {time_s, config}}.

    Empty dict when nothing has been measured — the planner then falls
    back to the BOPs model.
    """
    return dict(_load().get(spec_key(spec, backend, interpret), {}))


def get_config(spec: ConvSpec, backend: str, algo_name: str,
               interpret: Optional[bool] = None) -> Optional[KernelConfig]:
    """Best measured kernel config for one algorithm, or None."""
    entry = _load().get(spec_key(spec, backend, interpret),
                        {}).get(algo_name)
    if entry is None or "config" not in entry:
        return None
    return KernelConfig.from_json(entry["config"])


def record(spec: ConvSpec, backend: str, algo_name: str, time_s: float,
           config: Optional[KernelConfig] = None, *,
           predicted_s: Optional[float] = None,
           interpret: Optional[bool] = None, persist: bool = True) -> None:
    """Store one measurement (used by autotune; exposed for tests/offline
    calibration imports).  Last measurement wins — a re-tune must be able
    to correct entries that no longer reproduce (driver/library upgrades,
    different host load), so older-but-faster times are NOT kept.

    The load -> mutate -> persist span holds ONE lock acquisition: a
    concurrent ``set_cache_path()`` / ``clear()`` lands either entirely
    before (this record mutates the fresh store) or entirely after (the
    reset drops the in-memory entry, as those functions document) — it
    can never detach the dict being mutated from the one that persists,
    so a completed ``record`` is always on disk, and concurrent records
    reach the file in mutation order.
    """
    with _LOCK:
        store = _load()               # RLock: reentrant under our span
        entry = store.setdefault(spec_key(spec, backend, interpret), {})
        entry[algo_name] = {"time_s": float(time_s)}
        if config is not None:
            entry[algo_name]["config"] = config.to_json()
        if predicted_s is not None:
            # cost-model self-validation: autotune stores the model's
            # prediction for the measured winner alongside the ground
            # truth, so a drifting model is visible in the cache itself
            entry[algo_name]["predicted_s"] = float(predicted_s)
        if persist:
            _write(cache_path(), _snapshot_locked())
    _invalidate_plans()


# --------------------------------------------------------------------------
# measurement
# --------------------------------------------------------------------------
def time_fn(fn, *args, reps: int = 3, min_total_s: float = 0.02,
            max_reps: int = 64) -> float:
    """Mean wall-clock of ``fn(*args)`` after one warmup (compile) call.

    The one timing protocol shared by the autotuner, the cost-model
    calibration, and the benchmarks (``benchmarks/table3_throughput.py``).
    De-noised by an adaptive repeat: after the initial ``reps`` batch,
    timed batches double until at least ``min_total_s`` of wall-clock has
    accumulated (or ``max_reps`` calls ran) — a sub-millisecond kernel
    timed three times is mostly timer jitter, and coefficients fitted
    from jitter would mis-rank candidates.  ``min_total_s=0`` restores
    the fixed-``reps`` protocol.
    """
    jax.block_until_ready(fn(*args))              # compile + warm up once
    total, calls, batch = 0.0, 0, max(reps, 1)
    while True:
        t0 = time.perf_counter()
        for _ in range(batch):
            out = fn(*args)
            jax.block_until_ready(out)
        total += time.perf_counter() - t0
        calls += batch
        if total >= min_total_s or calls >= max_reps:
            return total / calls
        batch = min(calls, max_reps - calls)      # double, capped


def calibrate_act_scale(x: jnp.ndarray, algo, quant,
                        padding: str = "SAME") -> jnp.ndarray:
    """Absmax per-frequency activation scales (t, t) from one batch.

    Single-batch stand-in for PTQ calibration (``repro.quant.ptq``) used
    by the autotuner, benchmarks, and tests; respects ``quant.bits_act``.
    """
    from repro.core import conv2d as c2d
    from repro.quant.fake_quant import qmax_for_bits
    tx, _ = c2d.transform_input_2d(x, algo, padding)
    return jnp.abs(tx).max(axis=(0, 1, 2, 5)) \
        / qmax_for_bits(quant.bits_act) + 1e-9


def _synthetic_operands(spec: ConvSpec, seed: int = 0):
    if spec.rank != 2 or spec.in_channels is None \
            or spec.out_channels is None or spec.spatial is None:
        raise ValueError(
            "autotune needs a fully-hinted rank-2 spec (in/out channels "
            f"and spatial extents): {spec}")
    rng = np.random.RandomState(seed)
    H, W = spec.spatial
    cin_w = 1 if spec.depthwise else spec.in_channels // spec.groups
    x = jnp.asarray(rng.randn(1, H, W, spec.in_channels), jnp.float32)
    w = jnp.asarray(
        rng.randn(spec.kernel_size, spec.kernel_size, cin_w,
                  spec.out_channels) * 0.1, jnp.float32)
    return x, w


def _measure_plan(p, x, w, reps: int) -> float:
    if p.spec.quant.enabled and p.path == "lowered":
        # composite plans calibrate per sub-problem
        prep = p.prepare_weights(w, act_scale=p.calibrate(x))
    elif p.spec.quant.enabled and p.algorithm is not None:
        # absmax calibration on the synthetic batch itself — the timing is
        # scale-agnostic, only the datapath matters
        act_scale = calibrate_act_scale(x, p.algorithm, p.spec.quant,
                                        p.spec.padding)
        prep = p.prepare_weights(w, act_scale=act_scale)
    else:
        prep = p.prepare_weights(w)
    # one jit around the whole apply: the direct/reference paths are
    # otherwise eager, and dispatch overhead would skew the ranking
    return time_fn(jax.jit(lambda a: p.apply(a, prep)), x, reps=reps)


def autotune(spec: ConvSpec, backend: str = "pallas", *,
             algos: Optional[Sequence[str]] = None,
             candidates: Sequence[KernelConfig] = DEFAULT_CANDIDATES,
             include_direct: bool = True, reps: int = 3,
             top_k: Optional[int] = 3,
             interpret: Optional[bool] = None, persist: bool = True,
             log=None) -> Dict[str, Dict]:
    """Measure candidate configs for ``spec`` and persist the winners.

    Times candidate (algorithm, config) pairs on synthetic operands,
    records the fastest config per algorithm (plus the direct path), and
    returns the resulting ``lookup(spec, backend)`` entries.  Subsequent
    ``plan(spec, backend=..., algo='auto')`` calls rank by these measured
    latencies instead of BOPs.  The cache file is written once at the end
    (an interrupted run persists nothing, so a partial sweep cannot skew
    the planner across processes), with the direct baseline measured
    first.

    ``top_k``: when the analytic cost model (``repro.api.costmodel``) is
    fitted for this backend/device, launchable candidates are ranked by
    predicted latency and only the top ``top_k`` are measured — the
    ROADMAP's cold-start story: a fleet spec with live traffic behind it
    pays for k timed launches, not a full sweep.  The winner's predicted
    time is recorded next to the measurement (``predicted_s``) so the
    model self-validates in the cache.  With the model unfitted (or
    ``top_k=None``) every launchable candidate is measured, exactly as
    before.
    """
    from repro.api import planner, registry
    x, w = _synthetic_operands(spec)
    if algos is None:
        algos = [e.name for e in registry.entries(taps=spec.kernel_size)]
    results: Dict[str, Dict] = {}
    if include_direct:
        p = planner.plan(spec, backend=backend, algo="direct",
                         interpret=interpret)
        dt = _measure_plan(p, x, w, reps)
        if log:
            log(f"autotune direct: {dt*1e3:.2f}ms")
        record(spec, backend, "direct", dt, interpret=interpret,
               persist=False)
        results["direct"] = {"time_s": dt}
    # lowered specs can collapse many algorithm names onto one composite
    # (every tap-mismatched name resolves its sub-specs with 'auto'):
    # measure each distinct composite once.  The signature is structural
    # — (sub-spec, resolved algorithm) per sub-plan — because recording a
    # measurement invalidates the plan cache, so object identities do not
    # survive from one name to the next.
    seen_composites: Dict[tuple, str] = {}
    from repro.analysis import kernel_checks, ranges
    for name in algos:
        try:
            p_name = planner.plan(spec, backend=backend, algo=name,
                                  interpret=interpret)
        except ranges.AccumulatorOverflowError as exc:
            # plan-time overflow pre-flight rejected the algorithm for
            # this spec/backend: never time it
            if log:
                log(f"autotune {name}: skipped, {exc}")
            continue
        if p_name.path == "lowered":
            sig = tuple((sp.spec, sp.algo_name) for sp in p_name.sub_plans)
            first = seen_composites.setdefault(sig, name)
            if first != name:
                if log:
                    log(f"autotune {name}: same lowered composite as "
                        f"{first}; skipped")
                continue
        launchable = list(candidates)
        predictions: Dict[KernelConfig, float] = {}
        if p_name.path == "fast" and p_name.algorithm is not None:
            # static resource pre-flight: drop fused configs whose launch
            # geometry breaks the VMEM budget / strip bounds / scratch
            # invariants instead of timing a kernel that would fail (or
            # silently spill) on hardware
            launchable, rejected = kernel_checks.check_candidates(
                spec, p_name.algorithm, candidates, batch=x.shape[0])
            if log:
                for cfg, errs in rejected:
                    log(f"autotune {name} {cfg.datapath}"
                        f"(k={cfg.k_block},co={cfg.cout_block},"
                        f"r={cfg.rows_per_step},"
                        f"db={int(cfg.double_buffer)}): rejected by "
                        f"pre-flight [{errs[0].code}]")
            if top_k is not None:
                # fitted cost model: measure only the predicted top-k
                from repro.api import costmodel
                ranked = costmodel.rank_candidates(
                    spec, p_name.algorithm, launchable, backend=backend,
                    interpret=interpret, batch=x.shape[0])
                if ranked is not None:
                    predictions = dict(ranked)
                    launchable = [cfg for cfg, _ in ranked[:top_k]]
                    if log and len(ranked) > len(launchable):
                        log(f"autotune {name}: cost model kept top-"
                            f"{len(launchable)} of {len(ranked)} "
                            f"launchable candidates")
        best: Optional[float] = None
        best_cfg: Optional[KernelConfig] = None
        for cfg in launchable:
            p0 = planner.plan(spec, backend=backend, algo=name,
                              interpret=interpret)
            if p0.path == "direct":        # spec degraded to direct
                continue
            p = p0.with_config(cfg)        # composite: fans out to subs
            dt = _measure_plan(p, x, w, reps)
            if log:
                log(f"autotune {name} {cfg.datapath}"
                    f"(k={cfg.k_block},co={cfg.cout_block},"
                    f"r={cfg.rows_per_step},db={int(cfg.double_buffer)}): "
                    f"{dt*1e3:.2f}ms")
            if best is None or dt < best:
                best, best_cfg = dt, cfg
        if best is not None:
            record(spec, backend, name, best, best_cfg,
                   predicted_s=predictions.get(best_cfg),
                   interpret=interpret, persist=False)
            results[name] = {"time_s": best, "config": best_cfg.to_json()}
            if best_cfg in predictions:
                results[name]["predicted_s"] = predictions[best_cfg]
    if persist:
        _save()
    return results
