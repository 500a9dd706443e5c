"""``ConvPlan`` — a resolved (spec, algorithm, backend) ready to execute.

A plan is produced by ``repro.api.plan()`` and owns the two halves of the
deployment story:

  * :meth:`ConvPlan.prepare_weights` — the offline half: transform weights
    into the algorithm's domain once, optionally quantizing them to int8
    with PTQ-calibrated static scales (paper §5-6: weights are stored in
    the transform domain, avoiding double quantization).  Prepared weights
    are memoized per plan, keyed on the concrete weight array.
  * :meth:`ConvPlan.apply` — the online half: one signature for every
    backend and precision.  ``apply(x, w)`` accepts either raw weights
    (prepared on the fly) or a :class:`PreparedWeights`.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.api.spec import ConvSpec
from repro.runtime import default_interpret
from repro.core.conv2d import transform_weights_2d
from repro.core.generator import BilinearAlgorithm
import repro.quant.fake_quant as fq

# FIFO bound on prepared weights retained per plan.  Entries pin the raw
# weights plus their ~(t/R)^2-times-larger transform-domain copies, so this
# trades memory for re-prepare cost; 16 covers every same-spec layer of the
# paper's evaluation CNNs.
_PREP_CACHE_MAX = 16


class PrepCache:
    """Identity-keyed FIFO of prepared weights, shared by :class:`ConvPlan`
    and the lowering layer's ``CompositePlan``.

    Keys are operand object ids; entries pin the operands so ids stay
    valid for the entry's lifetime.  Tracers (and pytrees containing
    tracers — composite plans pass per-sub-plan scale *sequences*) are
    never cached: under tracing there is nothing concrete to hold on to.
    """

    def __init__(self, maxsize: int = _PREP_CACHE_MAX):
        self._maxsize = maxsize
        self._lock = threading.Lock()
        self._entries: Dict[tuple, tuple] = {}

    @staticmethod
    def key_for(operands) -> Optional[tuple]:
        leaves = jax.tree_util.tree_leaves(operands)
        if any(isinstance(o, jax.core.Tracer) for o in leaves):
            return None
        return tuple(id(o) for o in operands)

    def get(self, key, operands):
        with self._lock:
            entry = self._entries.get(key)
        if entry is not None and \
                all(a is b for a, b in zip(entry[0], operands)):
            return entry[1]
        return None

    def put(self, key, operands, value) -> None:
        with self._lock:
            while len(self._entries) >= self._maxsize:
                self._entries.pop(next(iter(self._entries)))
            # the cache entry keeps the operands alive: ids stay valid
            self._entries[key] = (operands, value)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


def _normalize_w_scale(w_scale: jnp.ndarray, t: int, cout: int
                       ) -> jnp.ndarray:
    """Accept any weight-granularity scale shape; return (t, t, Cout)."""
    s = jnp.asarray(w_scale, jnp.float32)
    if s.ndim == 4:                       # keepdims (t|1, t|1, 1, Cout|1)
        return jnp.broadcast_to(s, (t, t, 1, cout))[:, :, 0, :]
    if s.shape == (t, t, cout):
        return s
    if s.shape == (t, t):                 # frequency-wise
        return jnp.broadcast_to(s[:, :, None], (t, t, cout))
    if s.ndim <= 1:                       # scalar or per-channel
        return jnp.broadcast_to(s, (t, t, cout))
    raise ValueError(f"cannot interpret w_scale shape {s.shape} "
                     f"for t={t}, Cout={cout}")


@dataclasses.dataclass(frozen=True)
class PreparedWeights:
    """Offline-processed weights for one plan.

    ``tw`` is the transform-domain fp tensor ((t, t, Cin, Cout) for rank 2,
    (t, C) for rank 1 depthwise); for int8 plans ``wq``/``w_scale``/
    ``act_scale`` additionally hold the offline-quantized weights and the
    static scales both backends consume.
    """

    w: Any                                   # raw weights as passed in
    tw: Optional[jnp.ndarray] = None
    wq: Optional[jnp.ndarray] = None         # (t^2, Cin, Cout) int8
    w_scale: Optional[jnp.ndarray] = None    # (t, t, Cout)
    act_scale: Optional[jnp.ndarray] = None  # (t, t)

    @property
    def quantized(self) -> bool:
        return self.wq is not None


@dataclasses.dataclass(eq=False)
class ConvPlan:
    """Executable plan: call :meth:`apply`; inspect ``algorithm``/``cost``."""

    spec: ConvSpec
    backend: str
    algo_name: str                            # registry name or 'direct'
    algorithm: Optional[BilinearAlgorithm]    # None = direct path
    # Pallas interpret mode: off on a TPU, on elsewhere (repro.runtime)
    interpret: bool = dataclasses.field(default_factory=default_interpret)
    cost: Optional[float] = None              # planner's BOPs estimate
    config: Optional[Any] = None              # tuning.KernelConfig (measured)
    _prep: PrepCache = dataclasses.field(
        default_factory=PrepCache, repr=False)

    @property
    def path(self) -> str:
        return "direct" if self.algorithm is None else "fast"

    def with_config(self, config) -> "ConvPlan":
        """This plan with a different kernel config (shared prep cache)."""
        return dataclasses.replace(self, config=config)

    # ------------------------------------------------------------------
    # offline: weight preparation
    # ------------------------------------------------------------------
    def prepare_weights(self, w: jnp.ndarray, *,
                        act_scale: Optional[jnp.ndarray] = None,
                        w_scale: Optional[jnp.ndarray] = None
                        ) -> PreparedWeights:
        """Pre-transform (and for int8 plans, pre-quantize) weights.

        ``act_scale`` (t, t) comes from PTQ calibration
        (``PTQLayer.static_scales``); it is required for the static-int8
        execution path.  ``w_scale`` defaults to absmax scales at the
        spec's weight granularity, broadcast to (t, t, Cout).
        Results are cached per concrete weight array.

        Backends that define ``place_prepared(plan, prep)`` (the sharded
        SPMD backend: C_out-sharded ``wq``/``w_scale`` placement) get the
        prepared tensors routed through it before caching, so the offline
        half also covers device layout — skipped under tracing, where
        there are no concrete buffers to place.
        """
        from repro import faults
        faults.maybe_fault(faults.PREPARE, detail=self)
        operands = (w, act_scale, w_scale)
        key = PrepCache.key_for(operands)
        if key is not None:
            cached = self._prep.get(key, operands)
            if cached is not None:
                return cached
        prep = self._prepare_uncached(w, act_scale, w_scale)
        if key is not None:
            from repro.api import backends    # late: avoids import cycle
            place = getattr(backends.get_backend(self.backend),
                            "place_prepared", None)
            if place is not None:
                prep = place(self, prep)
            self._prep.put(key, operands, prep)
        return prep

    def _prepare_uncached(self, w, act_scale, w_scale) -> PreparedWeights:
        if self.algorithm is None:
            return PreparedWeights(w=w)
        algo = self.algorithm
        if self.spec.rank == 1:
            if self.spec.quant.enabled:
                raise NotImplementedError(
                    "quantized rank-1 depthwise convolution is not "
                    "implemented; use quant=FP32")
            g = jnp.asarray(algo.g(), dtype=w.dtype)
            return PreparedWeights(w=w, tw=jnp.einsum("tr,rc->tc", g, w))
        tw = transform_weights_2d(w, algo)
        if not self.spec.quant.enabled or act_scale is None:
            return PreparedWeights(w=w, tw=tw)
        t = algo.t
        cout = tw.shape[-1]
        if w_scale is None:
            axes = fq.weight_reduce_axes(
                tw.ndim, self.spec.quant.weight_granularity)
            amax = jnp.max(jnp.abs(tw), axis=tuple(axes), keepdims=True)
            w_scale = amax / fq.qmax_for_bits(self.spec.quant.bits_weight) \
                + 1e-12
        w_scale = _normalize_w_scale(w_scale, t, cout)
        wq = fq.quantize_transformed_weights(
            tw, w_scale, self.spec.quant.bits_weight)
        act_scale = jnp.asarray(act_scale, jnp.float32).reshape(t, t)
        return PreparedWeights(w=w, tw=tw, wq=wq, w_scale=w_scale,
                               act_scale=act_scale)

    # ------------------------------------------------------------------
    # online: execution
    # ------------------------------------------------------------------
    def apply(self, x: jnp.ndarray, w, *,
              bias: Optional[jnp.ndarray] = None,
              elementwise_hook: Optional[Callable] = None) -> jnp.ndarray:
        """Run the convolution.  ``w`` is raw weights or PreparedWeights.

        ``elementwise_hook(tx, tw) -> (tx, tw)`` injects transform-domain
        processing (fake quantization, calibration observers) on the
        reference backend's fast path; static-int8 plans and the Pallas
        backend do not take hooks — quantization is baked into the plan.

        Pallas-backend applies run through the resilience layer
        (``repro.api.resilience``): on kernel failure the datapath
        degrades fused -> staged (bit-identical) -> reference (fp-close),
        guarded by per-level circuit breakers so a persistently broken
        config stops being retried.  The chain disengages under tracing
        (exceptions at trace time are the caller's compile errors, and
        the guardrail cannot inspect tracer values) and when an
        elementwise hook is passed (the hook's backend errors are
        contract errors, not kernel faults).

        The backend runs the datapath it picks under
        ``jax.named_scope("plan.<datapath>")`` (``fused``, ``staged``,
        ``direct`` or ``reference``; ``repro.api.backends``).
        """
        from repro.api import backends, resilience  # late: avoids cycle
        prep = w if isinstance(w, PreparedWeights) else \
            self.prepare_weights(w)
        if elementwise_hook is None and resilience.engaged(self) \
                and not isinstance(x, jax.core.Tracer):
            return resilience.apply_resilient(self, x, prep, bias=bias)
        return backends.get_backend(self.backend).apply(
            self, x, prep, bias=bias, elementwise_hook=elementwise_hook)

    def __call__(self, x, w, **kwargs):
        return self.apply(x, w, **kwargs)
