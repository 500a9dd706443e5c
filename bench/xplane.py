"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

What a TPU trace holds (read by hand from a TPU v5 lite trace, jax 0.9):

* a plane ``/device:TPU:<n>`` per chip, with a line ``XLA Ops`` of the
  device operations that ran (an event's name is the HLO instruction's
  text, ``%sfc_fused_conv2d.1 = f32[...] custom-call(...)``), a line
  ``XLA Modules`` of the programs (``jit_forward(<fingerprint>)``) and a
  line ``Async XLA Ops`` of copies in flight, which overlap the ops and
  are left out here;
* a plane ``/host:CPU`` whose lines hold the host threads; the
  benchmark's own ``jax.profiler.TraceAnnotation`` spans (``bench.*``)
  sit there;
* no ``jax.named_scope`` names: those live in the compiled program's HLO
  text (``metadata={op_name="jit(forward)/s0c0/..."}``), so the scope of
  a device op is looked up by its instruction name in the text of its
  module (:func:`op_names`).  An op that carries no metadata (a copy, an
  async weight slice) is charged to the next scoped op of the same
  program run, whose input it is being made ready for.

Host and device stamps share the trace's clock to within about a
millisecond, so the window is taken from the host span ``bench.window``.
Device busy time is the union of the ``XLA Ops`` intervals inside it.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
HOST_SPAN_PREFIX = "bench."

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=.*?op_name=\"([^\"]*)\"")
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")


def op_names(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """(module name, {instruction name: op_name}) of a compiled program's
    HLO text (``jax.jit(f).lower(...).compile().as_text()``)."""
    module, names = "", {}
    for line in hlo_text.splitlines():
        if not module:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
                continue
        m = _INSTR.match(line)
        if m:
            names[m.group(1)] = m.group(2)
    return module, names


def _instr(event_name: str) -> str:
    head = event_name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def _module(event_name: str) -> str:
    return event_name.split("(", 1)[0]


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _host_spans(planes) -> List[Tuple[float, float, str]]:
    spans = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    return spans


def _scope_of(op_name: Optional[str], scopes) -> Optional[str]:
    if not op_name:
        return None
    for part in op_name.split("/"):
        if part in scopes:
            return part
    return None


def reduce_trace(profile, *, scopes: Iterable[str] = (),
                 programs: Optional[Dict[str, Dict[str, str]]] = None,
                 top: int = 10) -> Dict:
    """Busy time, idle share, device time per scope and the breakdown.

    ``profile`` is a ``jax.profiler.ProfileData``; ``scopes`` the layer
    names given to ``jax.named_scope``; ``programs`` maps a module name to
    its {instruction: op_name} table (:func:`op_names`).  Raises when the
    trace has no ``bench.window`` span or no device plane.
    """
    scopes = set(scopes)
    programs = programs or {}
    planes = list(profile.planes)
    spans = _host_spans(planes)
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} host span")
    w0, w1 = min(s for s, _ in windows), max(e for _, e in windows)
    devices = [p for p in planes if p.name.startswith("/device:TPU:")]
    if not devices:
        raise ValueError("trace has no /device:TPU plane")
    busy_ns, scope_ns, group_ns = [], defaultdict(float), defaultdict(float)
    gaps: List[Tuple[float, float]] = []
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        runs = []
        if "XLA Modules" in lines:
            runs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                           _module(ev.name))
                          for ev in lines["XLA Modules"].events)
        ops = []
        if "XLA Ops" in lines:
            ops = sorted((ev.start_ns, ev.duration_ns, ev.name)
                         for ev in lines["XLA Ops"].events)
        ops = [(max(s, w0), min(s + d, w1), name) for s, d, name in ops
               if s + d > w0 and s < w1]
        # module run of each op (runs do not overlap on one core)
        run_i, pending, last_run = 0, [], None
        for s, e, name in ops:
            while run_i < len(runs) and runs[run_i][1] < s:
                run_i += 1
            run = runs[run_i] if run_i < len(runs) and runs[run_i][0] <= s \
                else None
            module = run[2] if run else ""
            if run is not last_run:
                for d, mod, nm in pending:
                    group_ns[mod or _instr(nm).split(".")[0]] += d
                pending, last_run = [], run
            scope = _scope_of(programs.get(module, {}).get(_instr(name)),
                              scopes)
            if scope is None:
                pending.append((e - s, module, name))
                continue
            for d, _, _ in pending:
                scope_ns[scope] += d
                group_ns[scope] += d
            pending = []
            scope_ns[scope] += e - s
            group_ns[scope] += e - s
        # an unscoped op after the last scoped one of its run keeps its
        # module (or, outside any program, its instruction) name
        for d, module, name in pending:
            group_ns[module or _instr(name).split(".")[0]] += d
        union = _union((s, e) for s, e, _ in ops)
        busy_ns.append(sum(e - s for s, e in union))
        edges = [w0] + [x for iv in union for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = len(devices)
    window_s = (w1 - w0) * 1e-9
    busy_s = sum(busy_ns) / n_dev * 1e-9

    def gap_name(a: float, b: float) -> str:
        mid = 0.5 * (a + b)
        open_ = sorted({n for s, e, n in spans
                        if s <= mid <= e and n != WINDOW_SPAN})
        return "+".join(open_) or "none"

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "scope_s": {k: v / n_dev * 1e-9 for k, v in scope_ns.items()},
        "device_ops": [[k, v / n_dev * 1e-9] for k, v in
                       sorted(group_ns.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[gap_name(a, b), (b - a) * 1e-9]
                      for a, b in gaps[:top]],
    }
