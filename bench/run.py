#!/usr/bin/env python3
"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.
Everything the run needs is found by name:

* ``bench/configs/<config>.json`` (the configuration entry's ``file``):
  sizes, arithmetic, ``counted_algo``, ``serve_layer``, the check's limits;
  its ``family`` selects ``bench/graphs/<family>.py``, which builds the
  network and its plain f32 reference;
* ``bench/traffic/<traffic>.json``: the mix; its ``kind`` selects the
  module ``bench/<kind>.py`` (``offline`` or ``serve``);
* ``bench/metrics/<metric>.py``: one reader per per-layer metric.

So a configuration, a mix or a per-layer metric is added as new files
plus new entries in ``BENCHMARK.json``.

The run refuses (exit 2, no result) without a TPU or with fewer chips
than the cell asks for.  Set-up (``setup_s``: process start to window
start) plans, calibrates, prepares, compiles or loads from JAX's
persistent compilation cache (``<checkout>/.jax_cache``, or
``JAX_COMPILATION_CACHE_DIR``) and warms every shape the window uses.
Then it measures for ``--seconds``; with ``--trace 1`` under the
profiler, reporting the per-layer metrics instead of the end-to-end ones.
After the window the program's state is freed and the outputs are
compared with the plain reference; each number compared is printed
beside its limit on the last lines of standard error and under
``checks``, the last key of the result line (standard output's last
line).

Tools for setting the benchmark up, not cells: ``--control int4`` runs the program's int4
path in place of the configured int8 one (the check must then fail), and
``--sweep r1,r2,...`` runs a serving mix's window once per rate after one
set-up, printing one line per rate (the knee search).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_JSON = os.path.join(ROOT, "BENCHMARK.json")
METRICS_DIR = os.path.join(ROOT, "bench", "metrics")
# the script's own directory would put bench/'s modules at top level
sys.path[:] = [p for p in sys.path
               if os.path.abspath(p or ".") != os.path.join(ROOT, "bench")]
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class Run:
    """One run's context, handed to the traffic kind's module."""

    def __init__(self, *, cfg, family, traffic, seed, seconds, quant):
        self.cfg, self.family, self.traffic = cfg, family, traffic
        self.seed, self.seconds, self.quant = seed, seconds, quant
        self.log = log
        self._t = time.perf_counter()

    def phase(self, name: str) -> None:
        """Log the seconds a set-up phase took."""
        t = time.perf_counter()
        log(f"phase {name} {t - self._t:.3f} s")
        self._t = t


def load_cell(bench_json: str, workload: str):
    """(bench, cell, config dict, traffic dict) for ``workload``."""
    with open(bench_json) as f:
        bench = json.load(f)
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    base = os.path.dirname(os.path.abspath(bench_json))
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(base, entry["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(base, "bench", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return bench, cell, cfg, traffic


def applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def read_metric(name: str, rec: Dict):
    """Run ``bench/metrics/<name>.py``'s ``read(rec)``."""
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"),
        os.path.join(METRICS_DIR, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)


def _trace_start():
    import jax
    d = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    return d


def _trace_read(d: str, state: Dict, scopes):
    import glob
    from jax.profiler import ProfileData
    from bench import xplane
    programs = {}
    if "hlo" in state:
        module, names = xplane.op_names(state["hlo"]())
        programs[module] = names
    pb = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    if len(pb) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {d}, found {pb}")
    red = xplane.reduce_trace(ProfileData.from_file(pb[0]), scopes=scopes,
                              programs=programs)
    shutil.rmtree(d, ignore_errors=True)
    return red


def _bound_names(rec: Dict, red: Dict, peaks: Dict):
    """Tag each layer scope of the device-op breakdown with what bounds it."""
    from bench import work
    out = []
    for name, s in red["device_ops"]:
        layer = rec.get("layers", {}).get(name)
        if layer is not None:
            _, bound = work.least_time_s(layer, rec["counted_algo"],
                                         rec["batch"], peaks)
            name = f"{name}:{bound}"
        out.append([name, s])
    return out


def main(argv=None, *, require_tpu: bool = True,
         bench_json: str = BENCH_JSON) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("int4",), default=None)
    ap.add_argument("--sweep", default=None)
    args = ap.parse_args(argv)
    bench, cell, cfg, traffic = load_cell(bench_json, args.workload)

    import jax
    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            log(f"needs a TPU; JAX found {devices[0].platform!r}")
            return 2
        if len(devices) < cell["chips"]:
            log(f"{args.workload} needs {cell['chips']} chips; "
                f"JAX found {len(devices)}")
            return 2
    from repro.runtime import use_compilation_cache
    cache = use_compilation_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"compile cache {cache}")

    quant = dict(cfg["quant"])
    if args.control == "int4":
        quant.update(bits_act=4, bits_weight=4)
    family = importlib.import_module(f"bench.graphs.{cfg['family']}")
    kind = importlib.import_module(f"bench.{traffic['kind']}")
    h = Run(cfg=cfg, family=family, traffic=traffic, seed=args.seed,
            seconds=args.seconds, quant=quant)
    log(f"phase process start to set-up {h._t - T_START:.3f} s")
    state = kind.build(h)
    # set-up's objects leave the collector's view: a full collection in
    # the window then scans only what the window makes
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    log(f"setup_s {setup_s}")

    if args.sweep:
        for rate in (float(r) for r in args.sweep.split(",")):
            h.traffic = dict(traffic, rate_hz=rate)
            win = kind.window(h, state)
            print(json.dumps(kind.sweep_line(rate, win)), flush=True)
        return 0

    tdir = _trace_start() if args.trace else None
    try:
        win = kind.window(h, state)
    finally:
        if tdir is not None:
            jax.profiler.stop_trace()
    used = devices[:cell["chips"]]
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
           for d in used]
    rec = kind.record(h, state, win)
    red = None
    if tdir is not None:
        red = _trace_read(tdir, state, rec.get("layers", {}).keys())
        log(f"device s per scope: {red['scope_s']}")
    # free the program's state before the reference runs
    for k in ("run", "hlo"):
        state.pop(k, None)
    t_check = time.perf_counter()
    checks = kind.check(h, state, win)
    log(f"check took {time.perf_counter() - t_check:.3f} s")

    from repro.api import resilience
    fallbacks = {k: v for k, v in resilience.stats().items()
                 if v and (k == "resilience_apply_failure"
                           or k.startswith("resilience_fallback_"))}
    failed = win["failed"]
    if fallbacks:
        log(f"resilience fallbacks: {fallbacks}")
        failed = max(failed, 1)
    correct = all(math.isfinite(v) and v <= lim
                  for v, lim in checks.values())

    metrics: Dict[str, Dict] = {}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(used),
              "memory_peak_bytes": max(mem)}
    breakdown: Optional[Dict] = None
    if red is None:
        values = dict(kind.e2e(win), setup_s=setup_s)
        log(f"end to end {values}")
        for m in bench["end_to_end"]:
            if applies(m, cell["name"]) and m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        from bench import work
        peaks = work.load_peaks(devices[0].device_kind)
        rec = dict(rec, trace=red, peaks=peaks)
        for m in bench["per_layer"]:
            if applies(m, cell["name"]):
                v = read_metric(m["name"], rec)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = {"device_ops": _bound_names(rec, red, peaks),
                     "idle_gaps": red["idle_gaps"]}
    line = {"correct": correct, "attempted": win["attempted"],
            "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    print(json.dumps(line), flush=True)
    for k, (v, lim) in checks.items():
        print(f"check {k} {v!r} limit {lim!r}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
