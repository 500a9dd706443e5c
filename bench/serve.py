"""Serving traffic: an open loop of single-image requests into
``repro.serve.Engine`` over one layer of the configuration.

The schedule is drawn from the seed: ``round(rate_hz * seconds)``
requests (a fixed count, so every seed offers the same work), their due
times from ``arrivals.py`` (Poisson or bursty MMPP), and the request
shapes in the mix's exact proportions, shuffled.  Each
request's input comes from a pool of ``pool_per_shape`` seeded device
images per shape.  The generator sleeps until each due time and submits;
a request's latency runs from when it was due to when its future
resolved (a done-callback stamp), so a late generator or a stalled
engine shows in it.  The window closes when every request scheduled in
it has resolved (waiting at most ``WAIT_S`` past the last due time).

The check compares a seeded sample of ``check_requests`` served answers
(cropped outputs) with the plain f32 reference conv of the request's own
input: ``served_rel_err`` is the largest relative L2 error.  Only the
sampled requests' answers are kept; the others are dropped as they
resolve, so device memory holds what a server would.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List

import jax
import numpy as np

from bench import arrivals
from bench.graphs import layers as L
from bench.graphs import reference as ref

WAIT_S = 60.0


def schedule(tr: Dict, seconds: float, seed: int) -> List[tuple]:
    """[(due_s, shape_index, pool_index)] sorted by due time."""
    rng = np.random.default_rng(seed)
    n = max(1, int(round(tr["rate_hz"] * seconds)))
    due = arrivals.due_times(tr, n, seconds, rng)
    w = np.asarray(tr["weights"], np.float64)
    counts = np.floor(n * w / w.sum()).astype(int)
    counts[np.argmax(w)] += n - counts.sum()
    shapes = rng.permutation(np.repeat(np.arange(len(w)), counts))
    pool = rng.integers(0, tr["pool_per_shape"], size=n)
    return [(float(t), int(s), int(j)) for t, s, j in zip(due, shapes, pool)]


def build(h) -> Dict:
    from repro.serve import BucketTable, Engine
    from repro.serve.batcher import SchedulerPolicy
    cfg, tr = h.cfg, h.traffic
    convs = L.conv_layers(h.family, cfg)
    layer = next(l for l in convs if l["name"] == cfg["serve_layer"])
    key = L.seed_key(h.seed)
    params = L.make_params(jax.random.fold_in(key, 1), [layer])
    w, b = params[layer["name"]]["w"], params[layer["name"]]["b"]
    shapes = [tuple(s) for s in tr["shapes"]]
    n_pool = tr["pool_per_shape"]
    flat = L.make_images(jax.random.fold_in(key, 3),
                         [(s[0], s[1], layer["cin"]) for s in shapes
                          for _ in range(n_pool)])
    pool = [flat[i * n_pool:(i + 1) * n_pool] for i in range(len(shapes))]
    quant = L.quant_config(h.quant)
    table = BucketTable.for_workload(
        shapes, kernel_size=layer["kernel"], in_channels=layer["cin"],
        out_channels=layer["cout"], quant=quant)
    h.phase("weights and inputs")
    ecfg = tr["engine"]
    eng = Engine(w, table, algo=layer["algo"], max_batch=ecfg["max_batch"],
                 round_batches=ecfg["round_batches"],
                 warm_compile=ecfg["warm_compile"],
                 scheduler=SchedulerPolicy(kind=ecfg["scheduler"]),
                 calib_seed=h.seed % 2 ** 32)
    h.phase("engine warm")
    # serve one batch of every size through the synchronous path, so the
    # request path's own ops (stack, crop) are compiled before the window
    for si in range(len(shapes)):
        for n in _batch_sizes(ecfg["max_batch"]):
            futs = [eng.submit(pool[si][i % n_pool]) for i in range(n)]
            while eng.step():
                pass
            for f in futs:
                f.result()
    h.phase("request path warm")
    return dict(eng=eng, pool=pool, shapes=shapes, layer=layer, w=w, b=b,
                warm=eng.snapshot()["batch_occupancy"])


def _batch_sizes(max_batch: int) -> List[int]:
    sizes, s = [], 1
    while s < max_batch:
        sizes.append(s)
        s *= 2
    return sizes + [max_batch]


def window(h, state: Dict) -> Dict:
    eng, pool = state["eng"], state["pool"]
    sched = schedule(h.traffic, h.seconds, h.seed)
    n = len(sched)
    rng = np.random.default_rng(h.seed + 1)
    sample = set(rng.choice(n, size=min(h.traffic["check_requests"], n),
                            replace=False).tolist())
    done_t = [None] * n
    waits = [None] * n            # queue wait of a served request
    kept = {}                     # the sampled requests' futures
    late = np.zeros(n)
    annotate = jax.profiler.TraceAnnotation
    lock = threading.Lock()
    resolved = [0]
    all_resolved = threading.Event()

    def stamp(k):
        # the answer is read here and dropped: only the sampled futures
        # are held, so device memory holds what a server would
        def cb(f):
            t = time.perf_counter()
            ok = f.exception() is None
            with lock:
                done_t[k] = t
                if ok:
                    waits[k] = f.result().queue_wait_ms
                resolved[0] += 1
                if resolved[0] == n:
                    all_resolved.set()
        return cb

    eng.start()
    t0 = time.perf_counter()
    with annotate("bench.window"):
        for k, (due, si, j) in enumerate(sched):
            delay = t0 + due - time.perf_counter()
            if delay > 0:
                with annotate("bench.generator_sleep"):
                    time.sleep(delay)
            with annotate("bench.submit"):
                late[k] = time.perf_counter() - (t0 + due)
                f = eng.submit(pool[si][j])
            if k in sample:
                kept[k] = f
            f.add_done_callback(stamp(k))
            del f
        with annotate("bench.drain"):
            with lock:
                outstanding = n - resolved[0]
            all_resolved.wait(t0 + sched[-1][0] + WAIT_S
                              - time.perf_counter())
    t1 = time.perf_counter()
    eng.stop()
    h.log(f"generator late p95 {np.percentile(late, 95) * 1e3:.3f} ms, "
          f"max {late.max() * 1e3:.3f} ms; {outstanding} requests "
          f"outstanding when the last was submitted")
    limit_ms = (sched[-1][0] + WAIT_S) * 1e3
    lat, failed = [], 0
    for k in range(n):
        if waits[k] is not None:
            lat.append((done_t[k] - (t0 + sched[k][0])) * 1e3)
        else:
            # never answered, or answered with an error: a miss of any
            # latency limit, counted at the wait limit
            failed += 1
            lat.append(limit_ms)
    return dict(attempted=n, failed=failed, sched=sched, kept=kept,
                lat_ms=lat, queue_wait_ms=[x for x in waits if x is not None],
                late_s=late, outstanding_at_close=outstanding,
                wall_s=t1 - t0)


def check(h, state: Dict, win: Dict) -> Dict:
    conv = jax.jit(lambda x, w, b: ref.conv(x[None], w, b, 1)[0])
    err = 0.0
    for k, f in sorted(win["kept"].items()):
        if not (f.done() and f.exception() is None):
            continue                   # counted in failed already
        _, si, j = win["sched"][k]
        r = np.asarray(conv(state["pool"][si][j], state["w"], state["b"]),
                       np.float64)
        y = np.asarray(f.result().y, np.float64)
        err = max(err, float(np.linalg.norm(y - r) / np.linalg.norm(r)))
    return {"served_rel_err": (err, h.cfg["limits"]["served_rel_err"])}


def record(h, state: Dict, win: Dict) -> Dict:
    occ = state["eng"].snapshot()["batch_occupancy"]
    warm = state["warm"]
    n = occ["dispatches"] - warm["dispatches"]
    imgs = occ["mean"] * occ["dispatches"] - warm["mean"] * warm["dispatches"]
    return dict(kind="serve", queue_wait_ms=win["queue_wait_ms"],
                dispatches=n, imgs_per_dispatch=imgs / n if n else None)


def e2e(win: Dict) -> Dict[str, float]:
    # exact, from the whole list (linear interpolation between ranks)
    return {"p50_ms": float(np.percentile(win["lat_ms"], 50)),
            "p95_ms": float(np.percentile(win["lat_ms"], 95))}


def sweep_line(rate: float, win: Dict) -> Dict:
    """One row of the knee sweep (``run.py --sweep``)."""
    limit_ms = (win["sched"][-1][0] + WAIT_S) * 1e3
    ok = [x for x in win["lat_ms"] if x < limit_ms]
    half = len(ok) // 2
    return {"rate_hz": rate, "requests": win["attempted"],
            "failed": win["failed"],
            **e2e(win),
            "p50_first_half_ms": float(np.median(ok[:half] or [0])),
            "p50_second_half_ms": float(np.median(ok[half:] or [0])),
            "outstanding_at_close": win["outstanding_at_close"],
            "generator_late_p95_ms": float(np.percentile(win["late_s"], 95)
                                           * 1e3)}
