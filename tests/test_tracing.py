"""``repro.tracing`` and the spans, scopes and compile counts the program
records with it: the serving engine's span tree on an injected clock, the
conv apply path's named scopes in compiled HLO, and the two benchmark
readers over a synthetic span buffer."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.api import ConvSpec, plan, tuning
from repro.api.serving_cache import ServingCache
from repro.quant import INT8_FREQ
from repro.serve import BucketTable, Engine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from bench import run, xplane  # noqa: E402

CIN, COUT = 4, 8
DISPATCH_CHILDREN = ["serve.queue", "serve.prep", "serve.apply",
                     "serve.device_wait", "serve.resolve"]


class _TickClock:
    """Advances 1 ms on every read, so each stamp is distinct."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-3
        return self.t


def _engine(**kw):
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(3, 3, CIN, COUT) * 0.2, jnp.float32)
    table = BucketTable.for_workload(((8, 8), (12, 12)), kernel_size=3,
                                     in_channels=CIN, out_channels=COUT,
                                     quant=INT8_FREQ)
    return Engine(w, table, **kw)


def _img(h, seed=1):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(h, h, CIN), jnp.float32)


@pytest.fixture(scope="module")
def shared_cache():
    return ServingCache()


# ----------------------------------------------------------------------
# the module
# ----------------------------------------------------------------------
def test_span_nesting_parents_and_attrs():
    tracing.reset()
    with tracing.span("a", k=1) as a:
        with tracing.span("b") as b:
            b.set(n=3, ids=(1, 2))
    assert a.parent is None and b.parent == a.id
    assert b.thread == a.thread
    assert a.start <= b.start <= b.end <= a.end
    assert b.attrs == {"n": 3, "ids": (1, 2)} and a.attrs == {"k": 1}
    assert [s.name for s in tracing.spans()] == ["b", "a"]
    assert tracing.children(a) == [b]
    assert tracing.spans(parent=None) == [a]


def test_self_time_is_duration_less_union_of_children():
    tracing.reset()
    p = tracing.record("p", 0.0, 10.0)
    for a, b in ((1.0, 3.0), (2.0, 5.0), (7.0, 8.0), (9.0, 12.0)):
        tracing.record("c", a, b, parent=p)
    tracing.record("other", 0.0, 10.0)
    # children cover [1, 5] + [7, 8] + [9, 10] (clipped to the parent)
    assert tracing.self_time(p) == pytest.approx(4.0)


def test_buffer_drops_oldest_at_its_bound():
    tracing.reset()
    extra = 5
    for i in range(tracing.MAX_SPANS + extra):
        tracing.record("r", float(i), float(i) + 0.5, i=i)
    kept = tracing.spans("r")
    assert len(kept) == tracing.MAX_SPANS
    assert kept[0].attrs["i"] == extra
    assert kept[-1].attrs["i"] == tracing.MAX_SPANS + extra - 1
    tracing.reset()


def test_compiles_count_against_the_innermost_open_span():
    tracing.reset()
    with tracing.span("outer"):
        with tracing.span("inner"):
            jax.jit(lambda a: a * 3 + 1)(jnp.arange(7.0))
    assert tracing.compiles().get("inner", 0) >= 1
    assert "outer" not in tracing.compiles()


# ----------------------------------------------------------------------
# the serving engine's spans
# ----------------------------------------------------------------------
def test_dispatch_span_tree_on_the_engine_clock(shared_cache):
    clk = _TickClock()
    eng = _engine(max_batch=4, cache=shared_cache, clock=clk)
    tracing.reset()
    futs = [eng.submit(_img(8, seed=s)) for s in (2, 3)]
    assert eng.step() == 2
    res = [f.result(timeout=0) for f in futs]

    submits = tracing.spans("serve.submit")
    assert [s.attrs["request_id"] for s in submits] == \
        [r.request_id for r in res]
    take, = tracing.spans("serve.take")
    disp, = tracing.spans("serve.dispatch")
    assert take.attrs["n"] == 2 and take.parent == disp.parent
    assert take.end <= disp.start
    assert disp.attrs["n_real"] == 2 and disp.attrs["n_padded"] == 2
    assert disp.attrs["request_ids"] == tuple(r.request_id for r in res)
    kids = tracing.children(disp)
    assert sorted(s.name for s in kids) == sorted(
        ["serve.queue"] * 2 + DISPATCH_CHILDREN[1:])
    assert {s.attrs["batch_id"] for s in kids} == {disp.attrs["batch_id"]}
    # the children run in order, inside the dispatch
    steps = [next(s for s in kids if s.name == n)
             for n in DISPATCH_CHILDREN[1:]]
    for a, b in zip(steps, steps[1:]):
        assert a.end <= b.start
    assert disp.start <= steps[0].start and steps[-1].end <= disp.end

    wait = steps[DISPATCH_CHILDREN.index("serve.device_wait") - 1]
    queues = {q.attrs["request_id"]: q for q in kids
              if q.name == "serve.queue"}
    for s, r in zip(submits, res):
        q = queues[r.request_id]
        # the engine's stamps are the spans' stamps
        assert q.start == s.start and q.end == disp.start
        assert r.queue_wait_ms == (q.end - q.start) * 1e3
        assert r.service_ms == (wait.end - disp.start) * 1e3
        assert r.e2e_ms == (wait.end - s.start) * 1e3


def test_dispatch_thread_spans_nest_under_the_loop(shared_cache):
    tracing.reset()
    eng = _engine(max_batch=4, cache=shared_cache)
    eng.start()
    futs = [eng.submit(_img(12, seed=s)) for s in (4, 5, 6)]
    assert eng.drain(timeout=60)
    eng.stop()
    assert all(f.result(timeout=0).y.shape == (12, 12, COUT) for f in futs)
    loop, = tracing.spans("serve.loop")
    under = tracing.spans(parent=loop)
    assert {s.name for s in under} <= {"serve.take", "serve.dispatch"}
    dispatches = [s for s in under if s.name == "serve.dispatch"]
    assert sum(d.attrs["n_real"] for d in dispatches) == 3
    assert all(loop.start <= s.start and s.end <= loop.end for s in under)
    assert all(s.parent is None for s in tracing.spans("serve.submit"))


def test_buffer_holds_a_30s_serving_window(shared_cache):
    # one request a dispatch is the most spans a request can cost
    eng = _engine(max_batch=1, cache=shared_cache)
    tracing.reset()
    f = eng.submit(_img(8))
    eng.step()
    f.result(timeout=0)
    per_request = len(tracing.spans())
    assert per_request == 3 + len(DISPATCH_CHILDREN)
    rate_hz = 208                          # bench/traffic/serve-poisson.json
    assert 2 * 30 * rate_hz * per_request <= tracing.MAX_SPANS


def test_warmed_engine_serves_every_bucket_and_batch_without_compiling():
    eng = _engine(max_batch=4, cache=ServingCache(), round_batches=True,
                  warm_compile=True)
    for h in (8, 12):
        for n in range(1, 5):              # 3 pads up to the warm shape 4
            futs = [eng.submit(_img(h, seed=10 + i)) for i in range(n)]
            assert eng.step() == n
            for f in futs:
                f.result(timeout=0)
    assert eng.snapshot()["compiles"] == {}


# ----------------------------------------------------------------------
# the conv apply path's scopes, as a device trace names them
# ----------------------------------------------------------------------
def _op_names(fn, x):
    return list(xplane.op_names(jax.jit(fn).lower(x).compile()
                                .as_text())[1].values())


def test_plan_and_sub_plan_scopes_reach_the_hlo():
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(1, 12, 12, 4), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 4, 8) * 0.2, jnp.float32)

    spec = ConvSpec.for_conv2d(x.shape, w.shape, quant=INT8_FREQ)
    fused = plan(spec, backend="pallas", algo="sfc6_6")
    prep = fused.prepare_weights(w, act_scale=tuning.calibrate_act_scale(
        x, fused.algorithm, spec.quant, spec.padding))

    def dense(x):
        with jax.named_scope("convA"):
            return fused.apply(x, prep)

    names = _op_names(dense, x)
    assert any("convA/plan.fused/" in n for n in names)
    assert all(xplane._scope_of(n, {"convA"}) == "convA"
               for n in names if "plan.fused" in n)

    spec2 = ConvSpec.for_conv2d(x.shape, w.shape, stride=2, quant=INT8_FREQ)
    lowered = plan(spec2, backend="pallas", algo="sfc4_4_r2")
    assert lowered.path == "lowered"
    prep2 = lowered.prepare_weights(w, act_scale=lowered.calibrate(x))

    def strided(x):
        with jax.named_scope("convB"):
            return lowered.apply(x, prep2)

    names = _op_names(strided, x)
    assert any("convB/sub0/plan." in n for n in names)
    assert all(xplane._scope_of(n, {"convA", "convB"}) == "convB"
               for n in names if "/sub" in n)


def test_every_fused_launch_of_a_traced_forward_records_its_grouping():
    """One ``kernels.fused_grouping`` record per fused launch, repeated
    shapes and lowered sub-plans included; an eager launch records
    nothing."""
    from repro.kernels import sfc_fused as sf
    rng = np.random.RandomState(8)
    x = jnp.asarray(rng.randn(4, 12, 12, 4), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 4, 4) * 0.2, jnp.float32)
    spec = ConvSpec.for_conv2d(x.shape, w.shape, quant=INT8_FREQ)
    dense = plan(spec, backend="pallas", algo="sfc6_6")
    prep = dense.prepare_weights(w, act_scale=tuning.calibrate_act_scale(
        x, dense.algorithm, spec.quant, spec.padding))
    spec2 = ConvSpec.for_conv2d(x.shape, w.shape, stride=2, quant=INT8_FREQ)
    lowered = plan(spec2, backend="pallas", algo="sfc4_4_r2")
    prep2 = lowered.prepare_weights(w, act_scale=lowered.calibrate(x))
    fast_subs = [sp for sp in lowered.sub_plans if sp.path == "fast"]
    assert fast_subs

    def forward(x):
        # the same shape twice: the kernel's jit traces it once
        return lowered.apply(dense.apply(dense.apply(x, prep), prep), prep2)

    tracing.reset()
    jax.jit(forward).lower(x)
    recs = tracing.spans("kernels.fused_grouping")
    assert len(recs) == 2 + len(fast_subs)
    g = sf.fused_geometry(dense.algorithm, 4, 12, 12, 4, 4)
    assert recs[0].attrs == recs[1].attrs == dict(
        imgs=g.imgs, rows=g.rows, cols=g.cols, grid_steps=g.grid_steps)
    assert g.imgs == 4                     # small maps fold whole images
    tracing.reset()
    jax.block_until_ready(dense.apply(x, prep))
    assert tracing.spans("kernels.fused_grouping") == []


# ----------------------------------------------------------------------
# the benchmark's readers of the engine spans
# ----------------------------------------------------------------------
def _synthetic_window():
    tracing.reset()
    old = tracing.record("serve.loop", 0.0, 1.0)
    tracing.record("serve.dispatch", 0.1, 0.9, parent=old)
    loop = tracing.record("serve.loop", 10.0, 20.0)
    # three dispatches: 4, 2 and 6 ms long, 1, 1 and 3 ms on the device
    for t0, dur, dev in ((11.0, 4e-3, 1e-3), (12.0, 2e-3, 1e-3),
                         (13.0, 6e-3, 3e-3)):
        d = tracing.record("serve.dispatch", t0, t0 + dur, parent=loop)
        tracing.record("serve.prep", t0, t0 + 1e-4, parent=d)
        tracing.record("serve.device_wait", t0 + 1e-4, t0 + 1e-4 + dev,
                       parent=d)
    tracing.record("serve.take", 10.0, 11.0, parent=loop)
    tracing.record("serve.dispatch", 30.0, 30.5)   # a warm-up step()


def test_readers_read_the_newest_loop_on_a_synthetic_buffer():
    _synthetic_window()
    rec = {"kind": "serve"}
    # host ms: 3, 1 and 3 -> median 3
    assert run.read_metric("dispatch_host_ms", rec) == pytest.approx(3.0)
    # 12 ms of dispatches in a 10 s loop
    assert run.read_metric("dispatch_busy_share", rec) == \
        pytest.approx(100.0 * 12e-3 / 10.0)
    tracing.reset()
    assert run.read_metric("dispatch_host_ms", rec) is None
    assert run.read_metric("dispatch_busy_share", rec) is None
