"""The reduction from a profiler trace to busy time, idle share, device
time per named scope and the breakdown (bench/xplane.py)."""
import gzip
import json
import os
from types import SimpleNamespace as NS

import pytest

from bench import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _profile(ops, modules, host):
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev(*m) for m in modules]),
        NS(name="XLA Ops", events=[_ev(*o) for o in ops]),
        NS(name="Async XLA Ops", events=[_ev("%copy-start = x", 0, 10_000)]),
    ])
    cpu = NS(name="/host:CPU", lines=[
        NS(name="python", events=[_ev(*h) for h in host])])
    return NS(planes=[cpu, dev])


HLO = """HloModule jit_forward, is_scheduled=true
ENTRY %main.1 (x.1: f32[2]) -> f32[2] {
  %convA.1 = f32[2] custom-call(%x.1), metadata={op_name="jit(forward)/convA/pallas_call" stack_frame_id=1}
  %fusion.2 = f32[2] fusion(%convA.1), metadata={op_name="jit(forward)/convB/conv_general_dilated"}
  ROOT %fusion.3 = f32[2] fusion(%fusion.2), metadata={op_name="jit(forward)/jit(relu)/max"}
}
"""


def _synthetic():
    # window 0..1000 ns; one program run 100..700 with four ops, one of
    # them unscoped (a copy, charged to the next scoped op)
    ops = [("%copy.7 = f32[2] copy(%x.1)", 100, 50),
           ("%convA.1 = f32[2] custom-call(%copy.7)", 150, 250),
           ("%fusion.2 = f32[2] fusion(%convA.1)", 400, 100),
           ("%fusion.3 = f32[2] fusion(%fusion.2)", 600, 100),
           # outside the window: ignored
           ("%convA.1 = f32[2] custom-call(%copy.7)", 2000, 100)]
    modules = [("jit_forward(123)", 100, 600), ("jit_forward(123)", 2000, 100)]
    host = [("bench.window", 0, 1000), ("bench.forward_enqueue", 0, 90),
            ("bench.block", 500, 400)]
    module, names = xplane.op_names(HLO)
    assert module == "jit_forward"
    return xplane.reduce_trace(_profile(ops, modules, host),
                               scopes={"convA", "convB"},
                               programs={module: names})


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    red = _synthetic()
    assert red["window_s"] == pytest.approx(1000e-9)
    # ops cover 100..500 and 600..700
    assert red["busy_s"] == pytest.approx(500e-9)
    assert red["idle_share"] == pytest.approx(0.5)


def test_time_per_scope_charges_unscoped_ops_to_the_next_scoped_op():
    red = _synthetic()
    assert red["scope_s"] == pytest.approx({"convA": 300e-9,
                                            "convB": 100e-9})
    ops = dict(red["device_ops"])
    assert ops["convA"] == pytest.approx(300e-9)
    # the trailing unscoped op keeps its program's name
    assert ops["jit_forward"] == pytest.approx(100e-9)


def test_idle_gaps_are_named_by_the_host_span_open_in_them():
    gaps = _synthetic()["idle_gaps"]
    assert [g[0] for g in gaps] == ["bench.block", "bench.forward_enqueue",
                                   "bench.block"]
    assert [g[1] for g in gaps] == pytest.approx([300e-9, 100e-9, 100e-9])


def test_trace_without_window_span_is_refused():
    with pytest.raises(ValueError):
        xplane.reduce_trace(_profile([], [], []))


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5 lite: a jitted program with the
    named scopes convA (a fused SFC pallas_call), poolB and convC (an XLA
    conv), run three times inside ``bench.window``."""
    from jax.profiler import ProfileData
    with gzip.open(os.path.join(DATA, "v5e_trace.xplane.pb.gz")) as f:
        profile = ProfileData.from_serialized_xspace(f.read())
    with open(os.path.join(DATA, "v5e_trace.json")) as f:
        expect = json.load(f)
    module, names = xplane.op_names(expect["hlo"])
    red = xplane.reduce_trace(profile, scopes=expect["scopes"],
                              programs={module: names})
    for key in ("window_s", "busy_s", "idle_share"):
        assert red[key] == pytest.approx(expect[key], rel=1e-9)
    assert red["scope_s"] == pytest.approx(expect["scope_s"], rel=1e-9)
    assert set(red["scope_s"]) == set(expect["scopes"])
    # the pallas_call dominates its scope; everything lies in the window
    assert 0 < red["busy_s"] < red["window_s"]
    assert sum(red["scope_s"].values()) <= red["busy_s"] * (1 + 1e-9)
