"""``chip_smoke.py`` on the CPU: its phases at tiny widths (Pallas in
interpret mode), its refusal to run without a TPU, its last-line format,
and the resilience contract it depends on — a kernel that cannot run
fails ``ConvPlan.apply`` loudly, while an injected runtime fault still
degrades to the bit-identical staged datapath.
"""
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import chip_smoke  # noqa: E402

from repro import faults  # noqa: E402
from repro.api import ConvSpec, plan, resilience, tuning  # noqa: E402
from repro.configs.resnet18 import CNNConfig  # noqa: E402
from repro.quant.fake_quant import INT8_FREQ  # noqa: E402

TINY_VGG = CNNConfig(name="tiny-vgg", kind="vgg", stages=(1, 2),
                     widths=(8, 16), image_size=16, n_classes=10)


@pytest.fixture(autouse=True)
def _fresh_counters():
    resilience.reset()
    yield
    resilience.reset()


def test_vgg_phase_tiny():
    out = chip_smoke.prepare_vgg_phase(batch=2, image=16, cfg=TINY_VGG)()
    assert set(out["vgg_layer_max_err"]) == {"s0c0", "s1c0", "s1c1"}
    assert all(e < 1e-3 for e in out["vgg_layer_max_err"].values())
    assert out["vgg_rel_err_vs_f32"] < chip_smoke.INT8_REL_ENVELOPE
    assert chip_smoke.fallback_events() == {}


def test_resnet_phase_tiny():
    layers = (("s1tos2", 12, 8, 16, 2, False, "lowered"),
              ("dw3x3", 12, 16, 16, 1, True, "fast"))
    out = chip_smoke.prepare_resnet_phase(batch=2, layers=layers)()
    assert set(out["resnet_layer_max_err"]) == {"s1tos2", "dw3x3"}
    assert chip_smoke.fallback_events() == {}


def test_serve_phase_tiny():
    out = chip_smoke.prepare_serve_phase(hw=12, channels=8, n_requests=5,
                                         max_batch=4)()
    assert out["requests_served"] == 5
    assert out["serve_loop_errors"] == 0


def test_main_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    assert capsys.readouterr().out == ""          # no result printed


def test_last_line_format():
    line = json.loads(chip_smoke.last_line(4))
    d = jax.devices()[0]
    assert line == {"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": 4}}


def test_fallback_events_count_only_failures():
    assert chip_smoke.fallback_events({"resilience_breaker_probe": 2}) == {}
    counters = {"resilience_apply_failure": 1,
                "resilience_fallback_staged": 1,
                "resilience_fallback_reference": 0}
    assert chip_smoke.fallback_events(counters) == {
        "resilience_apply_failure": 1, "resilience_fallback_staged": 1}


# ---------------------------------------------------------------------------
# the resilience contract: no fallback may hide a kernel that cannot run
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def int8_case():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1, 12, 12, 8), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 8, 8) * 0.2, jnp.float32)
    spec = ConvSpec.for_conv2d(x.shape, w.shape, quant=INT8_FREQ)
    p = plan(spec, backend="pallas", algo="sfc6_6")
    act = tuning.calibrate_act_scale(x, p.algorithm, spec.quant)
    prep = p.prepare_weights(w, act_scale=act)
    staged = p.with_config(tuning.DEFAULT_STAGED).apply(x, prep)
    return p, x, prep, staged


@pytest.mark.parametrize("error", [
    AttributeError("module has no attribute 'Unblocked'"),
    TypeError("unexpected keyword argument"),
    NotImplementedError("unsupported shape cast"),
    jax.errors.JaxRuntimeError(
        "INTERNAL: Mosaic failed to compile TPU kernel: unsupported"),
])
def test_kernel_defect_propagates_out_of_apply(int8_case, monkeypatch,
                                               error):
    import repro.kernels.sfc_fused as sf
    p, x, prep, _ = int8_case

    def broken(*args, **kwargs):
        raise error
    monkeypatch.setattr(sf, "sfc_fused_conv2d", broken)
    with pytest.raises(type(error)):
        p.apply(x, prep)
    assert chip_smoke.fallback_events() == {}


def test_injected_fault_still_degrades_to_staged(int8_case):
    p, x, prep, staged = int8_case
    with faults.inject({faults.APPLY_FUSED: faults.FaultSpec()}):
        y = p.apply(x, prep)
    assert np.array_equal(np.asarray(y), np.asarray(staged))
    assert chip_smoke.fallback_events() == {
        "resilience_apply_failure": 1, "resilience_fallback_staged": 1}
