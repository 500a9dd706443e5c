"""Fused single-pass Pallas SFC kernel + measured-latency planner tests.

Parity contract: the fused kernel must match the ``reference`` backend's
static-int8 simulation to the API's existing epsilon (rtol/atol 1e-4) and
the staged Pallas pipeline bit-for-bit (identical integer grid + scales).
The parity matrix itself lives in the shared oracle
(``repro.testing.assert_conv_conformance``) that
``tests/test_conformance.py`` fuzzes; the cases here pin the specific
shapes this kernel has regressed on plus the planner plumbing.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ConvSpec, plan, select_algorithm, tuning
from repro.core import conv2d as c2d
from repro.core.generator import generate_sfc
from repro.kernels import ops, ref
from repro.kernels.sfc_fused import sfc_fused_conv2d
from repro.kernels.sfc_tdmm import tdmm_int8
from repro.quant.fake_quant import INT4_FREQ, INT8_FREQ
from repro.testing import assert_conv_conformance

REGISTRY_ALGOS = ["sfc4_4", "sfc6_6", "sfc6_7"]

# hermetic tuning cache: the autouse fixture in conftest.py points
# REPRO's timing cache at a per-test tmp path

# tier-1 keeps one cheap variant slice per case; the conformance suite
# (and its CI job) covers the full variant grid
FAST_VARIANTS = (dict(k_block=128, rows_per_step=1),)


# ---------------------------------------------------------------------------
# fused kernel vs reference backend / staged pipeline (shared oracle)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("algo_name", REGISTRY_ALGOS)
@pytest.mark.parametrize("padding", ["SAME", "VALID"])
def test_fused_backend_parity(algo_name, padding):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 13, 13, 16), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 16, 8) * 0.2, jnp.float32)
    spec = ConvSpec.for_conv2d(x.shape, w.shape, padding=padding,
                               quant=INT8_FREQ)
    assert (plan(spec, backend="pallas", algo=algo_name).config
            or tuning.DEFAULT_FUSED).datapath == "fused"
    assert_conv_conformance(x, w, spec, algo_name, variants=FAST_VARIANTS)


@pytest.mark.parametrize("shape,cout", [
    ((1, 9, 11, 5), 7),        # odd spatial, tiny ragged channels
    ((1, 17, 13, 19), 21),     # odd spatial, C_in/C_out not block multiples
])
def test_fused_odd_shapes_and_ragged_channels(shape, cout):
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(*shape), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, shape[-1], cout) * 0.2, jnp.float32)
    spec = ConvSpec.for_conv2d(x.shape, w.shape, quant=INT8_FREQ)
    assert_conv_conformance(x, w, spec, "sfc6_6", variants=FAST_VARIANTS)


def test_fused_sub8bit_policy_uses_spec_bits():
    """INT4 policy must clip on the +/-7 grid, not the int8 carrier's."""
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(1, 12, 12, 12), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 12, 6) * 0.2, jnp.float32)
    spec = ConvSpec.for_conv2d(x.shape, w.shape, quant=INT4_FREQ)
    assert_conv_conformance(x, w, spec, "sfc6_6", variants=FAST_VARIANTS)


def test_fused_xq_cache_disabled_recompute_path(monkeypatch):
    """Multiple C_out blocks with the strip cache too small to use."""
    import repro.kernels.sfc_fused as sf
    monkeypatch.setattr(sf, "XQ_CACHE_BYTES", 0)
    rng = np.random.RandomState(8)
    algo = generate_sfc(6, 6, 3)
    x = jnp.asarray(rng.randn(1, 10, 16, 70), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 70, 48) * 0.1, jnp.float32)
    tx, _ = c2d.transform_input_2d(x, algo)
    act = jnp.abs(tx).max(axis=(0, 1, 2, 5)) / 127 + 1e-9
    tw = c2d.transform_weights_2d(w, algo)
    w_scale = jnp.abs(tw).max(axis=2) / 127 + 1e-12
    wq = ops.quantize_weights(w, algo, w_scale)
    want = ref.quantized_fastconv2d_ref(x, w, algo, act, w_scale)
    got = sfc_fused_conv2d(x, wq, act, w_scale, algo,
                           k_block=32, cout_block=16)
    assert bool(jnp.all(got == want))


def test_fused_large_cin_kblocked_accumulation():
    """C_in beyond one k block: int32 scratch accumulates across k steps."""
    rng = np.random.RandomState(2)
    algo = generate_sfc(6, 6, 3)
    x = jnp.asarray(rng.randn(1, 12, 12, 300), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 300, 40) * 0.05, jnp.float32)
    tx, _ = c2d.transform_input_2d(x, algo)
    act = jnp.abs(tx).max(axis=(0, 1, 2, 5)) / 127 + 1e-9
    tw = c2d.transform_weights_2d(w, algo)
    w_scale = jnp.abs(tw).max(axis=2) / 127 + 1e-12
    wq = ops.quantize_weights(w, algo, w_scale)
    want = ref.quantized_fastconv2d_ref(x, w, algo, act, w_scale)
    # 3 k-steps (300 -> 128+128+44-pad) and a ragged C_out block
    got = sfc_fused_conv2d(x, wq, act, w_scale, algo,
                           k_block=128, cout_block=32)
    assert bool(jnp.all(got == want))   # same integer grid: bit-exact
    # the batched grid accumulates the identical k-step sequence per strip
    batched = sfc_fused_conv2d(x, wq, act, w_scale, algo,
                               k_block=128, cout_block=32, rows_per_step=2)
    assert bool(jnp.all(batched == want))


@pytest.mark.parametrize("algo_name", ["sfc6_6"])
def test_fused_bitexact_vs_staged(algo_name):
    """Fused and staged pipelines share scales/grid: identical outputs."""
    rng = np.random.RandomState(3)
    algo = generate_sfc(6, 6, 3)
    x = jnp.asarray(rng.randn(2, 11, 14, 24), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 24, 10) * 0.2, jnp.float32)
    tx, _ = c2d.transform_input_2d(x, algo)
    act = jnp.abs(tx).max(axis=(0, 1, 2, 5)) / 127 + 1e-9
    tw = c2d.transform_weights_2d(w, algo)
    w_scale = jnp.abs(tw).max(axis=2) / 127 + 1e-12
    wq = ops.quantize_weights(w, algo, w_scale)
    y_fused = sfc_fused_conv2d(x, wq, act, w_scale, algo)
    y_staged = ops.quantized_fastconv2d(x, wq, act, w_scale, algo)
    assert bool(jnp.all(y_fused == y_staged))


# ---------------------------------------------------------------------------
# k-blocked tdmm + single-gather extract_tiles (staged-path satellites)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("P,T,K,N,kb", [(4, 8, 16, 8, 8),
                                        (7, 33, 19, 21, 7),
                                        (5, 40, 300, 24, 128)])
def test_tdmm_kblock_parity(P, T, K, N, kb):
    rng = np.random.RandomState(4)
    xq = jnp.asarray(rng.randint(-127, 128, (P, T, K)), jnp.int8)
    wq = jnp.asarray(rng.randint(-127, 128, (P, K, N)), jnp.int8)
    sx = jnp.asarray(rng.rand(P), jnp.float32)
    sw = jnp.asarray(rng.rand(P, N), jnp.float32)
    want = ref.tdmm_int8_ref(xq, wq, sx, sw)
    got = tdmm_int8(xq, wq, sx, sw, k_block=kb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_extract_tiles_single_gather_parity():
    """Single-gather tiling == the transform_input_2d tiling (oracle)."""
    rng = np.random.RandomState(5)
    algo = generate_sfc(6, 7, 3)
    x = jnp.asarray(rng.randn(2, 13, 17, 5), jnp.float32)
    for padding in ("SAME", "VALID"):
        tiles, geom = ops.extract_tiles(x, algo, padding)
        bt = jnp.asarray(algo.bt(), jnp.float32)
        got = jnp.einsum("ti,ijnc,uj->ntuc", bt, tiles, bt,
                         precision="highest")
        want, _ = c2d.transform_input_2d(x, algo, padding)
        want = want.reshape(-1, algo.t, algo.t, x.shape[-1])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# measured-latency planner: timing cache overrides the BOPs ranking
# ---------------------------------------------------------------------------
def test_seeded_timing_cache_overrides_bops_ranking():
    spec = ConvSpec(rank=2, kernel_size=3, in_channels=64, out_channels=64,
                    spatial=(56, 56), quant=INT8_FREQ)
    bops_pick = select_algorithm(spec)
    assert bops_pick != "direct"            # BOPs favors a fast algorithm
    assert select_algorithm(spec, "pallas") == bops_pick  # nothing measured
    # seed measurements saying direct is fastest on this host
    tuning.record(spec, "pallas", bops_pick, 5e-3)
    tuning.record(spec, "pallas", "direct", 1e-4)
    assert select_algorithm(spec, "pallas") == "direct"
    p = plan(spec, backend="pallas", algo="auto")
    assert p.algo_name == "direct"
    # the reference backend has no measurements: BOPs ranking still applies
    assert select_algorithm(spec, "reference") == bops_pick
    assert plan(spec, backend="reference", algo="auto").algo_name == bops_pick


def test_tuned_config_rides_the_plan():
    spec = ConvSpec(rank=2, kernel_size=3, in_channels=32, out_channels=32,
                    spatial=(24, 24), quant=INT8_FREQ)
    cfg = tuning.KernelConfig(datapath="staged", k_block=64)
    tuning.record(spec, "pallas", "sfc6_6", 2e-3, cfg)
    p = plan(spec, backend="pallas", algo="sfc6_6")
    assert p.config == cfg
    # staged-config plans execute (and agree with the fused default)
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(1, 24, 24, 32), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 32, 32) * 0.1, jnp.float32)
    tx, _ = c2d.transform_input_2d(x, p.algorithm)
    act = jnp.abs(tx).max(axis=(0, 1, 2, 5)) / 127 + 1e-9
    prep = p.prepare_weights(w, act_scale=act)
    y_staged = p.apply(x, prep)
    y_fused = dataclasses.replace(p, config=tuning.DEFAULT_FUSED).apply(
        x, prep)
    assert bool(jnp.all(y_staged == y_fused))


def test_autotune_records_and_planner_consumes(deterministic_time_fn):
    # deterministic_time_fn (conftest) replaces wall-clock with call-order
    # ranks: direct is measured first, so it "wins" reproducibly and the
    # ranking assertion below cannot flake on host-load noise
    spec = ConvSpec(rank=2, kernel_size=3, in_channels=8, out_channels=8,
                    spatial=(12, 12), quant=INT8_FREQ)
    bops_pick = select_algorithm(spec)
    res = tuning.autotune(
        spec, "pallas", algos=["sfc6_6", bops_pick], reps=1,
        candidates=(tuning.DEFAULT_FUSED,))
    assert "sfc6_6" in res and "direct" in res
    measured = tuning.lookup(spec, "pallas")
    assert measured["sfc6_6"]["time_s"] > 0
    # the BOPs-best candidate was timed, so the measured ranking governs
    picked = select_algorithm(spec, "pallas")
    assert picked == min(measured, key=lambda n: measured[n]["time_s"])
    assert picked == "direct"          # measured first => lowest fake time


def test_partial_timing_cache_falls_back_to_bops():
    """A sweep that never timed the BOPs-best candidate must not hide it."""
    spec = ConvSpec(rank=2, kernel_size=3, in_channels=128, out_channels=128,
                    spatial=(28, 28), quant=INT8_FREQ)
    bops_pick = select_algorithm(spec)
    assert bops_pick != "direct"
    tuning.record(spec, "pallas", "direct", 1e-6)   # bops_pick never timed
    assert select_algorithm(spec, "pallas") == bops_pick
