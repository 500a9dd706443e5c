"""repro.analysis.ranges: static overflow/bit-width verification.

Covers the golden certificate table, the exactness of the safe-C_in
bound (a real int8 x int8 -> int32 contraction wraps one past it and is
exact at it), the plan-time pre-flight on integer-datapath backends, the
tightness of the 2-D transform bound, and (slow tier) a hypothesis fuzz
of observed vs predicted transform-domain ranges.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import ranges
from repro.api import plan, registry
from repro.api.spec import ConvSpec
from repro.core import conv2d as c2d
from repro.quant.fake_quant import QuantConfig

Q88 = QuantConfig(enabled=True, bits_act=8, bits_weight=8)


# --------------------------------------------------------------------------
# golden certificate table (derived from the exact Fraction matrices;
# a generator change that alters transform growth must show up here)
# --------------------------------------------------------------------------
GOLDEN = {
    # name: (M, R, t, bt_l1, transform_hi, transform_bits, at_l1, integer)
    "sfc4_4":    (4, 3, 7, 4.0, 2032, 12, 3.0, True),
    "sfc4_4_r2": (4, 2, 6, 4.0, 2032, 12, 2.5, True),
    "sfc4_5_r2": (5, 2, 7, 4.0, 2032, 12, 2.5, True),
    "sfc6_6":    (6, 3, 10, 6.0, 4572, 14, 8 / 3, True),
    "sfc6_6_r4": (6, 4, 12, 6.0, 4572, 14, 11 / 3, True),
    "sfc6_7":    (7, 3, 12, 6.0, 4572, 14, 11 / 3, True),
    "sfc6_7_r2": (7, 2, 10, 6.0, 4572, 14, 8 / 3, True),
    "wino2":     (2, 3, 4, 2.0, 508, 10, 3.0, False),
    "wino4":     (4, 3, 6, 10.0, 12700, 15, 19.0, False),
}


def test_certificate_golden_table():
    certs = ranges.all_certificates()
    assert set(certs) == set(GOLDEN), "registry/golden table drifted"
    for name, (M, R, t, l1, hi, bits, at_l1, integer) in GOLDEN.items():
        c = certs[name]
        assert (c.M, c.R, c.t) == (M, R, t), name
        assert c.bt_row_l1 == pytest.approx(l1), name
        assert c.transform_hi == hi, name
        assert c.transform_bits == bits, name
        assert c.at_row_l1 == pytest.approx(at_l1), name
        assert c.integer_transform is integer, name
        # shared stage-3/4 facts at 8/8 bits
        assert c.product_hi == 127 * 127
        assert c.safe_cin == ranges.safe_cin_bound() == 133144
        assert c.acc_bits_at_safe_cin == 32
        assert c.dequant_exact_cin == 2 ** 24 // (127 * 127) == 1040
        # 2-D growth is the separable square of the 1-D row norm
        assert c.transform_growth_2d == pytest.approx(l1 * l1)


def test_certificate_headroom_and_json_roundtrip():
    c = ranges.certificate(registry.get_algorithm("sfc4_4"))
    assert c.headroom_bits(64) > 0
    assert c.headroom_bits(c.safe_cin) == 0
    assert c.headroom_bits(c.safe_cin + 1) <= 0
    j = c.to_json()
    assert j["safe_cin"] == c.safe_cin and j["algo"] == c.algo


def test_transform_bits_matches_historical_bops_formula():
    # the shared helper must stay bit-identical to the expression the
    # BOPs model inlined historically — rankings must not move
    for e in registry.entries():
        algo = registry.get_algorithm(e.name)
        row_l1 = max(int(sum(abs(v) for v in row)) for row in algo.BT)
        legacy = 8 + max(1, math.ceil(math.log2(max(row_l1, 2))))
        assert ranges.transform_bits_1d(algo, 8) == legacy, e.name


# --------------------------------------------------------------------------
# the bound is exact: the real accumulator wraps one past it
# --------------------------------------------------------------------------
def _int8_contraction(k: int) -> int:
    """Worst-case K-length int8 x int8 contraction through the same
    primitive/accumulator the kernels use (lax dot, int32 preferred)."""
    a = jnp.full((1, k), 127, dtype=jnp.int8)
    b = jnp.full((k, 1), 127, dtype=jnp.int8)
    out = jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return int(out[0, 0])


def test_safe_cin_bound_is_exact():
    bound = ranges.safe_cin_bound()
    assert bound == (2 ** 31 - 1) // (127 * 127)
    # at the bound: exact
    assert _int8_contraction(bound) == bound * 127 * 127
    # one past the bound: the int32 accumulator actually wraps —
    # this is the mis-accumulation the plan() pre-flight prevents
    wrapped = _int8_contraction(bound + 1)
    assert wrapped != (bound + 1) * 127 * 127
    assert wrapped < 0


def test_check_contraction_error_names_bound():
    with pytest.raises(ranges.AccumulatorOverflowError) as ei:
        ranges.check_contraction(ranges.safe_cin_bound() + 1, 8, 8,
                                 context=" (unit test)")
    msg = str(ei.value)
    assert str(ranges.safe_cin_bound()) in msg
    assert "unit test" in msg


# --------------------------------------------------------------------------
# plan-time pre-flight
# --------------------------------------------------------------------------
def _overflow_spec(cin: int = 200_000) -> ConvSpec:
    return ConvSpec(kernel_size=3, in_channels=cin, out_channels=8,
                    spatial=(8, 8), quant=Q88)


def test_plan_rejects_overflow_spec_on_integer_backends():
    for backend in ("pallas", "pallas_spmd"):
        with pytest.raises(ranges.AccumulatorOverflowError) as ei:
            plan(_overflow_spec(), backend=backend, algo="sfc4_4")
        assert str(ranges.safe_cin_bound()) in str(ei.value)


def test_plan_allows_overflow_spec_on_reference_backend():
    # the reference backend fake-quantizes in f32 — no int32 to wrap
    p = plan(_overflow_spec(), backend="reference", algo="sfc4_4")
    assert p.path == "fast" and p.algo_name == "sfc4_4"


def test_plan_boundary_cases_on_pallas():
    bound = ranges.safe_cin_bound()
    ok = plan(_overflow_spec(bound), backend="pallas", algo="sfc4_4")
    assert ok.algo_name == "sfc4_4"
    with pytest.raises(ranges.AccumulatorOverflowError):
        plan(_overflow_spec(bound + 1), backend="pallas", algo="sfc4_4")
    # unquantized, depthwise (K=1), and grouped-under-bound specs pass
    assert plan(ConvSpec(kernel_size=3, in_channels=bound + 1,
                         out_channels=8, spatial=(8, 8)),
                backend="pallas", algo="sfc4_4").spec.in_channels \
        == bound + 1
    dw = ConvSpec(kernel_size=3, depthwise=True, in_channels=bound + 8,
                  out_channels=bound + 8, spatial=(8, 8), quant=Q88)
    assert plan(dw, backend="pallas", algo="sfc4_4") is not None


def test_autotune_skips_overflowing_algorithm(deterministic_time_fn):
    # autotune over a spec no integer algorithm may run: every fast algo
    # is skipped with a logged reason, only direct is measured
    from repro.api import tuning
    spec = ConvSpec(kernel_size=3, in_channels=ranges.safe_cin_bound() + 1,
                    out_channels=8, spatial=(4, 4), quant=Q88)
    msgs = []
    res = tuning.autotune(spec, backend="pallas", algos=["sfc4_4"],
                          reps=1, persist=False, log=msgs.append)
    assert list(res) == ["direct"]
    assert any("skipped" in m and "sfc4_4" in m for m in msgs)


# --------------------------------------------------------------------------
# tightness of the transform bound
# --------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["sfc4_4", "sfc6_6", "wino4"])
def test_transform_bound_is_achieved(name):
    # X = qmax * sign(outer(bt_u, bt_u)) drives frequency (u, u) to
    # exactly qmax * ||B^T_u||_1^2 — the certificate's transform_hi
    algo = registry.get_algorithm(name)
    cert = ranges.certificate(algo)
    bt = np.array([[float(v) for v in row] for row in algo.BT])
    u = int(np.argmax(np.abs(bt).sum(axis=1)))
    x = 127.0 * np.sign(np.outer(bt[u], bt[u]))
    x = x[None, :, :, None]                       # (1, L, L, 1)
    tx = np.einsum("ti,bijc,uj->btuc",
                   bt, x, bt)
    peak = float(np.abs(tx).max())
    assert peak == pytest.approx(cert.transform_hi, rel=1e-6)
    # and nothing exceeds the bound
    assert peak <= cert.transform_hi * (1 + 1e-9)


def test_transform_interval_contains_random_inputs():
    rng = np.random.default_rng(0)
    for name in ("sfc4_4", "sfc6_7", "wino2"):
        algo = registry.get_algorithm(name)
        hi = ranges.transform_interval_hi(algo, 127.0)
        x = rng.integers(-127, 128,
                         size=(2, algo.L, algo.L, 3)).astype(np.float32)
        tx, _ = c2d.transform_input_2d(jnp.asarray(x), algo,
                                       padding="VALID")
        assert float(jnp.max(jnp.abs(tx))) <= hi + 1e-4


# --------------------------------------------------------------------------
# prepare-time transform-matrix cache (the hoisted call-time cast)
# --------------------------------------------------------------------------
def test_transform_matrices_cached_and_frozen():
    algo = registry.get_algorithm("sfc4_4")
    a = c2d.transform_matrices(algo, "float32")
    b = c2d.transform_matrices(algo, "float32")
    assert all(x is y for x, y in zip(a, b))      # one entry per (algo, dtype)
    assert a[0].dtype == jnp.float32
    bt16 = c2d.transform_matrices(algo, "bfloat16")[0]
    assert bt16.dtype == jnp.bfloat16
    # the exact-matrix memo on the algorithm itself is immutable
    f64 = algo.bt()
    assert f64 is algo.bt()
    with pytest.raises(ValueError):
        f64[0, 0] = 99.0


def test_cached_matrices_bit_identical_to_call_time_cast():
    # the sfc_transform kernels used to cast bt at every call; they now
    # take the algorithm's static coefficients, cached per algorithm, and
    # the cached device matrices must equal a call-time cast bit for bit
    from repro.kernels.sfc_transform import sfc_transform
    algo = registry.get_algorithm("sfc6_6")
    assert c2d.transform_coefficients(algo) is \
        c2d.transform_coefficients(algo)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((5, algo.L, algo.L, 3)),
                    dtype=jnp.float32)
    tiles = jnp.transpose(x, (1, 2, 0, 3))          # (L, L, nT, C)
    bt_cached = c2d.transform_matrices(algo, "float32")[0]
    bt_fresh = jnp.asarray(np.asarray(algo.bt()), jnp.float32)
    assert jnp.array_equal(bt_cached, bt_fresh)
    assert jnp.array_equal(sfc_transform(tiles, algo),
                           sfc_transform(tiles, algo))
    # and the fp reference path agrees with itself across calls
    tx_a, _ = c2d.transform_input_2d(x, algo, padding="VALID")
    tx_b, _ = c2d.transform_input_2d(x, algo, padding="VALID")
    assert jnp.array_equal(tx_a, tx_b)


@pytest.mark.slow
def test_transform_range_fuzz_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=50, deadline=None)
    @hyp.given(st.data())
    def run(data):
        name = data.draw(st.sampled_from(sorted(GOLDEN)))
        algo = registry.get_algorithm(name)
        cert = ranges.certificate(algo)
        vals = data.draw(st.lists(
            st.integers(min_value=-127, max_value=127),
            min_size=algo.L * algo.L, max_size=algo.L * algo.L))
        x = np.array(vals, dtype=np.float64).reshape(algo.L, algo.L)
        bt = np.array([[float(v) for v in row] for row in algo.BT])
        tx = bt @ x @ bt.T
        assert np.abs(tx).max() <= cert.transform_hi + 1e-6

    run()
