"""Cross-backend differential conformance suite for the conv kernel zoo.

Every test routes through the one shared oracle
(``repro.testing.assert_conv_conformance``): int8 paths must be
bit-identical across the staged pipeline and every fused-kernel
configuration (k-blocking, C_out blocking, the batched multi-tile-row
grid, DMA double-buffering), and fp-close to the reference backend's int8
simulation; fp paths are held to the API epsilon.

Three tiers:

  * a small deterministic corpus (tier-1: runs on every ``pytest -q``) —
    the regression net for the shapes that have bitten before (ragged
    channels, odd spatial, VALID, image folding);
  * an exhaustive deterministic sweep marked ``kernels`` (CI's kernel
    job; minutes of interpret-mode wall-clock);
  * a ``hypothesis`` fuzz layer marked ``slow`` that samples the full
    ConvSpec space — H/W 3..33, ragged C_in/C_out, batch 1..4, every
    registered algorithm, SAME/VALID, k_block/rows_per_step grids.

The VMEM-budget helper that sizes the batched grid is regression-tested
here against the numbers documented in ``sfc_fused.py``'s docstring.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.api import ConvSpec, plan
from repro.core.generator import generate_sfc
from repro.kernels import sfc_fused as sf
from repro.quant.fake_quant import FP32, INT4_FREQ, INT8_FREQ
from repro.testing import assert_conv_conformance

try:
    from hypothesis import HealthCheck, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:           # container without the test extra: the
    HAVE_HYPOTHESIS = False   # deterministic corpus still runs

ALGOS = ["sfc4_4", "sfc6_6", "sfc6_7"]


def _case(b, h, w_, cin, cout, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(b, h, w_, cin), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, cin, cout) * 0.2, jnp.float32)
    return x, w


# ---------------------------------------------------------------------------
# tier-1 deterministic corpus (fast: one algo/variant slice per case)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("algo_name", ALGOS)
@pytest.mark.parametrize("padding", ["SAME", "VALID"])
def test_conformance_core(algo_name, padding):
    """The PR-2 parity matrix, now through the shared oracle (batched +
    double-buffered variants included)."""
    x, w = _case(2, 13, 13, 16, 8)
    spec = ConvSpec.for_conv2d(x.shape, w.shape, padding=padding,
                               quant=INT8_FREQ)
    assert_conv_conformance(x, w, spec, algo_name,
                            variants=(dict(k_block=128, rows_per_step=1),
                                      dict(k_block=64, rows_per_step=2),
                                      dict(rows_per_step=None,
                                           double_buffer=True)))


@pytest.mark.parametrize("shape,cout,rps", [
    ((1, 9, 11, 5), 7, 2),      # odd spatial, tiny ragged channels
    ((1, 17, 13, 19), 21, 4),   # C_in/C_out not block multiples
    ((4, 7, 7, 3), 5, 4),       # nH < rows_per_step: folds whole images
    ((3, 6, 6, 9), 4, 8),       # group exceeds B*nH: clamps to divisors
    ((8, 14, 14, 4), 8, None),  # the default grouping folds whole images
])
def test_conformance_ragged_and_folded(shape, cout, rps):
    x, w = _case(*shape, cout, seed=1)
    spec = ConvSpec.for_conv2d(x.shape, w.shape, quant=INT8_FREQ)
    variants = (dict(k_block=None, rows_per_step=rps),
                dict(k_block=8, cout_block=16, rows_per_step=rps,
                     double_buffer=True))
    if rps is None:
        algo = plan(spec, backend="pallas", algo="sfc6_6").algorithm
        assert sf.fused_geometry(algo, *shape, cout).imgs > 1
        # the default config, bit for bit the ungrouped grid
        variants += (dict(), dict(rows_per_step=1))
    assert_conv_conformance(x, w, spec, "sfc6_6", variants=variants)


def test_conformance_fp_and_direct_paths():
    """fp spec (no shared integer grid: epsilon only) and a stride-2 spec
    that degrades to the direct path on both backends."""
    x, w = _case(2, 12, 12, 8, 6, seed=2)
    assert_conv_conformance(x, w, ConvSpec.for_conv2d(x.shape, w.shape,
                                                      quant=FP32), "sfc6_6")
    assert_conv_conformance(
        x, w, ConvSpec.for_conv2d(x.shape, w.shape, stride=2,
                                  quant=INT8_FREQ), allow_degraded=True)


def test_conformance_int4_policy():
    """Sub-int8 policies clip on their own grid across every variant."""
    x, w = _case(1, 12, 12, 12, 6, seed=3)
    spec = ConvSpec.for_conv2d(x.shape, w.shape, quant=INT4_FREQ)
    assert_conv_conformance(x, w, spec, "sfc6_6",
                            variants=(dict(rows_per_step=2),
                                      dict(rows_per_step=None,
                                           double_buffer=True)))


def test_conformance_xq_cache_disabled(monkeypatch):
    """Batched + double-buffered with the strip cache too small to use:
    the every-step DMA consumption schedule must stay bit-identical."""
    monkeypatch.setattr(sf, "XQ_CACHE_BYTES", 0)
    x, w = _case(1, 10, 16, 70, 48, seed=4)
    spec = ConvSpec.for_conv2d(x.shape, w.shape, quant=INT8_FREQ)
    assert_conv_conformance(
        x, w, spec, "sfc6_6",
        variants=(dict(k_block=32, cout_block=16, rows_per_step=2,
                       double_buffer=True),))


# ---------------------------------------------------------------------------
# VMEM budget helper vs the documented worst case
# ---------------------------------------------------------------------------
def test_vmem_budget_matches_docstring_worst_case():
    """fused_vmem_bytes reproduces the sfc_fused.py budget table: VGG-16
    224x224 with SFC-6(7x7,3x3) at default blocks stays under budget."""
    algo = generate_sfc(6, 7, 3)         # SFC-6(7x7,3x3): t=12, M=7, L=9
    assert (algo.t, algo.M, algo.L) == (12, 7, 9)
    nW, Wp, kb, cb, n_k = 32, 226, 128, 128, 4      # 224x224, C_in 512
    total = sf.fused_vmem_bytes(algo, nW, Wp, kb, cb, n_k=n_k,
                                cache_xq=True)
    # the docstring's itemized terms
    strip = 9 * 226 * 128 * 4
    stage = 144 * 32 * 128 * 4
    xq_cache = 4 * 144 * 32 * 128
    weights = 2 * 144 * 128 * 128
    scales = 2 * 144 * 128 * 4
    acc = 144 * 32 * 128 * 4
    out = 2 * 7 * 7 * 32 * 128 * 4
    assert total == (strip + stage + xq_cache + weights + scales + acc
                     + out)
    assert total <= sf.VMEM_LIMIT_BYTES
    assert xq_cache <= sf.XQ_CACHE_BYTES
    # double-buffering adds one extra strip slot and still fits
    assert sf.fused_vmem_bytes(algo, nW, Wp, kb, cb, n_k=n_k,
                               cache_xq=True, double_buffer=True) \
        == total + strip <= sf.VMEM_LIMIT_BYTES


def test_auto_rows_never_exceeds_budget():
    """auto_rows_per_step's pick always fits; small images batch up,
    the 224x224 worst case does not blow the ceiling."""
    algo = generate_sfc(6, 7, 3)
    for (B, nH, nW, Wp) in [(1, 1, 1, 9), (1, 2, 2, 16), (4, 2, 2, 16),
                            (1, 32, 32, 226), (8, 32, 32, 226)]:
        g = sf.auto_rows_per_step(algo, B, nH, nW, Wp, 128, 128, n_k=4,
                                  n_o=4)
        imgs, rows = sf.grouping(B, nH, g)
        cols = imgs * rows * nW
        cache = sf.cache_fits(4, 4, algo.t ** 2, cols, 128)
        assert sf.fused_vmem_bytes(
            algo, nW, Wp, 128, 128, n_k=4, rows=rows, imgs=imgs,
            cache_xq=cache) <= sf.VMEM_LIMIT_BYTES
        if nH <= 2 and B == 1:
            assert rows == nH, "small images must batch tile-rows"


# VGG-16's 3x3/1 convs at 224x224 (name, H=W, C_in, C_out), run at b32
VGG16 = [("s0c0", 224, 3, 64), ("s0c1", 224, 64, 64),
         ("s1c0", 112, 64, 128), ("s1c1", 112, 128, 128),
         ("s2c0", 56, 128, 256), ("s2c1", 56, 256, 256),
         ("s2c2", 56, 256, 256), ("s3c0", 28, 256, 512),
         ("s3c1", 28, 512, 512), ("s3c2", 28, 512, 512),
         ("s4c0", 14, 512, 512), ("s4c1", 14, 512, 512),
         ("s4c2", 14, 512, 512)]
# ResNet-18's convs with a fused launch (name, H=W, C_in, C_out, kernel,
# stride), run at b1: the 7x7/2 stem and the 3x3/2 transitions lower to
# fused sub-plans
RESNET18 = [("stem", 224, 3, 64, 7, 2)] + [
    (f"s0b{b}.conv{c}", 56, 64, 64, 3, 1) for b in (0, 1) for c in (1, 2)
] + [layer for si, (hw, cin, cout) in enumerate(
    [(56, 64, 128), (28, 128, 256), (14, 256, 512)], start=1)
    for layer in [(f"s{si}b0.conv1", hw, cin, cout, 3, 2)]
    + [(f"s{si}b{b}.conv{c}", hw // 2, cout, cout, 3, 1)
       for b, c in ((0, 2), (1, 1), (1, 2))]]


def _fused_launches(B, hw, cin, cout, kernel=3, stride=1):
    """(algorithm, B, H, W, C_in, C_out, padding) of each fused launch of
    the layer's plan, lowered sub-plans included."""
    spec = ConvSpec.for_conv2d((B, hw, hw, cin),
                               (kernel, kernel, cin, cout), stride=stride,
                               quant=INT8_FREQ)
    p = plan(spec, backend="pallas",
             algo="sfc6_6" if (kernel, stride) == (3, 1) else "auto")
    subs = p.sub_plans if p.path == "lowered" else [p]
    out = [(sp.algorithm, B) + sp.spec.spatial
           + (sp.spec.in_channels, sp.spec.out_channels, sp.spec.padding)
           for sp in subs if sp.path == "fast"]
    assert out, p
    return out


@pytest.mark.parametrize(
    "B,layer", [(32, l) for l in VGG16] + [(1, l) for l in RESNET18],
    ids=[f"vgg16-b32-{l[0]}" for l in VGG16]
    + [f"resnet18-b1-{l[0]}" for l in RESNET18])
def test_default_grouping_policy_on_real_launches(B, layer):
    """The default grouping of every fused launch of VGG-16 at b32 and
    ResNet-18 at b1: it fits the budget, folds only divisors of B, fills
    the MXU's 128 rows or is the widest grouping that fits, and no
    fitting grouping takes fewer grid steps or computes fewer padded
    tile-rows in as many."""
    for algo, B_, H, W, C, Cout, pad in _fused_launches(B, *layer[1:]):
        g = sf.fused_geometry(algo, B_, H, W, C, Cout, padding=pad)
        fits = [f for f in (sf.fused_geometry(algo, B_, H, W, C, Cout,
                                              padding=pad, rows_per_step=r)
                            for r in range(1, B_ * g.nH + 1))
                if f.vmem_bytes() <= sf.VMEM_LIMIT_BYTES]
        assert g.vmem_bytes() <= sf.VMEM_LIMIT_BYTES
        assert B_ % g.imgs == 0
        assert g.cols >= 128 or g.cols == max(f.cols for f in fits)
        assert g.grid_steps == min(f.grid_steps for f in fits)
        assert g.imgs * g.nH_p == min(f.imgs * f.nH_p for f in fits
                                      if f.grid_steps == g.grid_steps)


# the serving fold of conv3_2 (256 -> 256) per bucket and batch 1..8,
# as the grouping default before it resolved from the shape left it
SERVE_FOLD = {56: [(10, 1, 10)] * 8, 48: [(8, 1, 8)] * 8,
              40: [(7, 1, 7), (14, 2, 7)] * 4}


@pytest.mark.parametrize("hw", sorted(SERVE_FOLD))
def test_serving_fold_ignores_the_default_grouping(hw):
    from repro.serve import batcher
    spec = ConvSpec.for_conv2d((1, hw, hw, 256), (3, 3, 256, 256),
                               quant=INT8_FREQ)
    p = plan(spec, backend="pallas", algo="sfc6_6")
    assert [batcher.fold_rows_per_step(p, b) for b in range(1, 9)] \
        == SERVE_FOLD[hw]


def test_grouping_folds_only_divisor_images():
    assert sf.grouping(4, 2, 1) == (1, 1)
    assert sf.grouping(4, 2, 2) == (1, 2)       # rows first
    assert sf.grouping(4, 2, 4) == (2, 2)       # then whole images
    assert sf.grouping(4, 2, 8) == (4, 2)
    assert sf.grouping(3, 1, 4) == (3, 1)       # divisor of B only
    assert sf.grouping(3, 2, 8) == (3, 2)
    assert sf.grouping(5, 1, 4) == (1, 1)       # 5 has no divisor <= 4 but 1
    assert sf.grouping(1, 3, 8) == (1, 3)       # rows clamp to nH


# ---------------------------------------------------------------------------
# exhaustive deterministic sweep (CI kernels job)
# ---------------------------------------------------------------------------
@pytest.mark.kernels
@pytest.mark.parametrize("algo_name", ALGOS)
@pytest.mark.parametrize("padding", ["SAME", "VALID"])
@pytest.mark.parametrize("b,h,w_,cin,cout", [
    (1, 3, 3, 1, 1), (1, 5, 33, 3, 2), (2, 33, 5, 2, 3),
    (3, 15, 21, 40, 24), (4, 8, 8, 130, 70), (1, 24, 24, 260, 140),
])
def test_conformance_sweep(algo_name, padding, b, h, w_, cin, cout):
    x, w = _case(b, h, w_, cin, cout, seed=h * w_ + cin)
    spec = ConvSpec.for_conv2d(x.shape, w.shape, padding=padding,
                               quant=INT8_FREQ)
    assert_conv_conformance(x, w, spec, algo_name)


# ---------------------------------------------------------------------------
# hypothesis fuzz layer (slow; CI kernels job, skipped without hypothesis)
# ---------------------------------------------------------------------------
if HAVE_HYPOTHESIS:
    conv_specs = st.tuples(
        st.integers(1, 4),                      # batch
        st.integers(3, 33), st.integers(3, 33),  # H, W (ragged included)
        st.integers(1, 140),                    # C_in (non-multiples of 128)
        st.integers(1, 140),                    # C_out
        st.sampled_from(ALGOS),
        st.sampled_from(["SAME", "VALID"]),
        st.sampled_from([None, 64, 128]),       # k_block
        st.sampled_from([1, 2, 4]),             # rows_per_step
        st.booleans(),                          # double_buffer
        st.integers(0, 2 ** 31 - 1),            # data seed
    )

    @pytest.mark.slow
    @settings(max_examples=25, deadline=None,
              suppress_health_check=[HealthCheck.too_slow,
                                     HealthCheck.data_too_large])
    @given(conv_specs)
    def test_conformance_fuzz(params):
        (b, h, w_, cin, cout, algo_name, padding, k_block, rps, db,
         seed) = params
        x, w = _case(b, h, w_, cin, cout, seed=seed)
        spec = ConvSpec.for_conv2d(x.shape, w.shape, padding=padding,
                                   quant=INT8_FREQ)
        assert_conv_conformance(
            x, w, spec, algo_name,
            variants=(dict(k_block=k_block, rows_per_step=rps,
                           double_buffer=db),
                      dict(k_block=k_block, rows_per_step=1)))
else:
    @pytest.mark.slow
    def test_conformance_fuzz():
        pytest.skip("hypothesis not installed (pip install -e '.[test]')")
