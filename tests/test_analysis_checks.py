"""repro.analysis.kernel_checks: static Pallas launch verification.

The checker consumes the same FusedGeometry the kernel launches from, so
these tests assert three things: real geometries are clean and match the
kernel's own arithmetic, corrupted geometries (dataclasses.replace) trip
the right finding codes, and the autotune/batcher integrations actually
consult the checker (tiny monkeypatched VMEM limit changes behaviour).
"""
import dataclasses

import pytest

from repro.analysis import kernel_checks as kc
from repro.api import plan, registry, tuning
from repro.api.spec import ConvSpec
from repro.kernels import sfc_fused as sf
from repro.quant.fake_quant import QuantConfig

Q88 = QuantConfig(enabled=True, bits_act=8, bits_weight=8)
ALGO = registry.get_algorithm("sfc4_4")


def test_real_geometries_are_clean():
    for args in [(2, 12, 12, 16, 24), (1, 28, 28, 64, 128),
                 (4, 7, 7, 130, 48)]:
        geom = sf.fused_geometry(ALGO, *args)
        assert kc.check_geometry(geom) == [], args
    dw = sf.fused_geometry(ALGO, 2, 8, 8, 20, 20, depthwise=True)
    assert kc.check_geometry(dw) == []
    # auto rows + double buffer resolve to a clean launch too
    auto = sf.fused_geometry(ALGO, 4, 32, 32, 64, 64, rows_per_step=None,
                             double_buffer=True)
    assert kc.check_geometry(auto) == []


def test_geometry_matches_kernel_docstring_values():
    # hand-derived reference launch from the sfc_fused docstring/smoke:
    # B=2 12x12 16->24 with sfc4_4 (M=4, t=7), one tile-row a step
    geom = sf.fused_geometry(ALGO, 2, 12, 12, 16, 24, rows_per_step=1)
    assert geom.grid == (6, 1, 1)
    # the strip is padded to the chip's tiling: 14 -> 16 columns (8
    # sublanes), 16 -> 128 input channels (lanes)
    assert geom.strip_shape == (1, 6, 16, 128)
    assert geom.vmem_bytes() == 458208
    assert geom.scratch_shapes() == (
        ("acc", (49, 3, 24), "int32"), ("stage", (49, 3, 128), "float32"),
        ("strip_buf", (1, 1, 6, 16, 128), "float32"))
    assert geom.rmw_axis == 2
    dw = sf.fused_geometry(ALGO, 2, 8, 8, 20, 20, rows_per_step=1,
                           depthwise=True)
    assert dw.grid == (4, 1)
    assert dw.kb == dw.cb == 128 and dw.n_k == 1
    assert dw.scratch_shapes() == (
        ("y", (7, 7, 2, 128), "float32"),
        ("strip_buf", (1, 1, 6, 16, 128), "float32"))
    assert dw.rmw_axis is None


def test_kc001_vmem_limit():
    geom = sf.fused_geometry(ALGO, 2, 12, 12, 16, 24)
    findings = kc.check_geometry(geom, vmem_limit=100)
    assert [f.code for f in findings] == ["KC001"]
    assert str(geom.vmem_bytes()) in findings[0].message


def test_kc002_strip_and_blocking_corruptions():
    geom = sf.fused_geometry(ALGO, 2, 12, 12, 16, 24)
    # under-tiled C_in: channels silently dropped
    assert "KC002" in {f.code for f in kc.check_geometry(
        dataclasses.replace(geom, n_k=0))}
    # over-tiled C_out
    assert "KC002" in {f.code for f in kc.check_geometry(
        dataclasses.replace(geom, n_o=geom.n_o + 1))}
    # strip group taller than the padded input: out-of-bounds read
    assert "KC002" in {f.code for f in kc.check_geometry(
        dataclasses.replace(geom, x_rows=geom.x_rows - 1))}
    # grouped images not covering the batch
    assert "KC002" in {f.code for f in kc.check_geometry(
        dataclasses.replace(geom, g_b=geom.g_b + 1, B=geom.B + 1))}


def test_kc003_dma_slot_aliasing():
    geom = sf.fused_geometry(ALGO, 2, 12, 12, 16, 24)
    # double-buffer prefetch landing in the in-flight slot
    aliased = dataclasses.replace(geom, double_buffer=True,
                                  db_prefetch_distance=2)
    assert [f.code for f in kc.check_geometry(aliased)] == ["KC003"]

    # an RMW axis that is not innermost leaves scratch accumulation
    # order undefined across grid dims
    class BadRmw(sf.FusedGeometry):
        @property
        def rmw_axis(self):
            return 0
    bad = BadRmw(**{f.name: getattr(geom, f.name)
                    for f in dataclasses.fields(geom)})
    assert any(f.code == "KC003" for f in kc.check_geometry(bad))


def test_kc003_leaky_out_index():
    # a 2-k-block geometry whose out_index leaks the k axis must trip
    # KC003; the uncorrupted counterpart is clean
    geom = sf.fused_geometry(ALGO, 2, 12, 12, 256, 24, k_block=128)
    assert geom.n_k == 2 and kc.check_geometry(geom) == []

    class LeakyGeom(sf.FusedGeometry):
        def out_index(self, i, j, k):
            return (i // self.g_h, i % self.g_h, k, j)
    leaky = LeakyGeom(**{f.name: getattr(geom, f.name)
                         for f in dataclasses.fields(geom)})
    assert any(f.code == "KC003" for f in kc.check_geometry(leaky))


def test_default_candidates_clean_on_representative_specs():
    assert kc.default_candidate_report() == []


def test_check_candidates_partitions_on_tiny_limit():
    spec = ConvSpec(kernel_size=3, in_channels=64, out_channels=64,
                    spatial=(14, 14), quant=Q88)
    ok, rejected = kc.check_candidates(spec, ALGO,
                                       tuning.DEFAULT_CANDIDATES)
    assert len(ok) == len(tuning.DEFAULT_CANDIDATES) and not rejected
    ok2, rej2 = kc.check_candidates(spec, ALGO, tuning.DEFAULT_CANDIDATES,
                                    vmem_limit=1000)
    # every fused candidate fails the budget; staged ones pass vacuously
    assert all(c.datapath == "staged" for c in ok2)
    assert all(any(f.code == "KC001" for f in errs) for _, errs in rej2)
    assert {c.datapath for c, _ in rej2} == {"fused"}


def test_autotune_preflight_skips_unlaunchable_candidates(
        deterministic_time_fn, monkeypatch):
    # with a tiny VMEM limit every fused candidate is rejected before
    # timing, so the measured winner must be a staged config
    monkeypatch.setattr(sf, "VMEM_LIMIT_BYTES", 1000)
    spec = ConvSpec(kernel_size=3, in_channels=16, out_channels=16,
                    spatial=(8, 8), quant=Q88)
    msgs = []
    res = tuning.autotune(spec, backend="pallas", algos=["sfc4_4"],
                          reps=1, persist=False, log=msgs.append,
                          include_direct=False)
    assert res["sfc4_4"]["config"]["datapath"] == "staged"
    assert any("rejected by pre-flight" in m and "KC001" in m
               for m in msgs)
    # and no fused candidate was ever timed
    assert not any("fused(" in m and "ms" in m for m in msgs)


def test_batcher_fold_uses_checker(monkeypatch):
    from repro.serve import batcher
    spec = ConvSpec(kernel_size=3, in_channels=64, out_channels=64,
                    spatial=(14, 14), quant=Q88)
    p = plan(spec, backend="pallas", algo="sfc4_4")
    # normal limit: whole batch folds into one grid step
    rps, imgs, rows = batcher.fold_rows_per_step(p, 4)
    assert (rps, imgs, rows) == (16, 4, 4)
    # choked limit: the fold shrinks — proof the batcher consults the
    # checker's geometry rather than private kernel arithmetic.  At 200kB
    # even the ungrouped step is over budget (the int8 weight block alone
    # is 49 * 64 * 64 B), so the fold falls back to the trivial group.
    monkeypatch.setattr(sf, "VMEM_LIMIT_BYTES", 200_000)
    assert batcher.fold_rows_per_step(p, 4) == (1, 1, 1)
    assert not kc.fold_fits(ALGO, p.config or tuning.DEFAULT_FUSED, 4,
                            14, 14, 64, 64, rows_per_step=1)


def test_fold_fits_matches_geometry_budget():
    cfg = tuning.DEFAULT_FUSED
    geom = sf.fused_geometry(ALGO, 2, 28, 28, 64, 64,
                             k_block=cfg.k_block,
                             cout_block=cfg.cout_block, rows_per_step=4,
                             double_buffer=cfg.double_buffer)
    assert kc.fold_fits(ALGO, cfg, 2, 28, 28, 64, 64, rows_per_step=4) \
        == (geom.vmem_bytes() <= sf.VMEM_LIMIT_BYTES)
