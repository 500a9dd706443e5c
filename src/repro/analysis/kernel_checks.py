"""Static resource/safety checker for fused Pallas kernel launches.

The fused kernel (``repro.kernels.sfc_fused``) exposes its complete
launch geometry as data (:class:`~repro.kernels.sfc_fused.FusedGeometry`)
— grid, channel blocking, strip DMA windows, scratch set, DMA
pipeline constants.  This module verifies, *without launching anything*:

  * **VMEM budget** — the per-grid-step footprint of the geometry fits
    ``VMEM_LIMIT_BYTES`` (a kernel that exceeds it spills or fails to
    allocate on real hardware; interpret mode would happily "run" it);
  * **strip bounds** — every strip DMA read (including the ragged
    last strip group of each image column) lands inside the padded HBM
    extents, and the blocked channel/output axes tile their padded
    extents exactly;
  * **scratch write races** — the int32 accumulator is read-modify-
    written only along the innermost (sequential) C_in grid axis, the
    output block index is independent of that axis (partial accumulator
    state must never flush), and the two-slot double-buffer DMA pipeline
    never lands a prefetch in the slot the current step is consuming
    (prefetch distance vs slot count).

:func:`check_candidates` is the autotuner pre-flight: it filters a
``KernelConfig`` sweep down to launchable candidates so invalid configs
are never timed.  The serving batcher uses :func:`fold_fits` for its
VMEM-aware batch folding instead of re-deriving kernel arithmetic.

This module is the sanctioned out-of-``repro.api`` consumer of
``repro.kernels`` metadata (see ``repro.analysis.lint`` ARCH001).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

from repro.core.generator import BilinearAlgorithm
from repro.kernels import sfc_fused as sf

ERROR = "ERROR"
WARNING = "WARNING"


@dataclasses.dataclass(frozen=True)
class Finding:
    """One static-analysis finding (shared shape with the AST linter)."""

    code: str          # e.g. 'KC001'
    severity: str      # ERROR | WARNING
    message: str
    where: str = ""    # file:line for lint, config/geometry repr here

    def __str__(self) -> str:
        loc = f" [{self.where}]" if self.where else ""
        return f"{self.severity} {self.code}{loc}: {self.message}"


def _grid_corners(extent: int) -> Tuple[int, ...]:
    """First/last indices of one grid axis (bounds are monotone in the
    index maps, so the corners witness any violation)."""
    return (0, extent - 1) if extent > 1 else (0,)


def check_geometry(geom: sf.FusedGeometry, *,
                   vmem_limit: Optional[int] = None) -> List[Finding]:
    """Verify one resolved launch geometry.  Empty list == launchable."""
    findings: List[Finding] = []
    limit = sf.VMEM_LIMIT_BYTES if vmem_limit is None else vmem_limit
    where = (f"grid={geom.grid} kb={geom.kb} cb={geom.cb} "
             f"rows={geom.rows} imgs={geom.imgs} "
             f"db={int(geom.double_buffer)}")

    # KC001 — VMEM budget
    need = geom.vmem_bytes()
    if need > limit:
        findings.append(Finding(
            "KC001", ERROR,
            f"per-grid-step VMEM footprint {need} B exceeds the "
            f"{limit} B limit; the kernel cannot hold one step's strip/"
            f"scratch working set on-chip", where))

    # KC002 — strip/block bounds vs padded HBM extents
    bx, rx, wx, cx = geom.x_extents
    si, ssp, sw, sk = geom.strip_shape
    for i in _grid_corners(geom.grid0):
        for k in _grid_corners(geom.n_k):
            ob, orow, ocol, och = geom.strip_offset(i, k)
            hi = (ob + si, orow + ssp, ocol + sw, och + sk)
            if hi[0] > bx or hi[1] > rx or hi[2] > wx or hi[3] > cx:
                findings.append(Finding(
                    "KC002", ERROR,
                    f"input strip of grid step (i={i}, k={k}) reads "
                    f"[{ob}:{hi[0]}, {orow}:{hi[1]}, {ocol}:{hi[2]}, "
                    f"{och}:{hi[3]}] outside the padded HBM extents "
                    f"{geom.x_extents}", where))
    # the blocked axes must tile their padded extents exactly: a short
    # tiling silently drops channels, an over-tiling reads out of bounds
    if geom.n_k * geom.kb != geom.Cp or geom.Cp < geom.C:
        findings.append(Finding(
            "KC002", ERROR,
            f"C_in blocking n_k*kb = {geom.n_k}*{geom.kb} does not tile "
            f"the padded channel extent Cp={geom.Cp} (C={geom.C})", where))
    if geom.n_o * geom.cb != geom.Op or geom.Op < geom.Cout:
        findings.append(Finding(
            "KC002", ERROR,
            f"C_out blocking n_o*cb = {geom.n_o}*{geom.cb} does not tile "
            f"the padded output extent Op={geom.Op} (Cout={geom.Cout})",
            where))
    if geom.g_b * geom.imgs != geom.B:
        findings.append(Finding(
            "KC002", ERROR,
            f"image grouping g_b*imgs = {geom.g_b}*{geom.imgs} != B="
            f"{geom.B}: grouped steps would read padded images", where))
    if geom.nH_p < geom.nH or geom.grid0 != geom.g_b * geom.g_h:
        findings.append(Finding(
            "KC002", ERROR,
            f"strip-group tiling (g_h={geom.g_h}, rows={geom.rows}, "
            f"nH_p={geom.nH_p}) does not cover nH={geom.nH} tile rows "
            f"or grid0={geom.grid0} != g_b*g_h", where))

    # KC003 — scratch-accumulator write races
    if not geom.depthwise:
        if geom.rmw_axis != len(geom.grid) - 1:
            findings.append(Finding(
                "KC003", ERROR,
                f"accumulator RMW axis {geom.rmw_axis} is not the "
                f"innermost grid axis {len(geom.grid) - 1}: k-blocks "
                f"would interleave with other grid dims and the scratch "
                f"accumulation order is undefined", where))
        for i in _grid_corners(geom.grid0):
            for j in _grid_corners(geom.n_o):
                idx0 = geom.out_index(i, j, 0)
                for k in _grid_corners(geom.n_k):
                    if geom.out_index(i, j, k) != idx0:
                        findings.append(Finding(
                            "KC003", ERROR,
                            f"output block index depends on the k axis at "
                            f"(i={i}, j={j}): partial accumulator state "
                            f"would flush to HBM between k-blocks", where))
    if geom.double_buffer:
        d = geom.db_prefetch_distance
        if d % geom.db_slots == 0:
            findings.append(Finding(
                "KC003", ERROR,
                f"double-buffer prefetch distance {d} aliases the "
                f"in-flight slot (slot count {geom.db_slots}): the "
                f"prefetch DMA would overwrite the strip the current "
                f"step is consuming", where))
        elif not 0 < d < geom.db_slots + 1:
            findings.append(Finding(
                "KC003", WARNING,
                f"double-buffer prefetch distance {d} exceeds the slot "
                f"count {geom.db_slots}; strips would queue more DMA "
                f"than the landing buffer holds", where))
    return findings


def geometry_for(algo: BilinearAlgorithm, config, B: int, H: int, W: int,
                 C: int, Cout: int, *, padding: str = "SAME",
                 depthwise: bool = False) -> sf.FusedGeometry:
    """Resolve the geometry a fused launch of ``config`` would use."""
    return sf.fused_geometry(
        algo, B, H, W, C, Cout, padding=padding,
        k_block=config.k_block, cout_block=config.cout_block,
        rows_per_step=config.rows_per_step,
        double_buffer=config.double_buffer, depthwise=depthwise)


def check_config(algo: BilinearAlgorithm, config, B: int, H: int, W: int,
                 C: int, Cout: int, *, padding: str = "SAME",
                 depthwise: bool = False,
                 vmem_limit: Optional[int] = None) -> List[Finding]:
    """Findings for one ``KernelConfig`` candidate on one workload.

    Staged-datapath configs pass vacuously: the staged kernels run three
    separately blocked ``pallas_call``s whose budgets are set by their
    own (small, shape-independent) tile blocks.
    """
    if getattr(config, "datapath", "fused") != "fused":
        return []
    geom = geometry_for(algo, config, B, H, W, C, Cout, padding=padding,
                        depthwise=depthwise)
    return check_geometry(geom, vmem_limit=vmem_limit)


def check_spec_config(spec, algo: BilinearAlgorithm, config, *,
                      batch: int = 1,
                      vmem_limit: Optional[int] = None
                      ) -> Optional[List[Finding]]:
    """:func:`check_config` from a fully-hinted ``ConvSpec``.

    Returns None when the spec lacks the shape hints needed to resolve a
    geometry (the dynamic conformance tests cover those) or is not a
    rank-2 fast-path shape.
    """
    if spec.rank != 2 or spec.spatial is None \
            or spec.in_channels is None or spec.out_channels is None:
        return None
    H, W = spec.spatial
    return check_config(algo, config, batch, H, W, spec.in_channels,
                        spec.out_channels, padding=spec.padding,
                        depthwise=spec.depthwise, vmem_limit=vmem_limit)


def check_candidates(spec, algo: BilinearAlgorithm,
                     candidates: Sequence, *, batch: int = 1,
                     vmem_limit: Optional[int] = None):
    """Partition a candidate sweep into (launchable, rejected).

    ``rejected`` pairs each dropped config with its ERROR findings; the
    autotuner logs and skips them instead of timing a kernel that would
    fail (or silently spill) on hardware.
    """
    ok, rejected = [], []
    for cfg in candidates:
        findings = check_spec_config(spec, algo, cfg, batch=batch,
                                     vmem_limit=vmem_limit)
        errors = [f for f in (findings or []) if f.severity == ERROR]
        if errors:
            rejected.append((cfg, errors))
        else:
            ok.append(cfg)
    return ok, rejected


def fold_fits(algo: BilinearAlgorithm, config, batch: int, H: int, W: int,
              C: int, Cout: int, *, padding: str = "SAME",
              rows_per_step: int) -> bool:
    """Whether folding ``rows_per_step`` into one grid step fits VMEM.

    The serving batcher's view of the kernel's grouping arithmetic: the
    geometry is resolved exactly as ``sfc_fused_conv2d`` would resolve a
    dispatch of ``batch`` images at this folding, and the decision is its
    VMEM budget — so the batcher never requests a grid step the kernel
    would spill on, without re-deriving kb/cb/cache arithmetic by hand.
    """
    geom = sf.fused_geometry(
        algo, batch, H, W, C, Cout, padding=padding,
        k_block=config.k_block, cout_block=config.cout_block,
        rows_per_step=rows_per_step,
        double_buffer=config.double_buffer)
    return geom.vmem_bytes() <= sf.VMEM_LIMIT_BYTES


def default_candidate_report(*, bits_act: int = 8, bits_weight: int = 8
                             ) -> List[Finding]:
    """Check every DEFAULT_CANDIDATES config against a representative
    workload sweep (the CI ``analysis`` job's kernel gate)."""
    from repro.api import registry
    from repro.api.spec import ConvSpec
    from repro.api.tuning import DEFAULT_CANDIDATES
    from repro.quant.fake_quant import QuantConfig
    quant = QuantConfig(enabled=True, bits_act=bits_act,
                        bits_weight=bits_weight)
    findings: List[Finding] = []
    shapes = [(1, 14, 14, 128, 128), (4, 28, 28, 64, 128),
              (1, 224, 224, 64, 64), (8, 7, 7, 512, 512)]
    for entry in registry.entries(taps=3):
        if entry.kind == "winograd":
            continue               # excluded from the int8 fast path
        algo = registry.get_algorithm(entry.name)
        for B, H, W, C, Cout in shapes:
            spec = ConvSpec(kernel_size=3, in_channels=C, out_channels=Cout,
                            spatial=(H, W), quant=quant)
            for cfg in DEFAULT_CANDIDATES:
                got = check_spec_config(spec, algo, cfg, batch=B)
                for f in got or []:
                    findings.append(dataclasses.replace(
                        f, where=f"{entry.name} B{B} {H}x{W} "
                                 f"{C}->{Cout} | {f.where}"))
    return findings
