"""Pallas TPU kernel: the int8 SFC convolution as ONE fused ``pallas_call``.

The staged pipeline (``repro.kernels.ops.quantized_fastconv2d``) runs three
kernels with two full HBM round-trips of the transform-domain tensor in
between — t^2/M^2 times the input footprint (3.06x for SFC-4(4x4,3x3),
2.78x for SFC-6(6x6,3x3)) — and feeds the first kernel a materialized tile
tensor that duplicates every input element L^2/M^2 times (2.25x / 1.78x).
This kernel keeps the whole pipeline on-chip (EXPERIMENTS.md §Perf):

  grid = (ceil(B/imgs) * ceil(nH/rows), C_out blocks, C_in k-blocks),
  k innermost

Per grid step it
  * DMAs one overlapping (imgs, span, W_padded, k_block) input strip
    group — ``rows`` consecutive tile-rows (span = (rows-1)*M + L) of
    ``imgs`` images — from HBM into a VMEM landing buffer.  The copy is
    synchronous by default; ``double_buffer`` adds a second slot and
    prefetches the next strip group while this one is computed;
  * applies the additions-only B^T X B transform and the per-frequency
    intN quantization with :func:`repro.core.conv2d.separable_2d` — the
    transform arithmetic the staged kernels and the reference simulation
    share — on lane-dense (nW, k_block) slabs read at stride M, and
    stages the quantized strips as the (P, imgs*rows*nW, k_block) matmul
    LHS.  The int8 strips are cached in VMEM across C_out blocks (bounded
    by ``XQ_CACHE_BYTES``; recomputed per block when they do not fit), so
    the transform runs once per (strip group, k-block);
  * runs the t^2 int8 x int8 -> int32 MXU matmuls, one 2-D dot per
    frequency, against the matching weight k-block — the LHS stacks all
    imgs*rows*nW tile columns of the group — and accumulates into an
    int32 VMEM scratch that persists across the C_in k-blocks;
  * on the last k-block dequantizes with the static per-frequency scales
    and applies the correction-term inverse A^T Y A (same shared helper),
    storing the (imgs, rows*M, nW*M) output strip group at stride M.

The transform-domain tensor therefore never touches HBM.

Grouping (``rows_per_step``): ``rows = min(rows_per_step, nH)`` tile-rows
of one image fold into a step; when ``rows_per_step >= nH`` the leftover
factor folds whole images (``imgs = rows_per_step // nH``, clamped to a
divisor of B so no padded images are computed).  ``rows_per_step=None``,
the default, resolves from the launch shape via :func:`auto_rows_per_step`:
the grouping with the fewest grid steps whose per-step footprint
(:func:`fused_vmem_bytes`, the budget math below) fits
``VMEM_LIMIT_BYTES``, with no more padded tile-rows than that step count
needs.  A step costs a fixed ~10 us (t^2 weight blocks through the MXU,
loop and accumulator overhead) plus work per tile column, so fewer,
fuller steps win on a TPU v5e even past the MXU's 128 rows and past the
point where the xq cache no longer fits (PERF.md §5).  All groupings are
bit-identical to ``rows_per_step=1``: the per-strip arithmetic and the
per-column matmul contraction are unchanged, only the grid batching
differs.

VMEM budget per grid step (f32 in, defaults K_BLOCK=COUT_BLOCK=128, the
VGG-16 224x224 worst case with SFC-6(7x7,3x3): L=9, t=12, nW=32, Wp=226,
rows=1, C_in=512 so n_k=4 with the xq cache):
  input strip : 9 * 226 * 128 * 4B          = 1.0 MiB   (x2 double_buffer)
  staged LHS  : 144 * 32 * 128 * 4B         = 2.3 MiB   (f32 quantized)
  xq cache    : 4 * 144 * 32 * 128 * 1B     = 2.3 MiB   (<= XQ_CACHE_BYTES)
  weights     : 2 * 144 * 128 * 128 * 1B    = 4.5 MiB   (pipelined, x2)
  scales      : 2 * 144 * 128 * 4B          = 0.1 MiB   (pipelined, x2)
  int32 acc   : 144 * 32 * 128 * 4B         = 2.3 MiB
  out strip   : 2 * 7 * 224 * 128 * 4B      = 1.5 MiB   (pipelined, x2)
                                             ~ 13.9 MiB < 24 MiB
:func:`fused_vmem_bytes` reproduces exactly these terms (scaled by the
grouping) and is regression-tested against them.  The launch asks the
compiler for ``VMEM_LIMIT_BYTES + VMEM_HEADROOM_BYTES`` of scoped VMEM:
the headroom holds Mosaic's own scratch (per-frequency dot results,
spilled transform slabs), which the budget does not count.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import conv2d as c2d
from repro.core.generator import BilinearAlgorithm
from repro.runtime import resolve_interpret

K_BLOCK = 128
COUT_BLOCK = 128
# the chip's f32 vreg tiling: a block's last dim is a multiple of LANES
# (or the whole axis), its second-to-last a multiple of SUBLANES
LANES = 128
SUBLANES = 8
# cap on the quantized-strip cache that amortizes the input transform
# across C_out blocks (full-K int8 residency of ONE strip group)
XQ_CACHE_BYTES = 4 * 1024 * 1024
# per-step VMEM ceiling the batching helper packs against (the budget
# math is documented in the module docstring and regression-tested in
# tests/test_conformance.py); the launch grants this plus the headroom,
# 32 MiB in all (a v5e TensorCore has 128 MiB of VMEM; the compiler's
# default scoped grant is 16 MiB, so the grant is passed explicitly)
VMEM_LIMIT_BYTES = 24 * 1024 * 1024
VMEM_HEADROOM_BYTES = 8 * 1024 * 1024


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def cache_fits(n_o: int, n_k: int, P: int, cols: int, kb: int) -> bool:
    """Whether the quantized-strip cache is worth allocating: multiple
    C_out blocks to amortize over, and full-K residency of one strip
    group's int8 strips under ``XQ_CACHE_BYTES``.  The ONE predicate both
    the VMEM-budget helper and the kernel wrapper consult — if they
    disagreed, ``auto_rows_per_step`` would budget a scratch the kernel
    does (or does not) allocate."""
    return n_o > 1 and n_k * P * cols * kb <= XQ_CACHE_BYTES


def grouping(B: int, nH: int, rows_per_step: int) -> Tuple[int, int]:
    """Resolve ``rows_per_step`` into ``(imgs, rows)`` folded per step.

    ``rows`` tile-rows of one image always come first; only when the
    requested group exceeds one image's tile-rows does the remainder fold
    whole images — and only divisors of B, so no zero-padded image is
    ever computed.
    """
    g = max(1, rows_per_step)
    rows = min(g, nH)
    imgs = 1
    if g >= nH and B > 1:
        cap = min(B, g // nH)
        imgs = max(d for d in range(1, cap + 1) if B % d == 0)
    return imgs, rows


def _vmem_bytes(t: int, M: int, L: int, n_w: int, w_padded: int,
                kb: int, cb: int, *, n_k: int, rows: int, imgs: int,
                cache_xq: bool, double_buffer: bool) -> int:
    P = t * t
    span = (rows - 1) * M + L
    cols = imgs * rows * n_w               # tile columns folded per step
    strip = imgs * span * w_padded * kb * 4
    if double_buffer:
        strip *= 2
    stage = P * cols * kb * 4              # f32 quantized matmul LHS
    xq_cache = n_k * P * cols * kb if cache_xq else 0   # int8
    # BlockSpec operands are double-buffered by the Pallas pipeline
    weights = 2 * P * kb * cb              # int8
    scales = 2 * P * cb * 4
    acc = P * cols * cb * 4                # int32
    out = 2 * imgs * rows * M * n_w * M * cb * 4
    return strip + stage + xq_cache + weights + scales + acc + out


def fused_vmem_bytes(algo: BilinearAlgorithm, n_w: int, w_padded: int,
                     kb: int, cb: int, *, n_k: int = 1, rows: int = 1,
                     imgs: int = 1, cache_xq: bool = False,
                     double_buffer: bool = False) -> int:
    """Per-grid-step VMEM footprint of the fused kernel, in bytes.

    Reproduces the module docstring's budget table term by term, scaled
    by the (imgs, rows) grouping: input strip group (doubled when
    double-buffered), the f32 staged quantized-strip matmul LHS, the
    optional full-K int8 xq cache, the pipelined weight and scale blocks,
    the int32 accumulator, and the pipelined output strip group.
    """
    return _vmem_bytes(algo.t, algo.M, algo.L, n_w, w_padded, kb, cb,
                       n_k=n_k, rows=rows, imgs=imgs, cache_xq=cache_xq,
                       double_buffer=double_buffer)


def auto_rows_per_step(algo: BilinearAlgorithm, B: int, nH: int, n_w: int,
                       w_padded: int, kb: int, cb: int, *, n_k: int = 1,
                       n_o: int = 1, double_buffer: bool = False) -> int:
    """The budget-fitting grouping with the fewest grid steps.

    Candidates fold tile-rows of one image first — for each count of
    strip groups per image, the fewest rows that reach it, so a step
    computes no padded tile-row it can avoid — then whole images,
    divisors of B only.  The smallest candidate of the fewest steps
    whose footprint fits ``VMEM_LIMIT_BYTES`` wins; 1 (the ungrouped
    grid, which the docstring's worst case shows fits at the default
    block sizes) when none does.
    """
    def steps(g: int) -> int:
        imgs, rows = grouping(B, nH, g)
        return (B // imgs) * -(-nH // rows)

    def fits(g: int) -> bool:
        imgs, rows = grouping(B, nH, g)
        cache = cache_fits(n_o, n_k, algo.t ** 2, imgs * rows * n_w, kb)
        return fused_vmem_bytes(algo, n_w, w_padded, kb, cb, n_k=n_k,
                                rows=rows, imgs=imgs, cache_xq=cache,
                                double_buffer=double_buffer) \
            <= VMEM_LIMIT_BYTES

    rows = sorted({-(-nH // g_h) for g_h in range(1, nH + 1)})
    cands = rows + [d * nH for d in range(2, B + 1) if B % d == 0]
    return min((g for g in cands if fits(g)), key=steps, default=1)


@dataclasses.dataclass(frozen=True)
class FusedGeometry:
    """The complete static launch geometry of one fused-kernel call.

    This is THE description of the grid, blocking, strip reads, and
    scratch allocations — derived once by :func:`fused_geometry` and
    consumed both by :func:`sfc_fused_conv2d` (to build the launch) and
    by the static resource checker (``repro.analysis.kernel_checks``) and
    the serving batcher, so out-of-kernel consumers never re-derive (and
    silently diverge from) the kernel's own arithmetic.

    Shapes are post-padding: ``x_rows``/``w_padded`` are the padded input
    extents the strip DMA reads against (``w_padded`` rounded up to the
    sublane tiling), ``Cp``/``Op`` the padded channel extents (input
    channel blocks are whole multiples of the 128-lane width).
    ``rows_per_step`` is the *resolved* grouping (never None).  For
    depthwise launches ``n_k == 1``, ``kb == cb`` (the shared
    channel block), and ``cache_xq``/``double_buffer`` are forced off —
    there is no reduction to block and no cross-block strip reuse.
    """

    # algorithm tile geometry
    t: int
    M: int
    L: int
    # problem extents (padding already applied where noted)
    B: int
    C: int
    Cout: int
    nH: int                  # tile rows per image
    nW: int                  # tile cols per image
    out_h: int               # unpadded output extents
    out_w: int
    x_rows: int              # padded input rows incl. grouped-grid pad
    w_padded: int            # padded input cols (Wp)
    depthwise: bool
    # channel blocking
    kb: int                  # C_in k-block (== cb for depthwise)
    Cp: int                  # C padded to a multiple of kb
    n_k: int
    cb: int                  # C_out block
    Op: int                  # Cout padded to a multiple of cb
    n_o: int
    # grid batching
    rows_per_step: int       # resolved grouping request
    imgs: int                # whole images folded per step
    rows: int                # tile-rows folded per step
    g_h: int                 # strip groups per image column
    g_b: int                 # image groups (B // imgs)
    nH_p: int                # g_h * rows
    span: int                # input rows read per strip group
    grid0: int               # g_b * g_h
    # features
    cache_xq: bool
    double_buffer: bool
    # double-buffer pipeline constants (the kernel's two-slot DMA scheme)
    db_slots: int = 2
    db_prefetch_distance: int = 1

    # ---- derived ----
    @property
    def P(self) -> int:
        return self.t * self.t

    @property
    def cols(self) -> int:
        """Tile columns stacked into the matmul LHS per grid step."""
        return self.imgs * self.rows * self.nW

    @property
    def grid(self) -> Tuple[int, ...]:
        return (self.grid0, self.n_o) if self.depthwise \
            else (self.grid0, self.n_o, self.n_k)

    @property
    def rmw_axis(self) -> Optional[int]:
        """Grid axis allowed to read-modify-write the int32 accumulator
        scratch (the innermost C_in reduction axis); None when the launch
        carries no accumulator (depthwise)."""
        return None if self.depthwise else len(self.grid) - 1

    def vmem_bytes(self) -> int:
        """Per-grid-step VMEM footprint of THIS geometry (same terms as
        :func:`fused_vmem_bytes`, evaluated on the resolved fields)."""
        return _vmem_bytes(self.t, self.M, self.L, self.nW, self.w_padded,
                           self.kb, self.cb, n_k=self.n_k, rows=self.rows,
                           imgs=self.imgs, cache_xq=self.cache_xq,
                           double_buffer=self.double_buffer)

    # ---- strip reads (the DMA source and landing buffer) ----
    @property
    def strip_shape(self) -> Tuple[int, int, int, int]:
        return (self.imgs, self.span, self.w_padded, self.kb)

    @property
    def strip_slots(self) -> int:
        """Landing-buffer slots: two when double-buffered, else one."""
        return self.db_slots if self.double_buffer else 1

    def strip_offset(self, i: int, k: int = 0
                     ) -> Tuple[int, int, int, int]:
        """Element offsets of grid step (i, ·, k)'s input strip group —
        the source window of the kernel's strip DMA."""
        return ((i // self.g_h) * self.imgs,
                (i % self.g_h) * self.rows * self.M, 0, k * self.kb)

    @property
    def x_extents(self) -> Tuple[int, int, int, int]:
        """HBM extents of the padded input the strip reads index into."""
        return (self.B, self.x_rows, self.w_padded, self.Cp)

    def out_index(self, i: int, j: int, k: int = 0
                  ) -> Tuple[int, int, int, int]:
        """Output BlockSpec block index for grid step (i, j, k).  Must be
        independent of ``k``: the int32 accumulator spans all k-blocks and
        only the last one writes the block."""
        del k
        return (i // self.g_h, i % self.g_h, 0, j)

    # ---- workload accounting (the analytic cost model's inputs) ----
    # These accessors are the ONE place the kernel's per-launch work is
    # counted: repro.api.costmodel prices candidates from them and must
    # never re-derive strip/blocking arithmetic (lint rule COST001).
    @property
    def grid_steps(self) -> int:
        """Total grid steps of the launch (the per-step overhead quanta)."""
        n = 1
        for g in self.grid:
            n *= g
        return n

    @property
    def input_consuming_steps(self) -> int:
        """Grid steps that read their input strip group from HBM.

        With the quantized-strip cache only the first C_out block of each
        (strip group, k-block) touches the input; every other step replays
        from VMEM.  The double-buffer DMA path issues exactly one copy per
        consuming step, so the count is the same either way."""
        if self.depthwise:
            return self.grid0 * self.n_o
        if self.cache_xq:
            return self.grid0 * self.n_k
        return self.grid0 * self.n_o * self.n_k

    @property
    def transform_invocations(self) -> int:
        """How many times the B^T X B transform + quantize runs (equals
        :attr:`input_consuming_steps`: strips are transformed exactly when
        they are read, cached strips replay the quantized result)."""
        return self.input_consuming_steps

    def hbm_bytes(self) -> Dict[str, int]:
        """Per-launch HBM traffic of this geometry, bytes by stream.

        input   — f32 strip-group reads, one per consuming step (the
                  overlapping spans are re-read per strip group; the xq
                  cache removes the per-C_out-block re-reads);
        weights — the int8 weight block every step fetches;
        output  — the f32 spatial strip groups the last k-block writes.
        """
        strip = self.imgs * self.span * self.w_padded * self.kb * 4
        inp = self.input_consuming_steps * strip
        if self.depthwise:
            wgt = self.grid0 * self.n_o * self.P * self.cb
        else:
            wgt = self.grid0 * self.n_o * self.n_k * self.P * self.kb \
                * self.cb
        out = self.grid0 * self.n_o \
            * self.imgs * self.rows * self.M * self.nW * self.M * self.cb * 4
        return {"input": inp, "weights": wgt, "output": out,
                "total": inp + wgt + out}

    def compute_ops(self) -> Dict[str, int]:
        """Per-launch arithmetic of this geometry, ops by execution unit.

        mxu_macs    — int8 MXU multiply-accumulates of the t^2 transform-
                      domain matmuls (zero for depthwise);
        vpu_ew      — the depthwise transform-domain elementwise products;
        vpu_transform — f32 VPU work of the separable B^T X B transform +
                      per-frequency quantize, once per consuming step;
        vpu_inverse — dequant + A^T Y A correction inverse per finalize.
        """
        cols = self.cols
        if self.depthwise:
            mxu = 0
            ew = self.grid0 * self.n_o * self.P * cols * self.cb
        else:
            mxu = self.grid0 * self.n_o * self.n_k * self.P * cols \
                * self.kb * self.cb
            ew = 0
        # per strip of a consuming step: the column transform (t outputs
        # of L terms for each of L rows), the row transform (t^2 outputs
        # of L terms) and the per-frequency quantize, on (nW, kb) slabs
        per_step = self.imgs * self.rows * self.kb * self.nW * (
            self.L * self.t * self.L + self.t * self.t * self.L + self.P)
        transform = self.transform_invocations * per_step
        # per finalize: dequant scale (P x cols) + the two inverse einsums
        inverse = self.grid0 * self.n_o * cols * self.cb * (
            self.P + self.M * self.t * self.t + self.M * self.M * self.t)
        return {"mxu_macs": mxu, "vpu_ew": ew, "vpu_transform": transform,
                "vpu_inverse": inverse}

    def scratch_shapes(self) -> Tuple[Tuple[str, Tuple[int, ...], str], ...]:
        """(name, shape, dtype) of every VMEM scratch the launch allocates,
        in ``pallas_call`` order."""
        if self.depthwise:
            out = [("y", (self.t, self.t, self.nW, self.cb), "float32")]
        else:
            out = [("acc", (self.P, self.cols, self.cb), "int32"),
                   ("stage", (self.P, self.cols, self.kb), "float32")]
        if self.cache_xq:
            out.append(("xq_cache", (self.n_k, self.P, self.cols, self.kb),
                        "int8"))
        out.append(("strip_buf", (self.strip_slots,) + self.strip_shape,
                    "float32"))
        return tuple(out)


def fused_geometry(algo: BilinearAlgorithm, B: int, H: int, W: int,
                   C: int, Cout: int, *, padding: str = "SAME",
                   k_block: Optional[int] = K_BLOCK,
                   cout_block: int = COUT_BLOCK,
                   rows_per_step: Optional[int] = None,
                   double_buffer: bool = False,
                   depthwise: bool = False) -> FusedGeometry:
    """Resolve the launch geometry :func:`sfc_fused_conv2d` will use.

    Pure integer arithmetic on static shapes — safe to call from the
    planner, the autotuner's pre-flight checker, and the serving batcher
    without touching jax.  ``rows_per_step=None`` resolves through
    :func:`auto_rows_per_step` exactly as the kernel wrapper does.
    """
    t, M, R, L = algo.t, algo.M, algo.R, algo.L
    lo_h, hi_h, out_h = c2d.pad_amounts(H, M, R, padding)
    lo_w, hi_w, out_w = c2d.pad_amounts(W, M, R, padding)
    xp_h = H + lo_h + hi_h
    nH = (xp_h - (R - 1)) // M
    nW = (W + lo_w + hi_w - (R - 1)) // M
    # the strip DMA slices the padded input: its column extent is padded
    # to the f32 sublane tiling and every channel block to the lane width
    Wp = _round_up(W + lo_w + hi_w, SUBLANES)
    if depthwise:
        cb = min(_round_up(cout_block, LANES), _round_up(C, LANES))
        Cp = _round_up(C, cb)
        kb, n_k = cb, 1
        Op, n_o = Cp, Cp // cb
        cache_xq = double_buffer = False
        if rows_per_step is None:
            rows_per_step = auto_rows_per_step(algo, B, nH, nW, Wp, cb, cb,
                                               n_k=1, n_o=n_o)
    else:
        kb = _round_up(C, LANES) if k_block is None \
            else min(_round_up(k_block, LANES), _round_up(C, LANES))
        Cp = _round_up(C, kb)
        # an output channel block is the whole (8-padded) C_out or a
        # multiple of the lane width
        cb = _round_up(Cout, SUBLANES) if Cout <= cout_block \
            else _round_up(cout_block, LANES)
        Op = _round_up(Cout, cb)
        n_k = Cp // kb
        n_o = Op // cb
        if rows_per_step is None:
            rows_per_step = auto_rows_per_step(
                algo, B, nH, nW, Wp, kb, cb, n_k=n_k, n_o=n_o,
                double_buffer=double_buffer)
    imgs, rows = grouping(B, nH, rows_per_step)
    g_h = -(-nH // rows)
    nH_p = g_h * rows
    g_b = B // imgs                        # imgs divides B by construction
    span = (rows - 1) * M + L
    cache_xq = False if depthwise \
        else cache_fits(n_o, n_k, t * t, imgs * rows * nW, kb)
    return FusedGeometry(
        t=t, M=M, L=L, B=B, C=C, Cout=Cout, nH=nH, nW=nW,
        out_h=out_h, out_w=out_w,
        x_rows=max(xp_h, (nH_p - 1) * M + L), w_padded=Wp,
        depthwise=depthwise, kb=kb, Cp=Cp, n_k=n_k, cb=cb, Op=Op, n_o=n_o,
        rows_per_step=rows_per_step, imgs=imgs, rows=rows, g_h=g_h,
        g_b=g_b, nH_p=nH_p, span=span, grid0=g_b * g_h,
        cache_xq=cache_xq, double_buffer=double_buffer)


def _strip_dma(x_hbm, buf_ref, sem_ref, geom: FusedGeometry, si, sk, slot):
    """DMA descriptor for strip group ``si`` / k-block ``sk`` into a slot."""
    b0, row0, _, c0 = geom.strip_offset(si, sk)
    return pltpu.make_async_copy(
        x_hbm.at[pl.ds(b0, geom.imgs), pl.ds(row0, geom.span), :,
                 pl.ds(c0, geom.kb)],
        buf_ref.at[slot], sem_ref.at[slot])


def _for_each_strip(geom: FusedGeometry, body) -> None:
    """``body(s, im, r)`` for every strip ``s`` of one group (image ``im``
    of the group, tile-row ``r`` of the image)."""
    def step(s, carry):
        body(s, s // geom.rows, s % geom.rows)
        return carry
    jax.lax.fori_loop(0, geom.imgs * geom.rows, step, 0)


def _strip_loader(buf_ref, slot, geom: FusedGeometry, im, r):
    """``load_col(j)`` of :func:`~repro.core.conv2d.separable_2d`: tile
    column element ``j`` of every tile of one strip, an ``(L, nW, kb)``
    stack of stride-M slabs."""
    M = geom.M

    def load_col(j):
        return buf_ref[slot, im, pl.ds(r * M, geom.L),
                       pl.ds(j, geom.nW, stride=M), :]
    return load_col


def _strip_store(o_ref, geom: FusedGeometry, im, r):
    """``emit_col(n, zs)`` writing output tile column ``n`` of every tile
    of one strip into the spatial output block at stride M."""
    M = geom.M

    def emit_col(n, zs):
        o_ref[im, pl.ds(r * M, M), pl.ds(n, geom.nW, stride=M), :] = \
            jnp.stack(zs)
    return emit_col


def _fused_kernel(inv_ref, scale_ref, x_hbm, w_ref, o_ref, acc_ref,
                  stage_ref, *scratch, geom: FusedGeometry, bt, at,
                  qmax: int):
    """One (strip group, C_out block, C_in block) step of the pipeline.

    ``scratch`` holds the quantized-strip cache (only with ``cache_xq``),
    then the strip landing buffer and its DMA semaphores.
    """
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)
    t, P, nW, n_k = geom.t, geom.P, geom.nW, geom.n_k
    scratch = list(scratch)
    xq_ref = scratch.pop(0) if geom.cache_xq else None
    buf_ref, sem_ref = scratch

    @pl.when(k == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # one strip-sequence entry per CONSUMING step: with the xq cache only
    # j == 0 steps read the input (j > 0 replays from VMEM)
    if geom.cache_xq:
        s_idx, total = i * n_k + k, geom.grid0 * n_k

        def coords(sn):
            return sn // n_k, sn % n_k
    else:
        s_idx, total = (i * geom.n_o + j) * n_k + k, \
            geom.grid0 * geom.n_o * n_k

        def coords(sn):
            return sn // (geom.n_o * n_k), sn % n_k
    slots = geom.strip_slots

    def dma(sn):
        return _strip_dma(x_hbm, buf_ref, sem_ref, geom, *coords(sn),
                          sn % slots)

    def consume():
        if geom.double_buffer:
            # the first step issues its own strip; every consuming step
            # then prefetches the NEXT strip into the other slot before
            # blocking on its own
            @pl.when(s_idx == 0)
            def _first():
                dma(0).start()

            @pl.when(s_idx + 1 < total)
            def _prefetch():
                dma(s_idx + 1).start()
        else:
            dma(s_idx).start()
        dma(s_idx).wait()
        slot = s_idx % slots

        def quantize(s, im, r):
            def emit_col(b, ys):
                # frequencies p = a*t + b of one column: stride t in stage
                stage_ref[pl.ds(b, t, stride=t), pl.ds(s * nW, nW), :] = \
                    jnp.stack([c2d.quantize_slab(y, inv_ref[a * t + b], qmax)
                               for a, y in enumerate(ys)])
            c2d.separable_2d(bt, _strip_loader(buf_ref, slot, geom, im, r),
                             emit_col)
        _for_each_strip(geom, quantize)
        if geom.cache_xq:
            def fill(p, carry):
                xq_ref[k, p] = stage_ref[p].astype(jnp.int8)
                return carry
            jax.lax.fori_loop(0, P, fill, 0)

    if geom.cache_xq:
        pl.when(j == 0)(consume)
    else:
        consume()

    # the t^2 int8 MXU matmuls, one 2-D dot per frequency
    def matmul(p, carry):
        xq = xq_ref[k, p] if geom.cache_xq \
            else stage_ref[p].astype(jnp.int8)
        acc_ref[p] += jnp.dot(xq, w_ref[p], preferred_element_type=jnp.int32)
        return carry
    jax.lax.fori_loop(0, P, matmul, 0)

    @pl.when(k == n_k - 1)
    def _finalize():
        # dequantize in place (f32 bits in the int32 scratch) before the
        # inverse reads it, as the staged pipeline reads a stored tensor
        def dequant(p, carry):
            y = acc_ref[p].astype(jnp.float32) * scale_ref[pl.ds(p, 1), :]
            acc_ref[p] = jax.lax.bitcast_convert_type(y, jnp.int32)
            return carry
        jax.lax.fori_loop(0, P, dequant, 0)

        def inverse(s, im, r):
            def load_col(b):
                return jax.lax.bitcast_convert_type(
                    acc_ref[pl.ds(b, t, stride=t), pl.ds(s * nW, nW), :],
                    jnp.float32)
            c2d.separable_2d(at, load_col, _strip_store(o_ref, geom, im, r))
        _for_each_strip(geom, inverse)


def _fused_dw_kernel(inv_ref, scale_ref, x_hbm, w_ref, o_ref, y_ref,
                     buf_ref, sem_ref, *, geom: FusedGeometry, bt, at,
                     qmax: int):
    """One (strip group, channel block) step of the depthwise pipeline.

    Depthwise has no channel contraction, so the grid loses the C_in
    k-dimension and the C_out blocks *are* the input channel blocks: the
    t^2 MXU matmuls collapse to a VPU elementwise int32 product against
    the (P, cb) weight block.  Each strip runs transform, product and
    inverse back to back through the (P, nW, cb) ``y_ref`` scratch.
    """
    i = pl.program_id(0)
    j = pl.program_id(1)
    t = geom.t
    cp = _strip_dma(x_hbm, buf_ref, sem_ref, geom, i, j, 0)
    cp.start()
    cp.wait()

    def strip(s, im, r):
        def emit_col(b, ys):
            def product(a, y):
                p = a * t + b
                q = c2d.quantize_slab(y, inv_ref[p], qmax).astype(jnp.int32)
                prod = q * w_ref[pl.ds(p, 1), :].astype(jnp.int32)
                return prod.astype(jnp.float32) * scale_ref[pl.ds(p, 1), :]
            y_ref[b] = jnp.stack([product(a, y) for a, y in enumerate(ys)])
        c2d.separable_2d(bt, _strip_loader(buf_ref, 0, geom, im, r),
                         emit_col)
        c2d.separable_2d(at, y_ref.__getitem__,
                         _strip_store(o_ref, geom, im, r))
    _for_each_strip(geom, strip)


def _compiler_params():
    # the scoped-VMEM grant: the kernel's own buffers are packed against
    # VMEM_LIMIT_BYTES by fused_geometry; the headroom covers Mosaic's
    # internal scratch (dot results, spilled transform slabs)
    return pltpu.CompilerParams(
        vmem_limit_bytes=VMEM_LIMIT_BYTES + VMEM_HEADROOM_BYTES)


@functools.partial(jax.jit, static_argnames=("algo", "padding", "bits",
                                             "interpret", "k_block",
                                             "cout_block", "rows_per_step",
                                             "double_buffer", "depthwise"))
def sfc_fused_conv2d(x: jnp.ndarray, wq: jnp.ndarray,
                     act_scale: jnp.ndarray, w_scale: jnp.ndarray,
                     algo: BilinearAlgorithm, *,
                     padding: str = "SAME", bits: int = 8,
                     interpret: Optional[bool] = None,
                     k_block: Optional[int] = K_BLOCK,
                     cout_block: int = COUT_BLOCK,
                     rows_per_step: Optional[int] = None,
                     double_buffer: bool = False,
                     depthwise: bool = False) -> jnp.ndarray:
    """int8 SFC convolution in one ``pallas_call``.

    x (B, H, W, Cin) f32; wq (t^2, Cin, Cout) int8; act_scale (t, t);
    w_scale (t, t, Cout) -> (B, H', W', Cout) f32.  Numerically identical
    to the staged ``quantized_fastconv2d`` (same transform arithmetic,
    integer grid and scales) at every grouping.  ``bits`` sets the
    activation clipping grid (sub-int8 policies run on the int8 carrier).
    ``k_block=None`` means full K: the whole C_in reduction in a single
    k-block (``n_k = 1``).  ``rows_per_step`` folds that many tile-rows
    (counting across images once one image's rows are exhausted — see
    :func:`grouping`) into a single grid step; ``None`` (the default)
    resolves it from the shape via :func:`auto_rows_per_step`.
    ``double_buffer`` prefetches the next strip group into a second VMEM
    slot while the current one is transformed and matmul'd.
    ``interpret=None`` compiles for a TPU and interprets elsewhere
    (:func:`repro.runtime.resolve_interpret`).

    ``depthwise`` (wq (t^2, 1, C), w_scale (t, t, C)) swaps the t^2 MXU
    matmuls for the transform-domain elementwise product
    (``_fused_dw_kernel``): the grid drops the C_in reduction dim and
    blocks over the shared in==out channel axis instead.  ``k_block``
    and ``double_buffer`` are no-ops there — there is no reduction to
    block, and each channel block's strip is read exactly once.
    """
    interpret = resolve_interpret(interpret)
    B, H, W, C = x.shape
    t, M, R = algo.t, algo.M, algo.R
    P = t * t
    if depthwise:
        assert wq.shape == (P, 1, C), (wq.shape, P, C)
    else:
        assert wq.shape[0] == P and wq.shape[1] == C, (wq.shape, P, C)
    Cout = wq.shape[2]
    lo_h, _, out_h = c2d.pad_amounts(H, M, R, padding)
    lo_w, _, out_w = c2d.pad_amounts(W, M, R, padding)
    # the ONE geometry derivation (grid, channel blocking, grouping, strip
    # spans, scratch set) — shared verbatim with the static resource
    # checker (repro.analysis.kernel_checks) and the serving batcher
    geom = fused_geometry(algo, B, H, W, C, Cout, padding=padding,
                          k_block=k_block, cout_block=cout_block,
                          rows_per_step=rows_per_step,
                          double_buffer=double_buffer, depthwise=depthwise)
    # grouped-grid padding: strips of the last group read rows up to
    # (nH_p - 1) * M + L; the extra zero rows produce output rows that are
    # sliced off below.  Channel dims pad with zeros; zero channels
    # quantize to zero / carry zero scales, so they contribute nothing.
    xp = jnp.pad(x, ((0, 0), (lo_h, geom.x_rows - H - lo_h),
                     (lo_w, geom.w_padded - W - lo_w), (0, geom.Cp - C)))
    inv = c2d.reciprocal_scale(act_scale).reshape(P)
    scale = jnp.pad(c2d.dequant_scale(act_scale, w_scale),
                    ((0, 0), (0, geom.Op - Cout)))
    bt, at = c2d.transform_coefficients(algo)
    qmax = 2 ** (bits - 1) - 1
    kb, cb, M = geom.kb, geom.cb, geom.M
    strip_buf = [pltpu.VMEM((geom.strip_slots,) + geom.strip_shape,
                            jnp.float32),
                 pltpu.SemaphoreType.DMA((geom.strip_slots,))]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out_block = (geom.imgs, geom.rows * M, geom.nW * M, cb)
    out_shape = jax.ShapeDtypeStruct((B, geom.nH_p * M, geom.nW * M, geom.Op),
                                     jnp.float32)
    if depthwise:
        kern = functools.partial(_fused_dw_kernel, geom=geom, bt=bt, at=at,
                                 qmax=qmax)
        out = pl.pallas_call(
            kern,
            grid=geom.grid,
            in_specs=[smem,
                      pl.BlockSpec((P, cb), lambda i, j: (0, j)),
                      hbm,
                      pl.BlockSpec((P, cb), lambda i, j: (0, j))],
            out_specs=pl.BlockSpec(
                out_block, lambda i, j: geom.out_index(i, j)),
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((t, t, geom.nW, cb), jnp.float32)]
            + strip_buf,
            compiler_params=_compiler_params(),
            interpret=interpret,
            name="sfc_fused_depthwise",
        )(inv, scale, xp,
          jnp.pad(wq.reshape(P, C), ((0, 0), (0, geom.Cp - C))))
        return out[:, :out_h, :out_w, :C]

    wqp = jnp.pad(wq, ((0, 0), (0, geom.Cp - C), (0, geom.Op - Cout)))
    scratch_shapes = [pltpu.VMEM((P, geom.cols, cb), jnp.int32),
                      pltpu.VMEM((P, geom.cols, kb), jnp.float32)]
    if geom.cache_xq:
        scratch_shapes.append(
            pltpu.VMEM((geom.n_k, P, geom.cols, kb), jnp.int8))
    kern = functools.partial(_fused_kernel, geom=geom, bt=bt, at=at,
                             qmax=qmax)
    out = pl.pallas_call(
        kern,
        grid=geom.grid,
        in_specs=[smem,
                  pl.BlockSpec((P, cb), lambda i, j, k: (0, j)),
                  hbm,
                  pl.BlockSpec((P, kb, cb), lambda i, j, k: (0, k, j))],
        out_specs=pl.BlockSpec(out_block, geom.out_index),
        out_shape=out_shape,
        scratch_shapes=scratch_shapes + strip_buf,
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="sfc_fused_dense",
    )(inv, scale, xp, wqp)
    return out[:, :out_h, :out_w, :Cout]
