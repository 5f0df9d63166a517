"""The CNNdroid conv ladder as Pallas TPU kernels.

Three kernels, one per paper method (§4.2–§4.4), sharing the grid-over-
frames structure (the paper launches one RenderScript kernel per frame
batch; we launch one grid cell per frame × tile):

* ``basic_parallel``  (§4.2) — NCHW, whole frame per grid cell, reduction
  loops over (c, kh, kw) with the *spatial* map vectorized — channels are
  NOT on the lane axis, mirroring the paper's un-swapped layout.  The MXU
  stays idle; only the VPU spatial lanes are used.
* ``basic_simd``      (§4.3) — NHWC after dimension swapping: channels on
  the 128-lane minor axis; grid cell (frame, oh-tile); per kernel position
  a [rows, C] × [C, OC] dot — the vectorized channel dot product — over
  one output-row band at a time.
* ``advanced_simd``   (§4.4) — NHWC + output-channel blocking: grid cell
  (frame, oh-tile, oc-tile); an im2col patch matrix [rows, KH·KW·C] built
  once per spatial tile in VMEM is reused for the whole 128-wide oc tile
  (the paper's 4/8-outputs-per-thread reuse at MXU width), with bias+ReLU
  fused in the epilogue.

Spatial tiling (the ``oh_block`` knob): both SIMD kernels split the output
height into bands of ``oh_block`` rows.  Each grid cell loads only the
input-row band its output band needs — ``(oh_block-1)*stride + KH`` rows
including the halo, addressed stride-aware with an element-offset
(``pl.Element``) block dim so neighbouring bands may overlap by the
``KH - stride`` halo rows.  ``oh_block=None`` picks the largest band whose
working set (input band + im2col patches + weights + output block) fits
``VMEM_BUDGET_BYTES`` — so frames far larger than VMEM (e.g. 512×512×64)
run on the same ladder instead of trying to stage the whole padded frame.

VMEM budget: block shapes keep the minor dimension lane-aligned when the
channel count allows (ops.py pads channels — the paper's divisible-by-4
observation at lane width 128/8); the heuristic targets half of Mosaic's
default 16 MiB scoped VMEM to leave room for double buffering, and the
cells request ``VMEM_LIMIT_BYTES`` for what the model does not charge.

Fused pooling epilogue (super-layers): both SIMD kernels accept an
optional ``pool=(pkh, pkw, psy, psx, kind, pool_relu)``.  A grid cell then
computes the conv-output band that feeds ``ph_block`` *pooled* rows — the
conv band is ``(ph_block-1)*psy + pkh`` rows, i.e. the oh-band snapped to
the pool stride and widened by the pool-window halo — applies bias+ReLU,
pools it in VMEM (``pool2d.kernels.pool_band``), and writes only the
pooled band.  The intermediate conv activation never touches HBM: one
dispatch, one HBM write, for what the per-layer ladder did in two passes.

Fused LRN epilogue (one stage further): an optional
``lrn=(n, alpha, beta, k)`` extends the fused cell to
conv→bias→ReLU→pool→LRN.  The channel-axis sum-of-squares runs over the
in-VMEM pooled band (fp32, ``lrn_band``) with the same asymmetric window
padding as ``engine._lrn`` — window ``[c - n//2, c + (n-1)//2]``, so even
``n`` stays C-channels-in/C-channels-out — and only the *normalized* band
is written.  AlexNet's two ``conv→relu→pool→norm`` runs become single
dispatches.  LRN needs every output channel of a pooled row in one cell,
so the advanced kernel drops its oc-grid blocking to one full-width tile
when ``lrn`` is set (the working-set model below charges for it) —
unless the *two-pass channel-halo* cell applies: ``resolve_lrn_ocb``
restores oc blocking by widening each weight tile with the LRN window's
``n - 1`` neighbour columns (zero columns past the frame edges), so a
tile computes the conv channels its own LRN windows read and
``lrn_band_halo`` keeps the ``ocb`` core at the store.

Sliding-window pool accumulator (``resolve_pool_carry``): when adjacent
pooled bands overlap (``K = pkh - psy >= 1`` conv rows), the carry cell
convolves only the ``R = ph_block*psy`` fresh rows per band step and
keeps the K boundary rows in VMEM scratch across the sequential band
axis — one extra seed step (its output block is sliced off) trades the
per-band halo re-read and re-convolution for a K-row scratch carry.

``fused_cell_bytes`` is the shared VMEM working-set model for one fused
grid cell (halo-widened input band + patch staging + weights + conv band
+ pooled band); ``auto_ph_block`` walks it to pick the largest pooled
band that fits the budget, and the fusion planner
(``repro.core.fusion``) evaluates the same model at the one-pool-window
floor to decline fusion for shapes whose smallest possible cell would
still bust the budget.

Fused conv→conv chains (VMEM-resident halo): ``conv2d_chain_simd`` runs
a whole run of consecutive convolutions as ONE grid cell per output-row
band — the cell computes a band of conv A, keeps it in VMEM, and
immediately convolves it with conv B's weights, with bias+ReLU between
stages and the pool/LRN epilogue allowed on the tail.  The band math
composes backwards across stages: a band of ``ohb`` final rows needs
``(ohb-1)*sB + kB`` rows of A's output, hence
``((ohb-1)*sB + kB - 1)*sA + kA`` input rows (``chain_band_geometry``).
Intermediate vertical padding cannot be materialized host-side (the pad
rows are *activation* zeros, not conv-of-zero-input), so each
intermediate stage zero-masks the rows of its band that fall outside its
valid output range — those rows ARE the next stage's padding.  Stage N+1
consumes every output channel of stage N, so chain cells run all stages
at full oc width (no oc-grid blocking); ``chain_cell_bytes`` /
``auto_chain_block`` generalize the working-set model to the per-stage
live sets (weights of every stage stay resident; the per-stage
band+patch temporaries are sequential, so their *maximum* is charged).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import ACC_DTYPE, ACC_PRECISION

# Target working set per grid cell — half the ~16 MB/core VMEM, leaving the
# other half for the pipeline's double buffering.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024

# Chain cells keep every stage's full-width weights resident (stage N+1
# consumes every output channel of stage N, so there is no oc tile to
# shrink them).  Weights are grid-invariant whole-array blocks, which
# Mosaic single-buffers (its lowering forces a synchronous pipeline for
# a trivial window), so the chain check runs against near-full default
# scoped VMEM (16 MiB minus pipeline headroom) instead of the
# half-capacity streaming budget; the streamed input/output bands are
# charged on top of the per-stage live set, standing in for their
# double buffers.
CHAIN_VMEM_BUDGET_BYTES = 14 * 1024 * 1024

# Scoped VMEM every conv/pool cell may use (v5e has 128 MiB per core;
# Mosaic's default scope is 16 MiB).  The working-set budgets above stay
# the planners' targets: the fp32-precision MXU contraction (three bf16
# passes per operand) and the pipeline buffers come on top of the
# modelled cell, so the limit leaves that room.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024

# Lane width of a TPU vreg and of the MXU tile.  Mosaic accepts a block
# whose minor dim is a multiple of this or spans the whole array dim, so
# the paper's 4/8-output-channel blocks are raised to it.
LANES = 128


def _out_size(size, k, stride, pad):
    return (size + 2 * pad - k) // stride + 1


def _band_rows(oh_block, kh, sy):
    """Input rows one output band needs: oh_block strided rows + halo."""
    return (oh_block - 1) * sy + kh


def band_intervals(n_tiles, blk, total, row_step, band, base: int = 0):
    """Per-grid-cell ``(start, rows)`` intervals of a banded kernel grid.

    Returns ``(out_iv, in_iv)``: ``out_iv[t]`` is cell ``t``'s output
    band in output-row coordinates with ``rows`` clipped to the ``total``
    valid rows (the surplus rows of a ragged last band are sliced off by
    the caller), and ``in_iv[t]`` the input-row band the cell stages, in
    padded-input coordinates — ``start = base + t*row_step``; a negative
    ``base`` means the caller pre-pads that many extra top zero rows (the
    chain cells' intermediate vertical padding).  ONE copy of the
    tile-planning math: ``_plan_oh_tiles`` / ``_plan_pool_tiles`` /
    ``pool2d_nhwc`` derive their bottom-padding need from ``in_iv[-1]``,
    and the static plan verifier (``repro.analysis.verifier``) proves
    band coverage over the same lists the kernels execute.
    """
    out_iv = [(t * blk, max(0, min(blk, total - t * blk)))
              for t in range(n_tiles)]
    in_iv = [(base + t * row_step, band) for t in range(n_tiles)]
    return out_iv, in_iv


def conv_cell_bytes(ohb, ow, wp, c, kh, kw, sy, oc_block,
                    im2col: bool = True, itemsize: int = 4) -> int:
    """Modelled VMEM working set of ONE un-fused conv grid cell (fp32
    staging): the halo-inclusive input row band, the patch staging (full
    im2col matrix for the advanced kernels, one [rows, C] slice for the
    basic kernel), one weight block, and the output accumulator.  Shared
    by the ``auto_oh_block`` walk and the static plan verifier's budget
    audit."""
    patch_c = kh * kw * c if im2col else c
    band = _band_rows(ohb, kh, sy)
    return (band * wp * c              # input row band (incl. halo)
            + ohb * ow * patch_c       # patch staging
            + kh * kw * c * oc_block   # weight block
            + ohb * ow * oc_block      # output block / accumulator
            ) * itemsize


def conv_macs(oh, ow, cin, kh, kw, oc) -> int:
    """Multiply-accumulates of ONE conv frame (``oh×ow×oc`` outputs, each
    reducing over ``cin×kh×kw``).  The arithmetic half of the analytic
    cost model (``repro.core.cost``) — every ladder method computes
    exactly these MACs; they differ only in achieved throughput."""
    return oh * ow * oc * cin * kh * kw


def band_overfetch_factor(n_tiles, band, padded_h) -> float:
    """HBM input-traffic multiplier of a banded dispatch: neighbouring
    bands re-fetch their halo rows, so one frame streams ``n_tiles *
    band`` input rows instead of the ``padded_h`` it holds.  ≥ 1.0 by
    construction (a single whole-frame band streams each row once).  The
    memory half of the analytic cost model — shrinking ``oh_block`` buys
    VMEM at the price of this factor."""
    if padded_h <= 0:
        return 1.0
    return max(1.0, (n_tiles * band) / padded_h)


def auto_oh_block(oh, ow, wp, c, kh, kw, sy, oc_block,
                  budget: int = VMEM_BUDGET_BYTES, itemsize: int = 4,
                  im2col: bool = True) -> int:
    """Largest output-row band whose per-cell working set
    (``conv_cell_bytes``) fits ``budget``.  Candidates walk down from the
    whole frame through powers of two; the floor is a single output row.
    """
    candidates = [oh] + [b for b in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1)
                         if b < oh]
    for ohb in candidates:
        if conv_cell_bytes(ohb, ow, wp, c, kh, kw, sy, oc_block,
                           im2col=im2col, itemsize=itemsize) <= budget:
            return ohb
    return 1


def resolve_basic_ocb(oc, ow, wp, c, kh, kw, sy) -> int:
    """The oc tile of an un-fused basic_simd cell: the full width (the
    paper's §4.3 cell) when its one-row floor cell fits
    ``VMEM_BUDGET_BYTES``, else halved while the half stays a whole
    number of lane tiles, until the floor cell fits (ResNet-50's
    1024→2048 projection: 8 MiB of weights at full width)."""
    ocb = oc
    while (conv_cell_bytes(1, ow, wp, c, kh, kw, sy, ocb, im2col=False)
           > VMEM_BUDGET_BYTES and ocb % (2 * LANES) == 0):
        ocb //= 2
    return ocb


def resolve_oh_block(oh, ow, wp, c, kh, kw, sy, oc_block, oh_block,
                     im2col: bool = True) -> int:
    """The output-row band a SIMD kernel will actually run with: the auto
    heuristic when ``oh_block`` is None, else the clamped explicit value.
    Public so benches/tools can report the executed geometry."""
    if oh_block is None:
        return auto_oh_block(oh, ow, wp, c, kh, kw, sy, oc_block,
                             im2col=im2col)
    return max(1, min(oh_block, oh))


def fused_cell_bytes(phb, ow, wp, c, kh, kw, sy, oc_block, pool,
                     im2col: bool = True, itemsize: int = 4,
                     oc_halo: int = 0) -> int:
    """Modelled VMEM working set of ONE fused conv→pool(→LRN) grid cell.

    ``phb`` pooled rows ⇒ ``(phb-1)*psy + pkh`` conv rows ⇒
    ``(cband-1)*sy + kh`` input rows (halo included).  Charged terms, all
    fp32 staging: the halo-widened input band, the patch staging (full
    im2col matrix for the advanced kernel, one [rows, C] slice for the
    basic kernel), one weight block, the conv-band accumulator, and the
    pooled output band.  ``oc_halo`` widens every oc-tile term by the
    LRN window's ``n - 1`` neighbour columns for the two-pass
    channel-halo cell (0 for the classic cells).  The same model backs
    both the kernel-side ``auto_ph_block`` walk and the planner's
    decline-to-fuse check, so the planner never forms a group the kernel
    cannot stage.
    """
    pkh, pkw, psy, psx = pool
    pw = (ow - pkw) // psx + 1
    cband = (phb - 1) * psy + pkh          # conv rows per cell
    band = (cband - 1) * sy + kh           # input rows per cell (halo incl.)
    patch_c = kh * kw * c if im2col else c
    ocw = oc_block + oc_halo               # halo-widened oc tile
    return (band * wp * c                  # halo-widened input band
            + cband * ow * patch_c        # patch staging
            + kh * kw * c * ocw           # weight block
            + cband * ow * ocw            # conv band accumulator
            + phb * pw * ocw              # pooled (normalized) output band
            ) * itemsize


def auto_ph_block(ph, ow, wp, c, kh, kw, sy, oc_block, pool,
                  budget: int = VMEM_BUDGET_BYTES,
                  im2col: bool = True, oc_halo: int = 0) -> int:
    """Largest pooled-row band whose fused-cell working set fits
    ``budget``; floors at one pooled row (one pool window of conv rows —
    which may exceed the soft budget: the planner's job is to keep such
    shapes un-fused in the first place)."""
    candidates = [ph] + [b for b in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1)
                         if b < ph]
    for phb in candidates:
        if fused_cell_bytes(phb, ow, wp, c, kh, kw, sy, oc_block, pool,
                            im2col=im2col, oc_halo=oc_halo) <= budget:
            return phb
    return 1


def _equalize_bands(blk, target):
    """Clamp a band size to ``target`` rows, then re-snap it to
    ``ceil(target / n_tiles)`` so the ragged last band shrinks to its fair
    share instead of fetching a full band of mostly-pad input rows.
    Returns ``(blk, n_tiles)``."""
    blk = max(1, min(blk, target))
    n_tiles = -(-target // blk)
    blk = -(-target // n_tiles)
    return blk, -(-target // blk)


def resolve_ph_block(ph, oh, ow, wp, c, kh, kw, sy, oc_block, pool, oh_block,
                     im2col: bool = True, oc_halo: int = 0) -> tuple:
    """The equalized pooled-row band a fused conv+pool cell will execute
    with, as ``(ph_block, n_tiles)``: the ``auto_ph_block`` walk when
    ``oh_block`` is None, else the explicit conv band snapped down to
    whole pool windows.  Public so the engine's geometry report shares
    the exact resolution the kernels run."""
    pkh, _, psy, _ = pool
    if oh_block is None:
        phb = auto_ph_block(ph, ow, wp, c, kh, kw, sy, oc_block, pool,
                            im2col=im2col, oc_halo=oc_halo)
    else:
        # snap the explicit conv band to the pool stride: the largest
        # pooled-row count whose conv band fits inside the oh-band
        ohb = max(1, min(oh_block, oh))
        phb = max(1, (ohb - pkh) // psy + 1) if ohb >= pkh else 1
    return _equalize_bands(phb, ph)


def lrn_band(x, n, alpha, beta, k):
    """AlexNet-style LRN over the channel (minor) axis of an fp32 band.

    Window ``[c - n//2, c + (n-1)//2]`` with zero padding — the same
    asymmetric split as ``engine._lrn``, so even ``n`` keeps C channels.
    Unrolled shifted-slice accumulation (``n`` is small and static):
    pure VPU work on data already in VMEM.
    """
    c = x.shape[-1]
    sq = x * x
    lo, hi = n // 2, n - 1 - n // 2
    sq_p = jnp.pad(sq, [(0, 0)] * (x.ndim - 1) + [(lo, hi)])
    acc = jnp.zeros_like(x)
    for i in range(n):
        acc = acc + jax.lax.slice_in_dim(sq_p, i, i + c, axis=x.ndim - 1)
    return x / (k + alpha * acc) ** beta


def lrn_band_halo(x, n, alpha, beta, k):
    """LRN over a channel-halo-widened band (the two-pass oc-blocked
    cell): ``x``'s minor axis holds ``ocb + n - 1`` conv channels — the
    tile's own ``ocb`` plus the ``n//2`` / ``n-1-n//2`` neighbour columns
    the window reaches into.  Zero weight columns at the frame edges make
    the halo channels exact zeros there, reproducing ``lrn_band``'s
    zero-padded window without any in-kernel padding.  Returns the
    normalized ``ocb``-wide core.
    """
    lo = n // 2
    ocb = x.shape[-1] - (n - 1)
    sq = x * x
    acc = jax.lax.slice_in_dim(sq, 0, ocb, axis=x.ndim - 1)
    for i in range(1, n):
        acc = acc + jax.lax.slice_in_dim(sq, i, i + ocb, axis=x.ndim - 1)
    core = jax.lax.slice_in_dim(x, lo, lo + ocb, axis=x.ndim - 1)
    return core / (k + alpha * acc) ** beta


def resolve_lrn_ocb(oc, oc_block, lrn, lrn_oc_block, ow, wp, c, kh, kw, sy,
                    pool, im2col: bool = True) -> tuple:
    """Resolve ``(ocb, oc_halo)`` for a fused conv→pool→LRN cell.

    The classic cell runs LRN at one full-width oc tile (the window reads
    every channel of a pooled row).  The two-pass channel-halo cell
    restores oc blocking by widening each weight tile with the window's
    ``n - 1`` neighbour columns so a tile can normalize its own ``ocb``
    channels locally.  Auto (``lrn_oc_block=None``) keeps the historical
    full-width tile whenever even the one-pooled-row floor cell fits the
    budget — default plans stay byte-identical — and blocks otherwise;
    ``True`` forces blocking, ``False`` forces full width.  Shared by the
    kernel dispatch, the fusion planner, and the verifier; the sanitizer
    re-derives it independently (Phase A).
    """
    if lrn is None or not im2col:
        return (min(oc_block, oc) if im2col else oc), 0
    blocked = min(oc_block, oc)
    if blocked >= oc or lrn_oc_block is False:
        return oc, 0
    if lrn_oc_block is None and fused_cell_bytes(
            1, ow, wp, c, kh, kw, sy, oc, pool) <= VMEM_BUDGET_BYTES:
        return oc, 0
    return blocked, lrn[0] - 1


def resolve_pool_carry(pool_carry, im2col, lrn, pool, phb, n_tiles) -> bool:
    """Whether a fused conv→pool dispatch runs the sliding-window carry
    cell: adjacent oh-bands share ``K = pkh - psy`` boundary conv rows,
    and the carry cell keeps them in VMEM scratch between band steps
    instead of re-convolving them.  Requires the im2col kernel, no LRN
    epilogue, overlapping pool windows (``K >= 1``) that fit inside one
    band's fresh rows (``K <= phb*psy``), and more than one band.
    ``pool_carry``: None = auto (on when feasible), False = off, True =
    requested (still falls back to off when infeasible).  Shared by the
    kernel dispatch, the fusion planner, and the verifier; the sanitizer
    re-derives it independently (Phase A)."""
    if pool is None or lrn is not None or not im2col or pool_carry is False:
        return False
    pkh, _, psy, _ = pool
    k_rows = pkh - psy
    return 1 <= k_rows <= phb * psy and n_tiles > 1


# ---------------------------------------------------------------------------
# §4.2 basic parallel — NCHW, no channel vectorization
# ---------------------------------------------------------------------------


def _basic_parallel_kernel(x_ref, w_ref, b_ref, o_ref, *, kh, kw, sy, sx,
                           relu):
    # x_ref: [C, H, W]; w_ref: [OC, C, KH, KW]; o_ref: [OC, OH, OW]
    oc, ohh, oww = o_ref.shape
    c = x_ref.shape[0]
    acc = jnp.zeros((oc, ohh, oww), jnp.float32)
    for ci in range(c):  # channels OUTER (un-swapped layout: no lane reuse)
        plane = x_ref[ci]  # [H, W]
        for i in range(kh):
            for j in range(kw):
                patch = jax.lax.slice(
                    plane, (i, j),
                    (i + (ohh - 1) * sy + 1, j + (oww - 1) * sx + 1),
                    (sy, sx),
                )  # [OH, OW] — spatial lanes only
                acc = acc + (patch.astype(ACC_DTYPE)[None] *
                             w_ref[:, ci, i, j].astype(ACC_DTYPE)
                             [:, None, None])
    acc = acc + b_ref[...].astype(ACC_DTYPE)[:, None, None]
    if relu:
        acc = jnp.maximum(acc, 0.0)
    o_ref[...] = acc.astype(o_ref.dtype)


def conv2d_basic_parallel(x, w, b, stride=(1, 1), padding=(0, 0), relu=False,
                          interpret: bool = False):
    n, c, h, wd = x.shape
    oc, _, kh, kw = w.shape
    sy, sx = stride
    py, px = padding
    xp = jnp.pad(x, ((0, 0), (0, 0), (py, py), (px, px)))
    oh, ow = _out_size(h, kh, sy, py), _out_size(wd, kw, sx, px)
    hp, wp = xp.shape[2], xp.shape[3]
    kern = functools.partial(_basic_parallel_kernel, kh=kh, kw=kw, sy=sy,
                             sx=sx, relu=relu)
    return pl.pallas_call(
        kern,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((None, c, hp, wp), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((oc, c, kh, kw), lambda i: (0, 0, 0, 0)),
            pl.BlockSpec((oc,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((None, oc, oh, ow), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, oc, oh, ow), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=interpret,
    )(xp, w, b)


# ---------------------------------------------------------------------------
# shared oh-band plumbing for the SIMD kernels
# ---------------------------------------------------------------------------


def _plan_oh_tiles(shape, oh, kh, kw, sy, oh_block, ow, oc_block,
                   im2col=True):
    """Resolve the band size from the padded input's ``shape``.

    Returns ``(bottom, ohb, n_tiles, band)`` where ``bottom`` is the
    zero rows to add under the input so the last band — starting at
    ``(n_tiles-1)*ohb*sy`` and spanning ``band`` rows — stays in bounds;
    the surplus output rows are sliced off by the caller.
    """
    n, hp, wp, c = shape
    ohb = resolve_oh_block(oh, ow, wp, c, kh, kw, sy, oc_block, oh_block,
                           im2col=im2col)
    n_tiles = -(-oh // ohb)
    band = _band_rows(ohb, kh, sy)
    _, in_iv = band_intervals(n_tiles, ohb, oh, ohb * sy, band)
    hp_need = in_iv[-1][0] + band
    return max(0, hp_need - hp), ohb, n_tiles, band


# ---------------------------------------------------------------------------
# shared pooled-band plumbing for the fused conv→ReLU→pool kernels
# ---------------------------------------------------------------------------


def _plan_pool_tiles(shape, oh, ow, kh, kw, sy, oh_block, oc_block, pool,
                     im2col=True, oc_halo=0):
    """Band geometry for a fused conv+pool cell.

    Resolves the pooled-row band directly from the fused-cell working-set
    model (``auto_ph_block``; an explicit ``oh_block`` is snapped down to
    whole pool windows: ``ph_block`` pooled rows ⇒ ``(ph_block-1)*psy +
    pkh`` conv rows per cell), then *equalizes* the bands — ``ph_block``
    is re-snapped to ``ceil(ph / n_tiles)`` so the last band covers its
    fair share instead of being a ragged remainder that still fetches a
    full band of (mostly pad) input rows.  ``shape`` is the padded
    input's.  Returns ``(bottom, ph_block, n_tiles, band, cband, ph, pw,
    row_step)`` where ``bottom`` is the zero rows to add under the input
    so every band stays in bounds, ``band`` input rows per cell,
    ``cband`` conv rows per cell, ``(ph, pw)`` the pooled output size,
    and ``row_step`` the input-row stride between consecutive bands.

    Floor: a fused cell can never hold fewer than one pool window of conv
    rows, so a one-pooled-row cell may exceed the *soft*
    VMEM_BUDGET_BYTES target (half of VMEM) by up to the pool-window
    factor.  All paper shapes stay far under the hard limit; shapes whose
    floor cell busts the budget are kept un-fused by the planner's
    working-set check (``repro.core.fusion``).
    """
    pkh, pkw, psy, psx = pool
    n, hp, wp, c = shape
    ph, pw = (oh - pkh) // psy + 1, (ow - pkw) // psx + 1
    if ph < 1 or pw < 1:
        raise ValueError(
            f"pool window ({pkh},{pkw}) larger than conv output ({oh},{ow})")
    phb, n_tiles = resolve_ph_block(ph, oh, ow, wp, c, kh, kw, sy, oc_block,
                                    pool, oh_block, im2col=im2col,
                                    oc_halo=oc_halo)
    cband = (phb - 1) * psy + pkh           # conv rows per cell
    band = (cband - 1) * sy + kh            # input rows per cell (halo incl.)
    row_step = phb * psy * sy
    _, in_iv = band_intervals(n_tiles, phb, ph, row_step, band)
    hp_need = in_iv[-1][0] + band
    return max(0, hp_need - hp), phb, n_tiles, band, cband, ph, pw, row_step


def pool_stage_shapes(rows, ow, width):
    """VMEM scratch that stages a ``[rows, ow, width]`` conv band for the
    pool epilogue, one fp32 ref per lane chunk of at most ``LANES``
    channels: Mosaic lowers a strided load only from a ref whose minor
    dim fits one lane tile."""
    return [pltpu.VMEM((rows, ow, min(LANES, width - lo)), jnp.float32)
            for lo in range(0, width, LANES)]


def _pool_staged(acc, p_refs, pool, conv_relu, phh, pww):
    """Bias-added fp32 conv rows ``acc`` [conv_rows * conv_ow, W] →
    (ReLU) → staged lane chunk by lane chunk in ``p_refs``
    (``pool_stage_shapes``) → (ReLU) → fp32 pooled band [phh, pww, W]."""
    from repro.kernels.pool2d.kernels import pool_band  # deferred: no cycle

    pkh, pkw, psy, psx, kind, pool_relu, conv_ow = pool
    if conv_relu:
        acc = jnp.maximum(acc, 0.0)
    cband = (phh - 1) * psy + pkh
    band = acc.reshape(cband, conv_ow, -1)
    parts = []
    for ci, p_ref in enumerate(p_refs):
        lo = ci * LANES
        p_ref[...] = jax.lax.slice_in_dim(band, lo, lo + p_ref.shape[2],
                                          axis=2)
        parts.append(pool_band(p_ref, phh, pww, pkh, pkw, psy, psx, kind))
    out = jnp.concatenate(parts, axis=-1)
    if pool_relu:
        out = jnp.maximum(out, 0.0)
    return out


def _pool_epilogue(acc, o_ref, p_refs, pool, conv_relu, lrn=None):
    """Shared epilogue: bias-added fp32 conv rows → (ReLU) → pooled band
    → (LRN).

    ``acc``: [conv_rows * conv_ow, OC] fp32; writes o_ref [PH_BLK, PW, OC].
    The conv band is staged in the VMEM scratch ``p_refs`` so
    ``pool_band`` can read its windows with strided ref loads.
    ``lrn=(n, alpha, beta, k)`` normalizes the pooled band across
    channels before the (single) HBM write — the conv AND pooled
    activations both stay VMEM-resident.
    """
    phh, pww, _ = o_ref.shape
    out = _pool_staged(acc, p_refs, pool, conv_relu, phh, pww)
    if lrn is not None:
        n, alpha, beta, k = lrn
        out = lrn_band(out, n, alpha, beta, k)
    o_ref[...] = out.astype(o_ref.dtype)


def _pool_epilogue_halo(acc, o_ref, p_refs, pool, conv_relu, lrn):
    """Channel-halo variant of ``_pool_epilogue`` for the oc-blocked LRN
    cell: ``acc`` holds ``ocb + n - 1`` conv channels (the tile's own
    plus the window's neighbour columns), the pooled band stays widened,
    and ``lrn_band_halo`` narrows it to the ``ocb`` core at the single
    HBM store.
    """
    phh, pww, _ = o_ref.shape
    n, alpha, beta, k = lrn
    wide = _pool_staged(acc, p_refs, pool, conv_relu, phh, pww)
    o_ref[...] = lrn_band_halo(wide, n, alpha, beta, k).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# §4.3 basic SIMD — NHWC, vectorized channel dot per kernel position
# ---------------------------------------------------------------------------


def _lane_tiled(xp, w, c, sy, sx):
    """``(xp, w, c, cc)``: a padded NHWC input and its weights as a SIMD
    cell reads them.  Mosaic loads with a stride only from a ref whose
    minor dim is at most one lane tile, so a strided conv over more than
    ``LANES`` channels views each input column as ``cc`` lane tiles side
    by side, ``[N, HP, WP*cc, LANES]`` (tile ``k`` of column ``q`` at
    ``q*cc + k``); channels are zero-padded to whole tiles, and ``w``
    (the weights, input channels on axis -2) with them."""
    if max(sy, sx) == 1 or c <= LANES:
        return xp, w, c, 1
    cc = -(-c // LANES)
    if c % LANES:  # w: [..., C, OC], or flattened [KH*KW*C, OC]
        xp = jnp.pad(xp, ((0, 0), (0, 0), (0, 0), (0, cc * LANES - c)))
        w = jnp.pad(w.reshape(-1, c, w.shape[-1]),
                    ((0, 0), (0, cc * LANES - c), (0, 0))
                    ).reshape(*w.shape[:-2], -1, w.shape[-1])
    n, hp, wp, _ = xp.shape
    return xp.reshape(n, hp, wp * cc, LANES), w, cc * LANES, cc


def _tap(x_ref, i, j, sy, sx, rows, ow, cc):
    """Kernel tap ``(i, j)`` of an input-row band ref as a ``[rows*ow,
    C]`` matrix, read from the lane-tiled view tile by tile when
    ``cc > 1`` (see ``_lane_tiled``)."""
    return jnp.concatenate(
        [x_ref[pl.ds(i, rows, stride=sy), pl.ds(j * cc + k, ow,
                                                 stride=sx * cc), :]
         .reshape(rows * ow, -1) for k in range(cc)], axis=-1)


def _split_refs(refs, residual):
    """``(r_ref, o_ref, p_refs)`` of a SIMD cell's refs after its bias:
    the shortcut's block where the cell runs a residual join (else
    None), the output, and the pool epilogue's staging scratch."""
    if residual:
        return refs[0], refs[1], refs[2:]
    return None, refs[0], refs[1:]


def _basic_simd_kernel(x_ref, w_ref, b_ref, *refs, kh, kw, sy, sx, relu,
                       pool=None, lrn=None, residual=False, cc=1):
    # x_ref: [BAND, WP, C] (input-row band); w_ref: [KH, KW, C, OC];
    # refs: the shortcut's [OH_BLK, OW, OC] block (residual), then o_ref:
    # [OH_BLK, OW, OC] (unfused) or [PH_BLK, PW, OC] (fused pool, the
    # rest staging the conv band: pool_stage_shapes)
    r_ref, o_ref, p_refs = _split_refs(refs, residual)
    if pool is None:
        ohh, oww, oc = o_ref.shape
    else:
        pkh, _, psy, _, _, _, conv_ow = pool
        phh, _, oc = o_ref.shape
        ohh, oww = (phh - 1) * psy + pkh, conv_ow  # conv rows this cell owns
    acc = jnp.zeros((ohh * oww, oc), jnp.float32)
    for i in range(kh):
        for j in range(kw):
            if cc == 1:  # [rows, C]: C on lanes
                patch = x_ref[pl.ds(i, ohh, stride=sy),
                              pl.ds(j, oww, stride=sx), :].reshape(
                                  ohh * oww, -1)
            else:
                patch = _tap(x_ref, i, j, sy, sx, ohh, oww, cc)
            acc = acc + jnp.dot(
                patch.astype(ACC_DTYPE),
                w_ref[i, j].astype(ACC_DTYPE),
                preferred_element_type=jnp.float32,
                precision=ACC_PRECISION,
            )  # vectorized dot over channels (the paper's 4-wide, here 128)
    acc = acc + b_ref[...].astype(ACC_DTYPE)
    if pool is not None:  # fused super-layer: pool in VMEM, write pooled band
        _pool_epilogue(acc, o_ref, p_refs, pool, relu, lrn)
        return
    if r_ref is not None:  # residual join: the shortcut before the ReLU
        acc = acc + r_ref[...].reshape(ohh * oww, oc).astype(ACC_DTYPE)
    if relu:
        acc = jnp.maximum(acc, 0.0)
    o_ref[...] = acc.reshape(ohh, oww, oc).astype(o_ref.dtype)


def conv2d_basic_simd(x_nhwc, w_hwio, b, stride=(1, 1), padding=(0, 0),
                      relu=False, oh_block=None, interpret: bool = False,
                      pool_kernel=None, pool_stride=None,
                      pool_kind: str = "max", pool_relu: bool = False,
                      lrn=None, residual=None):
    n, h, wd, c = x_nhwc.shape
    kh, kw, _, oc = w_hwio.shape
    sy, sx = stride
    py, px = padding
    hp, wp = h + 2 * py, wd + 2 * px
    oh, ow = _out_size(h, kh, sy, py), _out_size(wd, kw, sx, px)
    if lrn is not None and pool_kernel is None:
        raise ValueError("fused LRN epilogue requires a fused pool epilogue")
    if residual is not None and pool_kernel is not None:
        raise ValueError("a residual epilogue runs without a pool")
    if pool_kernel is not None:
        # fused super-layer: each cell writes a pooled band, the conv
        # activation stays in VMEM
        pkh, pkw = pool_kernel
        psy, psx = pool_stride if pool_stride is not None else pool_kernel
        bottom, phb, n_tiles, band, cband, ph, pw, row_step = \
            _plan_pool_tiles((n, hp, wp, c), oh, ow, kh, kw, sy, oh_block,
                             oc, (pkh, pkw, psy, psx), im2col=False)
        pool = (pkh, pkw, psy, psx, pool_kind, pool_relu, ow)
        out_rows, out_cols = phb, pw
        scratch = pool_stage_shapes(cband, ow, oc)
        ocb = oc
    else:
        # an oc grid only where the full-width weights bust the budget
        ocb = resolve_basic_ocb(oc, ow, wp, c, kh, kw, sy)
        bottom, ohb, n_tiles, band = _plan_oh_tiles(
            (n, hp, wp, c), oh, kh, kw, sy, oh_block, ow, ocb, im2col=False)
        pool = None
        row_step = ohb * sy
        out_rows, out_cols = ohb, ow
        scratch = []
    xp = jnp.pad(x_nhwc, ((0, 0), (py, py + bottom), (px, px), (0, 0)))
    xp, w_hwio, c, cc = _lane_tiled(xp, w_hwio, c, sy, sx)
    kern = functools.partial(_basic_simd_kernel, kh=kh, kw=kw, sy=sy, sx=sx,
                             relu=relu, pool=pool, lrn=lrn,
                             residual=residual is not None, cc=cc)
    in_specs = [
        # element-offset indexing: bands overlap by the KH-sy halo rows
        pl.BlockSpec((None, pl.Element(band), pl.Element(wp * cc),
                      pl.Element(c // cc)),
                     lambda i, t, u: (i, t * row_step, 0, 0)),
        pl.BlockSpec((kh, kw, c, ocb), lambda i, t, u: (0, 0, 0, u)),
        pl.BlockSpec((1, ocb), lambda i, t, u: (0, u)),
    ]
    operands = [xp, w_hwio, b.reshape(1, oc)]
    if residual is not None:
        # the shortcut, read block for block as the output is written
        in_specs.append(pl.BlockSpec((None, out_rows, out_cols, ocb),
                                     lambda i, t, u: (i, t, 0, u)))
        operands.append(jnp.pad(
            residual, ((0, 0), (0, n_tiles * out_rows - oh), (0, 0), (0, 0))))
    out = pl.pallas_call(
        kern,
        grid=(n, n_tiles, oc // ocb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, out_rows, out_cols, ocb),
                               lambda i, t, u: (i, t, 0, u)),
        out_shape=jax.ShapeDtypeStruct((n, n_tiles * out_rows, out_cols, oc),
                                       x_nhwc.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(*operands)
    return out[:, :ph] if pool_kernel is not None else out[:, :oh]


# ---------------------------------------------------------------------------
# §4.4 advanced SIMD — im2col in VMEM + output-channel blocking + epilogue
# ---------------------------------------------------------------------------


def _im2col_band(x_ref, kh, kw, sy, sx, rows, ow, cc=1):
    """The [rows*ow, KH*KW*C] im2col patch matrix of an input-row band
    ref, one strided ``pl.ds`` load per kernel position (Mosaic lowers
    strided ref loads, not strided slices of a loaded value) and lane
    tile (``cc``, see ``_lane_tiled``)."""
    cols = []
    for i in range(kh):  # im2col built once per spatial tile, reused for
        for j in range(kw):  # the whole 128-wide output-channel block (§4.4)
            if cc == 1:
                cols.append(x_ref[pl.ds(i, rows, stride=sy),
                                  pl.ds(j, ow, stride=sx), :]
                            .reshape(rows * ow, -1))
            else:
                cols.append(_tap(x_ref, i, j, sy, sx, rows, ow, cc))
    patches = jnp.concatenate(cols, axis=-1)  # [rows, KH*KW*C]
    return patches


def _advanced_simd_kernel(x_ref, w_ref, b_ref, *refs, kh, kw, sy, sx, relu,
                          pool=None, lrn=None, residual=False, cc=1):
    # x_ref: [BAND, WP, C] (input-row band); w_ref: [KH*KW*C, OC_BLK];
    # refs: the shortcut's [OH_BLK, OW, OC_BLK] block (residual), then
    # o_ref: [OH_BLK, OW, OC_BLK] (unfused) or [PH_BLK, PW, OC_BLK]
    # (fused, the rest staging the conv band: pool_stage_shapes)
    r_ref, o_ref, p_refs = _split_refs(refs, residual)
    if pool is None:
        ohh, oww, ocb = o_ref.shape
    else:
        pkh, _, psy, _, _, _, conv_ow = pool
        phh, _, ocb = o_ref.shape
        ohh, oww = (phh - 1) * psy + pkh, conv_ow  # conv rows this cell owns
    patches = _im2col_band(x_ref, kh, kw, sy, sx, ohh, oww, cc)
    acc = jnp.dot(patches.astype(ACC_DTYPE), w_ref[...].astype(ACC_DTYPE),
                  preferred_element_type=jnp.float32,
                  precision=ACC_PRECISION)  # one MXU matmul
    acc = acc + b_ref[...].astype(ACC_DTYPE)
    if pool is not None:  # fused super-layer: pool in VMEM, write pooled band
        _pool_epilogue(acc, o_ref, p_refs, pool, relu, lrn)
        return
    if r_ref is not None:  # residual join: the shortcut before the ReLU
        acc = acc + r_ref[...].reshape(ohh * oww, ocb).astype(ACC_DTYPE)
    if relu:  # fused epilogue in VMEM — zero-cost ReLU (Fig. 5)
        acc = jnp.maximum(acc, 0.0)
    o_ref[...] = acc.reshape(ohh, oww, ocb).astype(o_ref.dtype)


def _advanced_simd_halo_kernel(x_ref, w_ref, b_ref, o_ref, *p_refs, kh, kw,
                               sy, sx, relu, pool, lrn, cc=1):
    # two-pass channel-halo cell: w_ref/b_ref are widened to the tile's
    # ocb + n - 1 columns (its own channels plus the LRN window's
    # neighbours), so conv+pool run over the widened tile and
    # lrn_band_halo keeps only the ocb core at the store — oc blocking
    # and the LRN epilogue coexist.
    pkh, _, psy, _, _, _, conv_ow = pool
    phh = o_ref.shape[0]
    ohh, oww = (phh - 1) * psy + pkh, conv_ow  # conv rows this cell owns
    pmat = _im2col_band(x_ref, kh, kw, sy, sx, ohh, oww, cc)
    acc = jnp.dot(pmat.astype(ACC_DTYPE), w_ref[...].astype(ACC_DTYPE),
                  preferred_element_type=jnp.float32,
                  precision=ACC_PRECISION)
    acc = acc + b_ref[...].astype(ACC_DTYPE)
    _pool_epilogue_halo(acc, o_ref, p_refs, pool, relu, lrn)


def _advanced_simd_carry_kernel(x_ref, w_ref, b_ref, o_ref, c_ref, *p_refs,
                                kh, kw, sy, sx, relu, pool, k_rows, cc=1):
    # sliding-window pool accumulator: each band step convolves only its
    # R = PH_BLK*psy fresh conv rows; the K = pkh - psy boundary rows are
    # carried in VMEM scratch (c_ref) from the previous step instead of
    # being re-read and re-convolved from the input band.  Step 0 is a
    # pure seed band over the zero prepad (its output block is sliced off
    # host-side); its last K fresh rows are conv rows [0, K).  p_refs
    # stage the carried + fresh conv band for the pool epilogue.
    pkh, _, psy, _, _, _, conv_ow = pool
    phh, _, ocb = o_ref.shape
    r_rows = phh * psy  # fresh conv rows this step owns
    pmat = _im2col_band(x_ref, kh, kw, sy, sx, r_rows, conv_ow, cc)
    acc = jnp.dot(pmat.astype(ACC_DTYPE), w_ref[...].astype(ACC_DTYPE),
                  preferred_element_type=jnp.float32,
                  precision=ACC_PRECISION)
    acc = acc + b_ref[...].astype(ACC_DTYPE)
    fresh = acc.reshape(r_rows, conv_ow, ocb)
    whole = jnp.concatenate([c_ref[...], fresh], axis=0)
    _pool_epilogue(whole.reshape((k_rows + r_rows) * conv_ow, ocb), o_ref,
                   p_refs, pool, relu)
    # slide the window: the LAST K fresh conv rows become the next band's
    # carried head (pre-ReLU fp32 — the epilogue re-applies ReLU on read,
    # so every pooled output still sees relu(conv) exactly once)
    c_ref[...] = jax.lax.slice_in_dim(fresh, r_rows - k_rows, r_rows, axis=0)


def _pack_width_taps(x_nhwc, wmat, rows, px, kh, kw, sx, ow):
    """The conv operand with each kernel row's width taps unfolded into
    the lane axis: column ``q`` of the result holds padded-input columns
    ``q*sx + j`` (``j < kw``) side by side, tap-major, zero-padded to
    ``LANES`` channels, and the im2col weight rows are reordered to
    match.  ``rows`` is the ``(top, bottom)`` zero rows the band plan
    needs and ``px`` the conv's width padding.  The in-kernel patch
    build then joins ``kh`` lane-aligned pieces instead of ``kh*kw``
    narrow ones, which Mosaic would lower to one lane shuffle per piece
    and sublane tile (AlexNet conv1: 121 pieces of 8 lanes).

    On a TPU an HBM array whose minor axis holds C = 3 or 8 channels is
    tiled to 128 lanes, so the taps are cut with W on the lane axis:
    under jit the first transpose cancels the caller's NCHW→NHWC swap.
    They are stacked on a new axis rather than joined along C, which
    would let XLA's layout assignment put C back on the lanes to make
    the final transpose free."""
    n, _, _, c = x_nhwc.shape
    xp = jnp.pad(jnp.transpose(x_nhwc, (0, 3, 1, 2)),
                 ((0, 0), (0, 0), rows, (px, px)))
    hp = xp.shape[2]
    taps = jnp.stack(
        [jax.lax.slice(xp, (0, 0, 0, j), (n, c, hp, j + (ow - 1) * sx + 1),
                       (1, 1, 1, sx)) for j in range(kw)], axis=-1)
    xu = jnp.transpose(taps, (0, 2, 3, 4, 1)).reshape(n, hp, ow, kw * c)
    xu = jnp.pad(xu, ((0, 0), (0, 0), (0, 0), (0, LANES - kw * c)))
    oc = wmat.shape[1]
    wu = jnp.pad(wmat.reshape(kh, kw * c, oc),
                 ((0, 0), (0, LANES - kw * c), (0, 0)))
    return xu, wu.reshape(kh * LANES, oc)


def conv2d_advanced_simd(x_nhwc, w_hwio, b, stride=(1, 1), padding=(0, 0),
                         relu=False, oc_block: int = 128, oh_block=None,
                         interpret: bool = False, pool_kernel=None,
                         pool_stride=None, pool_kind: str = "max",
                         pool_relu: bool = False, lrn=None, pool_carry=None,
                         lrn_oc_block=None, residual=None):
    n, h, wd, c = x_nhwc.shape
    kh, kw, _, oc = w_hwio.shape
    sy, sx = stride
    py, px = padding
    hp, wp = h + 2 * py, wd + 2 * px
    oh, ow = _out_size(h, kh, sy, py), _out_size(wd, kw, sx, px)
    if lrn is not None and pool_kernel is None:
        raise ValueError("fused LRN epilogue requires a fused pool epilogue")
    if residual is not None and pool_kernel is not None:
        raise ValueError("a residual epilogue runs without a pool")
    if pool_kernel is not None:
        pkh, pkw = pool_kernel
        psy, psx = pool_stride if pool_stride is not None else pool_kernel
    # LRN reaches across ALL output channels of a pooled row, so the oc
    # grid collapses to one full-width tile when the epilogue is fused
    # (the planner's working-set check charges the full-width weights) —
    # unless the two-pass channel-halo cell restores the blocking with
    # window-widened weight tiles (resolve_lrn_ocb decides; oc_halo > 0
    # selects the halo dispatch below)
    if lrn is not None:
        ocb, oc_halo = resolve_lrn_ocb(oc, oc_block, lrn, lrn_oc_block, ow,
                                       wp, c, kh, kw, sy,
                                       (pkh, pkw, psy, psx))
    else:
        ocb, oc_halo = min(oc_block, oc), 0
    pad_oc = (-oc) % ocb
    wmat = w_hwio.reshape(kh * kw * c, oc)
    if pad_oc:
        wmat = jnp.pad(wmat, ((0, 0), (0, pad_oc)))
        b = jnp.pad(b, (0, pad_oc))
    ocp = oc + pad_oc
    if oc_halo:
        # widen the weight/bias columns by the LRN window reach; the halo
        # columns outside [0, ocp) are zero, so halo conv channels are
        # exact zeros at the frame edges (lrn_band's zero-pad semantics)
        halo_lo = lrn[0] // 2
        halo_hi = lrn[0] - 1 - halo_lo
        wmat = jnp.pad(wmat, ((0, 0), (halo_lo, halo_hi)))
        b = jnp.pad(b, (halo_lo, halo_hi))
    b = b.reshape(1, -1)  # 2-D: Mosaic tiles a 1-D block unlike XLA
    if pool_kernel is not None:
        # fused super-layer: each cell writes a pooled band, the conv
        # activation stays in VMEM
        bottom, phb, n_tiles, band, cband, ph, pw, row_step = \
            _plan_pool_tiles((n, hp, wp, c), oh, ow, kh, kw, sy, oh_block,
                             ocb, (pkh, pkw, psy, psx), oc_halo=oc_halo)
        pool = (pkh, pkw, psy, psx, pool_kind, pool_relu, ow)
        out_rows, out_cols = phb, pw
        carry = resolve_pool_carry(pool_carry, True, lrn,
                                   (pkh, pkw, psy, psx), phb, n_tiles)
        # the conv band the pool epilogue stages in VMEM
        scratch = pool_stage_shapes(cband, ow, ocb + oc_halo)
    else:
        bottom, ohb, n_tiles, band = _plan_oh_tiles(
            (n, hp, wp, c), oh, kh, kw, sy, oh_block, ow, ocb)
        pool = None
        row_step = ohb * sy
        out_rows, out_cols = ohb, ow
        carry = False
        scratch = []
    prepad = 0
    if carry:
        k_rows = pkh - psy        # conv rows carried between band steps
        r_rows = phb * psy        # fresh conv rows per band step
        band = (r_rows - 1) * sy + kh
        row_step = r_rows * sy
        # the zero prepad makes step 0 a pure seed band: its output block
        # pools prepad zeros (sliced off below) while its last K fresh
        # conv rows are conv rows [0, K) — step 1's carry.  The bottom
        # rows _plan_pool_tiles asked for are exactly what the shifted
        # bands need (the prepad algebra cancels to zero extra).
        prepad = row_step - k_rows * sy
    rows = (py + prepad, py + bottom)
    if kw > 1 and kw * c <= LANES:
        # a kernel row's taps fit one lane tile: unfold them host-side,
        # the cell then runs a (kh, 1) stride-(sy, 1) conv.  Row bands
        # are unchanged, so the geometry resolved above still holds.
        xp, wmat = _pack_width_taps(x_nhwc, wmat, rows, px, kh, kw, sx, ow)
        wp, c, kw, sx = ow, LANES, 1, 1
    else:
        xp = jnp.pad(x_nhwc, ((0, 0), rows, (px, px), (0, 0)))
    xp, wmat, c, cc = _lane_tiled(xp, wmat, c, sy, sx)
    if carry:
        oc_tiles = ocp // ocb
        kern = functools.partial(_advanced_simd_carry_kernel, kh=kh, kw=kw,
                                 sy=sy, sx=sx, relu=relu, pool=pool,
                                 k_rows=k_rows, cc=cc)
        out = pl.pallas_call(
            kern,
            grid=(n, oc_tiles, n_tiles + 1),
            in_specs=[
                # element-offset indexing; the carried rows replace the
                # pool-window share of the inter-band halo
                pl.BlockSpec((None, pl.Element(band), pl.Element(wp * cc),
                              pl.Element(c // cc)),
                             lambda i, u, j: (i, j * row_step, 0, 0)),
                pl.BlockSpec((kh * kw * c, ocb), lambda i, u, j: (0, u)),
                pl.BlockSpec((1, ocb), lambda i, u, j: (0, u)),
            ],
            out_specs=pl.BlockSpec((None, out_rows, out_cols, ocb),
                                   lambda i, u, j: (i, j, 0, u)),
            out_shape=jax.ShapeDtypeStruct(
                (n, (n_tiles + 1) * out_rows, out_cols, ocp), x_nhwc.dtype),
            scratch_shapes=[pltpu.VMEM((k_rows, ow, ocb), jnp.float32)]
            + scratch,
            compiler_params=pltpu.CompilerParams(
                # the band axis is sequential: each step consumes the
                # carry its predecessor left in scratch
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT_BYTES,
            ),
            interpret=interpret,
        )(xp, wmat, b)
        return out[:, out_rows:out_rows + ph, :, :oc]
    if oc_halo:
        oc_tiles = ocp // ocb
        ocw = ocb + oc_halo
        # adjacent weight tiles overlap by the n-1 halo columns: lay each
        # widened tile out on its own leading index (Mosaic blocks the
        # lane axis only at multiples of 128 or the whole dim)
        wmat = jnp.concatenate(
            [jax.lax.slice_in_dim(wmat, u * ocb, u * ocb + ocw, axis=1)
             .reshape(1, kh * kw * c, ocw) for u in range(oc_tiles)], axis=0)
        b = jnp.concatenate(
            [jax.lax.slice_in_dim(b, u * ocb, u * ocb + ocw, axis=1)
             .reshape(1, 1, ocw) for u in range(oc_tiles)], axis=0)
        kern = functools.partial(_advanced_simd_halo_kernel, kh=kh, kw=kw,
                                 sy=sy, sx=sx, relu=relu, pool=pool, lrn=lrn,
                                 cc=cc)
        out = pl.pallas_call(
            kern,
            grid=(n, n_tiles, oc_tiles),
            in_specs=[
                # element-offset indexing: bands overlap by the halo rows
                pl.BlockSpec((None, pl.Element(band), pl.Element(wp * cc),
                              pl.Element(c // cc)),
                             lambda i, t, u: (i, t * row_step, 0, 0)),
                pl.BlockSpec((None, kh * kw * c, ocw),
                             lambda i, t, u: (u, 0, 0)),
                pl.BlockSpec((None, 1, ocw), lambda i, t, u: (u, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, out_rows, out_cols, ocb),
                                   lambda i, t, u: (i, t, 0, u)),
            out_shape=jax.ShapeDtypeStruct(
                (n, n_tiles * out_rows, out_cols, ocp), x_nhwc.dtype),
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel"),
                vmem_limit_bytes=VMEM_LIMIT_BYTES,
            ),
            interpret=interpret,
        )(xp, wmat, b)
        return out[:, :ph, :, :oc]
    kern = functools.partial(_advanced_simd_kernel, kh=kh, kw=kw, sy=sy,
                             sx=sx, relu=relu, pool=pool, lrn=lrn,
                             residual=residual is not None, cc=cc)
    in_specs = [
        # element-offset indexing: bands overlap by the KH-sy halo rows
        pl.BlockSpec((None, pl.Element(band), pl.Element(wp * cc),
                      pl.Element(c // cc)),
                     lambda i, t, o: (i, t * row_step, 0, 0)),
        pl.BlockSpec((kh * kw * c, ocb), lambda i, t, o: (0, o)),
        pl.BlockSpec((1, ocb), lambda i, t, o: (0, o)),
    ]
    operands = [xp, wmat, b]
    if residual is not None:
        # the shortcut, read block for block as the output is written
        in_specs.append(pl.BlockSpec((None, out_rows, out_cols, ocb),
                                     lambda i, t, o: (i, t, 0, o)))
        operands.append(jnp.pad(residual, (
            (0, 0), (0, n_tiles * out_rows - oh), (0, 0), (0, pad_oc))))
    out = pl.pallas_call(
        kern,
        grid=(n, n_tiles, ocp // ocb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, out_rows, out_cols, ocb),
                               lambda i, t, o: (i, t, 0, o)),
        out_shape=jax.ShapeDtypeStruct((n, n_tiles * out_rows, out_cols, ocp),
                                       x_nhwc.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(*operands)
    if pool_kernel is not None:
        return out[:, :ph, :, :oc]
    return out[:, :oh, :, :oc]


# ---------------------------------------------------------------------------
# fused conv→conv chains — a run of convolutions per grid cell, the
# intermediate activations (and their halos) VMEM-resident
# ---------------------------------------------------------------------------
#
# A chain is described by per-stage tuples ``(kh, kw, sy, sx, py, px)`` plus
# the per-stage output-channel counts ``ocs``.  Stage 0's padding is applied
# host-side (like the single-conv kernels); every later stage's horizontal
# padding is materialized in VMEM (``jnp.pad`` on the band's width axis) and
# its *vertical* padding is realized by the zero-masked halo rows of the
# previous stage's band.


def chain_stage_dims(h, w, c, chain, ocs):
    """Per-stage ``(oh, ow, cin, oc)`` propagated through the chain from
    the (unpadded) chain input ``(h, w, c)``."""
    dims = []
    for (kh, kw, sy, sx, py, px), oc in zip(chain, ocs):
        oh = (h + 2 * py - kh) // sy + 1
        ow = (w + 2 * px - kw) // sx + 1
        dims.append((oh, ow, c, oc))
        h, w, c = oh, ow, oc
    return dims


def chain_band_geometry(blk, chain, pool):
    """Backward halo composition for one chain cell producing ``blk``
    final rows (pooled rows when ``pool`` is set).

    Returns ``(m, offs, band, in_step, in_base)``: ``m[i]`` is the rows of
    stage i's output band the cell materializes (``m[i-1] = (m[i]-1)*sy_i
    + kh_i`` — stage i's halo-widened input demand), ``offs[i] = (A, B)``
    the affine map from band index ``t`` to stage i's global starting row
    (``A*t + B``; B goes negative where intermediate vertical padding is
    consumed), ``band`` the input rows per cell, and ``(in_step,
    in_base)`` the affine input-row offset in stage-0 *padded-input*
    coordinates (``in_base`` ≤ 0: the caller pre-pads that many extra
    zero rows on top).
    """
    s = len(chain)
    m = [0] * s
    offs = [(0, 0)] * s
    if pool is not None:
        pkh, _, psy, _ = pool
        m[-1] = (blk - 1) * psy + pkh
        offs[-1] = (blk * psy, 0)
    else:
        m[-1] = blk
        offs[-1] = (blk, 0)
    for i in range(s - 1, 0, -1):
        kh, _, sy, _, py, _ = chain[i]
        a, b = offs[i]
        m[i - 1] = (m[i] - 1) * sy + kh
        offs[i - 1] = (a * sy, b * sy - py)
    kh0, _, sy0, _, _, _ = chain[0]
    band = (m[0] - 1) * sy0 + kh0
    a0, b0 = offs[0]
    return m, offs, band, a0 * sy0, b0 * sy0


def chain_tile_intervals(blk, n_tiles, target, chain, pool):
    """Per-grid-cell ``(start, rows)`` intervals of a chain dispatch —
    final-stage output bands (clipped to the ``target`` valid rows) and
    the composed halo-inclusive input bands in stage-0 *pre-padded*
    coordinates (a negative start means the kernel pre-pads that many
    extra genuine-zero top rows).  Shares ``chain_band_geometry`` with
    the kernel and ``band_intervals`` with the single-conv planners, so
    the verifier proves coverage over exactly what the cell executes."""
    _, _, band, in_step, in_base = chain_band_geometry(blk, chain, pool)
    return band_intervals(n_tiles, blk, target, in_step, band, base=in_base)


def chain_cell_bytes(blk, h, w, c, chain, ocs, pool,
                     im2col: bool = True, itemsize: int = 4,
                     oc_block_final=None) -> int:
    """Modelled VMEM live set of ONE chain grid cell producing ``blk``
    final rows (pooled rows when ``pool`` is set).

    Chains hold every *intermediate* stage's full-width weights resident
    (stage N+1 consumes every channel of stage N, so there is no oc tile
    to shrink them); the per-stage temporaries — incoming band, patch
    staging, outgoing band — are sequential, only one stage's set is live
    at a time, so their *maximum* is charged rather than their sum.  The
    streamed input band and final output band are charged once more on
    top, standing in for their pipeline double buffers.  Nothing consumes
    the FINAL stage's channels inside the cell, so ``oc_block_final``
    restores oc-grid blocking there: the final weights, outgoing band,
    and output stream shrink to one oc tile (the dominant resident-
    weights term for deep chains).  The same model backs the kernel-side
    ``auto_chain_block`` walk and the planner's decline-to-fuse check, so
    the planner never approves a chain the kernel cannot stage.
    """
    dims = chain_stage_dims(h, w, c, chain, ocs)
    m, _, band, _, _ = chain_band_geometry(blk, chain, pool)
    last = len(chain) - 1
    weights = 0
    stage_peak = 0
    in_rows, in_w = band, w + 2 * chain[0][5]
    for i, ((kh, kw, sy, sx, py, px), (oh, ow, ci, oc)) in enumerate(
            zip(chain, dims)):
        # Mosaic single-buffers a grid-invariant whole-array block and
        # double-buffers one that moves: the blocked final stage's
        # weights are charged twice
        copies = 1
        if i == last and oc_block_final is not None:
            copies = 2 if oc_block_final < oc else 1
            oc = min(oc_block_final, oc)
        weights += copies * kh * kw * ci * oc
        patch_c = kh * kw * ci if im2col else ci
        stage_peak = max(stage_peak,
                         in_rows * in_w * ci     # incoming band
                         + m[i] * ow * patch_c   # patch staging
                         + m[i] * ow * oc)       # outgoing band
        if i + 1 < len(chain):
            in_rows, in_w = m[i], ow + 2 * chain[i + 1][5]
    oh_f, ow_f, _, oc_f = dims[-1]
    if oc_block_final is not None:
        oc_f = min(oc_block_final, oc_f)
    if pool is not None:
        pkh, pkw, psy, psx = pool
        out_stream = blk * ((ow_f - pkw) // psx + 1) * oc_f
    else:
        out_stream = blk * ow_f * oc_f
    in_stream = band * (w + 2 * chain[0][5]) * c
    return (weights + stage_peak + in_stream + out_stream) * itemsize


def auto_chain_block(target, h, w, c, chain, ocs, pool,
                     budget: int = None, im2col: bool = True,
                     oc_block_final=None) -> int:
    """Largest final-row band whose chain-cell live set fits ``budget``
    (default ``CHAIN_VMEM_BUDGET_BYTES``); floors at one final row —
    which may exceed the budget: the planner's job is to keep such chains
    un-fused (or shortened) in the first place."""
    budget = CHAIN_VMEM_BUDGET_BYTES if budget is None else budget
    candidates = [target] + [b for b in (512, 256, 128, 64, 32, 16, 8, 4,
                                         2, 1) if b < target]
    for blk in candidates:
        if chain_cell_bytes(blk, h, w, c, chain, ocs, pool, im2col=im2col,
                            oc_block_final=oc_block_final) <= budget:
            return blk
    return 1


def resolve_chain_block(h, w, c, chain, ocs, pool, oh_block,
                        im2col: bool = True, budget: int = None,
                        oc_block_final=None) -> tuple:
    """The equalized final-row band a chain cell will execute with, as
    ``(blk, n_tiles)`` — the ``auto_chain_block`` walk when ``oh_block``
    is None, else the explicit final-stage conv band (snapped down to
    whole pool windows when a pool tail is fused).  Public so the
    engine's geometry report shares the exact resolution the kernel
    runs."""
    dims = chain_stage_dims(h, w, c, chain, ocs)
    oh_f, ow_f = dims[-1][0], dims[-1][1]
    if pool is not None:
        pkh, pkw, psy, psx = pool
        target = (oh_f - pkh) // psy + 1
        if target < 1 or (ow_f - pkw) // psx + 1 < 1:
            raise ValueError(f"pool window ({pkh},{pkw}) larger than final "
                             f"conv output ({oh_f},{ow_f})")
    else:
        target = oh_f
    if oh_block is None:
        blk = auto_chain_block(target, h, w, c, chain, ocs, pool,
                               budget=budget, im2col=im2col,
                               oc_block_final=oc_block_final)
    elif pool is not None:
        ohb = max(1, min(oh_block, oh_f))
        blk = max(1, (ohb - pkh) // psy + 1) if ohb >= pkh else 1
    else:
        blk = oh_block
    return _equalize_bands(blk, target)


def _band_conv(x, w_ref, kh, kw, sy, sx, m, ow, im2col):
    """One chain stage's conv over an in-VMEM fp32 band: ``x`` is
    ``[rows, width, C]``, returns the pre-bias ``[m*ow, OC]`` product —
    the full im2col matmul (advanced) or the per-kernel-position channel
    dots (basic)."""
    c = x.shape[2]
    if im2col:
        cols = []
        for i in range(kh):
            for j in range(kw):
                cols.append(jax.lax.slice(
                    x, (i, j, 0),
                    (i + (m - 1) * sy + 1, j + (ow - 1) * sx + 1, c),
                    (sy, sx, 1),
                ).reshape(m * ow, -1))
        patches = jnp.concatenate(cols, axis=-1)  # [rows, KH*KW*C]
        return jnp.dot(patches, w_ref[...].astype(ACC_DTYPE),
                       preferred_element_type=jnp.float32,
                       precision=ACC_PRECISION)
    acc = jnp.zeros((m * ow, w_ref.shape[-1]), jnp.float32)
    for i in range(kh):
        for j in range(kw):
            patch = jax.lax.slice(
                x, (i, j, 0),
                (i + (m - 1) * sy + 1, j + (ow - 1) * sx + 1, c),
                (sy, sx, 1),
            ).reshape(m * ow, -1)
            # vectorized dot over channels per kernel position (§4.3)
            acc = acc + jnp.dot(patch, w_ref[i, j].astype(ACC_DTYPE),
                                preferred_element_type=jnp.float32,
                                precision=ACC_PRECISION)
    return acc


def _chain_simd_kernel(x_ref, *refs, stages, pool, lrn, im2col):
    # x_ref: [BAND, WP0, C] (halo-widened chain-input band);
    # refs: (w0, b0, w1, b1, ..., o_ref, *p_refs) — p_refs, the pool
    # tail's conv-band staging scratch, only when pool is set; stages:
    # per-stage static tuples
    # (kh, kw, sy, sx, px, m, ow, relu, oh_valid, A, B) where px is the
    # stage's own horizontal padding (0 for stage 0 — host-applied),
    # m/ow the stage's band geometry, oh_valid its true output height and
    # (A, B) the affine band-index→global-row map for the padding mask.
    n_wb = 2 * len(stages)
    wb, o_ref = refs[:n_wb], refs[n_wb]
    t = pl.program_id(1)
    band = x_ref[...].astype(ACC_DTYPE)
    last = len(stages) - 1
    for si, (kh, kw, sy, sx, px, m, ow, relu, oh_valid, a, b0) in enumerate(
            stages):
        if px:
            # this stage's horizontal padding, materialized in VMEM
            band = jnp.pad(band, ((0, 0), (px, px), (0, 0)))
        acc = _band_conv(band, wb[2 * si], kh, kw, sy, sx, m, ow, im2col)
        acc = acc + wb[2 * si + 1][...].astype(ACC_DTYPE)
        if si == last:
            if pool is not None:  # pool(/LRN) the final band in VMEM
                _pool_epilogue(acc, o_ref, refs[n_wb + 1:], pool, relu,
                               lrn)
            else:
                if relu:
                    acc = jnp.maximum(acc, 0.0)
                o_ref[...] = acc.reshape(m, ow, -1).astype(o_ref.dtype)
            return
        if relu:
            acc = jnp.maximum(acc, 0.0)
        out = acc.reshape(m, ow, -1)
        # rows outside this stage's true output ARE the next stage's
        # vertical padding (activation zeros — NOT conv-of-pad-input,
        # which relu(bias) would corrupt): zero-mask them by global row
        rows = (a * t + b0 + jax.lax.broadcasted_iota(jnp.int32, (m, 1, 1),
                                                      0))
        band = jnp.where((rows >= 0) & (rows < oh_valid), out, 0.0)


def conv2d_chain_simd(x_nhwc, ws, bs, strides, paddings, relus,
                      im2col: bool = True, oh_block=None,
                      interpret: bool = False, pool_kernel=None,
                      pool_stride=None, pool_kind: str = "max",
                      pool_relu: bool = False, lrn=None,
                      oc_block_final=None):
    """A chain of consecutive convolutions as one fused dispatch.

    ``ws``: per-stage HWIO weights (channel-contiguous: stage i's input
    channels equal stage i-1's output channels); ``bs``/``strides``/
    ``paddings``/``relus`` parallel per-stage lists.  Each grid cell
    computes an output-row band of the FINAL stage — pooled rows when
    ``pool_kernel`` is set — staging every intermediate band (halo
    included) in VMEM; only the final band is written to HBM.
    Intermediate stages run at full output-channel width (stage N+1
    consumes every channel of stage N); ``oc_block_final`` restores
    oc-grid blocking on the FINAL stage, whose channels nothing inside
    the cell consumes — earlier stages recompute per oc tile, trading
    MACs for the dominant resident-weights term.  ``im2col`` selects the
    advanced (patch-matrix matmul) or basic (per-position channel dots)
    stage compute.
    """
    n, h, wd, c = x_nhwc.shape
    s = len(ws)
    if not (len(bs) == len(strides) == len(paddings) == len(relus) == s):
        raise ValueError("chain stage lists must have equal length")
    if lrn is not None and pool_kernel is None:
        raise ValueError("fused LRN epilogue requires a fused pool epilogue")
    if oc_block_final is not None and lrn is not None:
        raise ValueError("oc-blocked final stage requires no LRN epilogue "
                         "(the LRN window reads every output channel)")
    chain = tuple((w.shape[0], w.shape[1], st[0], st[1], pd[0], pd[1])
                  for w, st, pd in zip(ws, strides, paddings))
    ocs = tuple(w.shape[3] for w in ws)
    dims = chain_stage_dims(h, wd, c, chain, ocs)
    for oh_i, ow_i, _, _ in dims:
        if oh_i < 1 or ow_i < 1:
            raise ValueError("chain stage output collapsed to zero size")
    oh_f, ow_f, _, oc_f = dims[-1]
    if oc_block_final is not None and oc_block_final >= oc_f:
        oc_block_final = None  # full width already: classic dispatch
    if pool_kernel is not None:
        pkh, pkw = pool_kernel
        psy, psx = pool_stride if pool_stride is not None else pool_kernel
        pool_g = (pkh, pkw, psy, psx)
        target = (oh_f - pkh) // psy + 1
        out_cols = (ow_f - pkw) // psx + 1
        if target < 1 or out_cols < 1:
            raise ValueError(f"pool window {pool_kernel} larger than final "
                             f"conv output ({oh_f},{ow_f})")
        pool = (pkh, pkw, psy, psx, pool_kind, pool_relu, ow_f)
    else:
        pool_g, pool = None, None
        target, out_cols = oh_f, ow_f
    blk, n_tiles = resolve_chain_block(h, wd, c, chain, ocs, pool_g,
                                       oh_block, im2col=im2col,
                                       oc_block_final=oc_block_final)
    m, offs, band, in_step, in_base = chain_band_geometry(blk, chain, pool_g)
    # the pool tail stages the final conv band in VMEM
    ocw_f = oc_f if oc_block_final is None else oc_block_final
    scratch = ([] if pool is None else
               pool_stage_shapes(m[-1], ow_f, ocw_f))
    # stage-0 padding host-side (+ the extra top rows the intermediate
    # vertical padding pulls the first band up into, all genuine zeros)
    py0, px0 = paddings[0]
    top = py0 + max(0, -in_base)
    base = in_base + max(0, -in_base)
    hp_need = (n_tiles - 1) * in_step + base + band
    bot = max(py0, hp_need - (h + top))
    xp = jnp.pad(x_nhwc, ((0, 0), (top, bot), (px0, px0), (0, 0)))
    wp0 = xp.shape[2]
    stages = tuple(
        (kh, kw, sy, sx, 0 if i == 0 else px, m[i], dims[i][1], relus[i],
         dims[i][0], offs[i][0], offs[i][1])
        for i, (kh, kw, sy, sx, py, px) in enumerate(chain))
    kern = functools.partial(_chain_simd_kernel, stages=stages, pool=pool,
                             lrn=lrn, im2col=im2col)
    if oc_block_final is not None:
        # oc-blocked final stage: the kernel body is unchanged (it derives
        # every stage width from its weight block), only the grid gains an
        # oc axis and the final stage's weight/bias/output specs block on
        # it — intermediate stages recompute their full-width bands per
        # oc tile
        ocb_f = oc_block_final
        pad_f = (-oc_f) % ocb_f
        wlast, blast = ws[-1], bs[-1]
        if pad_f:
            wlast = jnp.pad(wlast, ((0, 0), (0, 0), (0, 0), (0, pad_f)))
            blast = jnp.pad(blast, (0, pad_f))
        ocp_f = oc_f + pad_f
        in_specs = [
            pl.BlockSpec((None,
                          pl.Element(band), pl.Element(wp0), pl.Element(c)),
                         lambda i, t, o: (i, t * in_step + base, 0, 0)),
        ]
        operands = [xp]
        last_w = s - 1
        for si, (w, b) in enumerate(zip(ws, bs)):
            if si == last_w:
                w, b = wlast, blast
            kh, kw, ci, oc = w.shape
            if im2col:
                operands.append(w.reshape(kh * kw * ci, oc))
                if si == last_w:
                    in_specs.append(pl.BlockSpec((kh * kw * ci, ocb_f),
                                                 lambda i, t, o: (0, o)))
                else:
                    in_specs.append(pl.BlockSpec((kh * kw * ci, oc),
                                                 lambda i, t, o: (0, 0)))
            else:
                operands.append(w)
                if si == last_w:
                    in_specs.append(pl.BlockSpec(
                        (kh, kw, ci, ocb_f), lambda i, t, o: (0, 0, 0, o)))
                else:
                    in_specs.append(pl.BlockSpec(
                        (kh, kw, ci, oc), lambda i, t, o: (0, 0, 0, 0)))
            operands.append(b.reshape(1, oc))
            if si == last_w:
                in_specs.append(pl.BlockSpec((1, ocb_f),
                                             lambda i, t, o: (0, o)))
            else:
                in_specs.append(pl.BlockSpec((1, oc),
                                             lambda i, t, o: (0, 0)))
        out = pl.pallas_call(
            kern,
            grid=(n, n_tiles, ocp_f // ocb_f),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, blk, out_cols, ocb_f),
                                   lambda i, t, o: (i, t, 0, o)),
            out_shape=jax.ShapeDtypeStruct(
                (n, n_tiles * blk, out_cols, ocp_f), x_nhwc.dtype),
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel"),
                vmem_limit_bytes=VMEM_LIMIT_BYTES,
            ),
            interpret=interpret,
        )(*operands)
        return out[:, :target, :, :oc_f]
    in_specs = [
        # element-offset indexing: chain bands overlap by the composed halo
        pl.BlockSpec((None, pl.Element(band), pl.Element(wp0), pl.Element(c)),
                     lambda i, t: (i, t * in_step + base, 0, 0)),
    ]
    operands = [xp]
    for w, b in zip(ws, bs):
        kh, kw, ci, oc = w.shape
        if im2col:
            operands.append(w.reshape(kh * kw * ci, oc))
            in_specs.append(pl.BlockSpec((kh * kw * ci, oc),
                                         lambda i, t: (0, 0)))
        else:
            operands.append(w)
            in_specs.append(pl.BlockSpec((kh, kw, ci, oc),
                                         lambda i, t: (0, 0, 0, 0)))
        operands.append(b.reshape(1, oc))
        in_specs.append(pl.BlockSpec((1, oc), lambda i, t: (0, 0)))
    out = pl.pallas_call(
        kern,
        grid=(n, n_tiles),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, blk, out_cols, oc_f),
                               lambda i, t: (i, t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n_tiles * blk, out_cols, oc_f),
                                       x_nhwc.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(*operands)
    return out[:, :target]
