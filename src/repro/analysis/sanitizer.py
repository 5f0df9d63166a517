"""Kernel sanitizer: abstract-interpretation proofs for Pallas dispatches.

The plan verifier (``repro.analysis.verifier``) proves band coverage by
evaluating the SAME resolver functions the kernels call — a bug in
``resolve_oh_block`` or ``chain_band_geometry`` fools both sides at
once.  This module closes that loop-hole with an N-version check that
shares NOTHING with the trusted code:

* **Phase A** re-derives every piece of band geometry from scratch —
  output sizes, halo bands, VMEM cell-byte models, the auto-block
  candidate walks, band equalization, and the backward chain halo
  composition — as fresh arithmetic written against the paper's tiling
  contract, not against the kernel sources.
* **Phase B** symbolically executes the actual kernel **source text**
  (parsed with ``ast``, never imported): the entry function runs
  concretely for one dispatch config, except that every call into a
  trusted resolver is intercepted and answered by Phase A; the kernel
  *body* then runs with grid indices as affine symbols over
  ``[0, grid_dim)`` and block offsets as affine expressions, proving:

  K101  every ``x_ref``/``w_ref`` load (block, slice, ``pl.ds``) stays
        inside the padded operand extents for ALL grid indices,
  K102  the union of ``o_ref`` stores covers every output element
        exactly once (no gaps, overlaps, ragged tails, or unguarded
        overwrites on accumulation axes),
  K103  accumulation happens in fp32 with exactly one downcast at the
        final ``o_ref`` store,
  K104  masked intermediate-padding rows in chain cells are provably
        zero before the next stage consumes them.

Anything the interpreter cannot prove — an unsupported construct, an
entry that raises, an internal inconsistency — degrades to a K100
finding, never to a silent pass.

This module imports ONLY the stdlib and the findings taxonomy.  It must
never import ``repro.core.fusion``, ``repro.analysis.verifier`` or the
kernel modules themselves (asserted by the tests): the whole point is
that its numbers come from a second, independent derivation.  The
cross-check between the two derivations is K105, performed by
``tools/sanitize.py``.
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import Any, Dict, List, Tuple

from repro.analysis.findings import Finding

# ---------------------------------------------------------------------------
# Phase A — fresh re-derivation of the band geometry (no shared code)
# ---------------------------------------------------------------------------

# The kernels target half of the ~16 MB/core VMEM for streamed cells and
# near-full capacity for chain cells (weights are grid-invariant).  Both
# constants are re-stated here on purpose: if the kernel side drifts,
# the K105 cross-check must see the disagreement.
_A_VMEM_BUDGET = 8 << 20
_A_CHAIN_BUDGET = 14 << 20
_A_BLOCK_CANDIDATES = (512, 256, 128, 64, 32, 16, 8, 4, 2, 1)


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _a_out(size: int, k: int, stride: int, pad: int) -> int:
    """Convolution output extent for SAME-style symmetric padding."""
    return (size + 2 * pad - k) // stride + 1


def _a_band(blk: int, k: int, stride: int) -> int:
    """Input rows a ``blk``-row output band reads, halo included."""
    return (blk - 1) * stride + k


def _a_equalize(blk: int, target: int) -> Tuple[int, int]:
    """Clamp, then re-snap a band to ``ceil(target / n_tiles)`` so the
    ragged last band shrinks to its fair share."""
    blk = max(1, min(blk, target))
    n_tiles = _ceil_div(target, blk)
    blk = _ceil_div(target, n_tiles)
    return blk, _ceil_div(target, blk)


def _a_intervals(n_tiles, blk, total, row_step, band, base=0):
    """Per-cell (start, rows) output/input intervals of a banded grid."""
    out_iv = [(t * blk, max(0, min(blk, total - t * blk)))
              for t in range(n_tiles)]
    in_iv = [(base + t * row_step, band) for t in range(n_tiles)]
    return out_iv, in_iv


def _a_conv_cell(ohb, ow, wp, c, kh, kw, sy, ocb, im2col=True, itemsize=4):
    patch_c = kh * kw * c if im2col else c
    return (_a_band(ohb, kh, sy) * wp * c + ohb * ow * patch_c
            + kh * kw * c * ocb + ohb * ow * ocb) * itemsize


def _a_auto_oh(oh, ow, wp, c, kh, kw, sy, oc_block,
               budget=_A_VMEM_BUDGET, itemsize=4, im2col=True):
    for ohb in [oh] + [b for b in _A_BLOCK_CANDIDATES if b < oh]:
        if _a_conv_cell(ohb, ow, wp, c, kh, kw, sy, oc_block,
                        im2col=im2col, itemsize=itemsize) <= budget:
            return ohb
    return 1


def _a_resolve_oh(oh, ow, wp, c, kh, kw, sy, oc_block, oh_block,
                  im2col=True):
    if oh_block is None:
        return _a_auto_oh(oh, ow, wp, c, kh, kw, sy, oc_block,
                          im2col=im2col)
    return max(1, min(oh_block, oh))


def _a_fused_cell(phb, ow, wp, c, kh, kw, sy, ocb, pool,
                  im2col=True, itemsize=4, oc_halo=0):
    pkh, pkw, psy, psx = pool
    pw = (ow - pkw) // psx + 1
    cband = _a_band(phb, pkh, psy)
    band = _a_band(cband, kh, sy)
    patch_c = kh * kw * c if im2col else c
    ocw = ocb + oc_halo
    return (band * wp * c + cband * ow * patch_c + kh * kw * c * ocw
            + cband * ow * ocw + phb * pw * ocw) * itemsize


def _a_auto_ph(ph, ow, wp, c, kh, kw, sy, oc_block, pool,
               budget=_A_VMEM_BUDGET, im2col=True, oc_halo=0):
    for phb in [ph] + [b for b in _A_BLOCK_CANDIDATES if b < ph]:
        if _a_fused_cell(phb, ow, wp, c, kh, kw, sy, oc_block, pool,
                         im2col=im2col, oc_halo=oc_halo) <= budget:
            return phb
    return 1


def _a_resolve_ph(ph, oh, ow, wp, c, kh, kw, sy, oc_block, pool, oh_block,
                  im2col=True, oc_halo=0):
    pkh, _, psy, _ = pool
    if oh_block is None:
        phb = _a_auto_ph(ph, ow, wp, c, kh, kw, sy, oc_block, pool,
                         im2col=im2col, oc_halo=oc_halo)
    else:
        ohb = max(1, min(oh_block, oh))
        phb = max(1, (ohb - pkh) // psy + 1) if ohb >= pkh else 1
    return _a_equalize(phb, ph)


def _a_resolve_lrn_ocb(oc, oc_block, lrn, lrn_oc_block, ow, wp, c, kh, kw,
                       sy, pool, im2col=True):
    """Phase-A re-derivation of the two-pass channel-halo split: the
    ``(ocb, oc_halo)`` a fused conv→pool→LRN dispatch runs with.  Auto
    keeps the classic full-width tile whenever the one-pooled-row floor
    cell fits the (re-stated) budget; otherwise the oc tile shrinks and
    every weight tile is widened by the LRN window's n-1 neighbours."""
    if lrn is None or not im2col:
        return (min(oc_block, oc) if im2col else oc), 0
    blocked = min(oc_block, oc)
    if blocked >= oc or lrn_oc_block is False:
        return oc, 0
    if lrn_oc_block is None and _a_fused_cell(
            1, ow, wp, c, kh, kw, sy, oc, pool) <= _A_VMEM_BUDGET:
        return oc, 0
    return blocked, lrn[0] - 1


def _a_resolve_pool_carry(pool_carry, im2col, lrn, pool, phb, n_tiles):
    """Phase-A re-derivation of the sliding-window carry gate: adjacent
    bands share ``K = pkh - psy`` conv rows, carried in VMEM scratch
    when overlap exists (K >= 1), fits one band's fresh rows
    (K <= phb*psy), and there is more than one band."""
    if pool is None or lrn is not None or not im2col \
            or pool_carry is False:
        return False
    k_rows = pool[0] - pool[2]
    return 1 <= k_rows <= phb * pool[2] and n_tiles > 1


def _a_chain_dims(h, w, c, chain, ocs):
    dims = []
    for (kh, kw, sy, sx, py, px), oc in zip(chain, ocs):
        oh, ow = _a_out(h, kh, sy, py), _a_out(w, kw, sx, px)
        dims.append((oh, ow, c, oc))
        h, w, c = oh, ow, oc
    return dims


def _a_chain_geom(blk, chain, pool):
    """Backward halo composition: rows/offsets every stage materializes
    for one cell of ``blk`` final (pooled) rows."""
    s = len(chain)
    m = [0] * s
    offs = [(0, 0)] * s
    if pool is not None:
        pkh, _, psy, _ = pool
        m[-1] = _a_band(blk, pkh, psy)
        offs[-1] = (blk * psy, 0)
    else:
        m[-1] = blk
        offs[-1] = (blk, 0)
    for i in range(s - 1, 0, -1):
        kh, _, sy, _, py, _ = chain[i]
        a, b = offs[i]
        m[i - 1] = _a_band(m[i], kh, sy)
        offs[i - 1] = (a * sy, b * sy - py)
    kh0, _, sy0, _, _, _ = chain[0]
    band = _a_band(m[0], kh0, sy0)
    a0, b0 = offs[0]
    return m, offs, band, a0 * sy0, b0 * sy0


def _a_chain_cell(blk, h, w, c, chain, ocs, pool, im2col=True, itemsize=4,
                  oc_block_final=None):
    dims = _a_chain_dims(h, w, c, chain, ocs)
    m, _, band, _, _ = _a_chain_geom(blk, chain, pool)
    last = len(chain) - 1
    weights = 0
    stage_peak = 0
    in_rows, in_w = band, w + 2 * chain[0][5]
    for i, ((kh, kw, sy, sx, py, px), (oh, ow, ci, oc)) in enumerate(
            zip(chain, dims)):
        # a weight block that moves along the oc grid is double-buffered;
        # the grid-invariant ones are fetched once
        copies = 1
        if i == last and oc_block_final is not None:
            copies = 2 if oc_block_final < oc else 1
            oc = min(oc_block_final, oc)
        weights += copies * kh * kw * ci * oc
        patch_c = kh * kw * ci if im2col else ci
        stage_peak = max(stage_peak, in_rows * in_w * ci
                         + m[i] * ow * patch_c + m[i] * ow * oc)
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


def _a_auto_chain(target, h, w, c, chain, ocs, pool, budget=None,
                  im2col=True, oc_block_final=None):
    budget = _A_CHAIN_BUDGET if budget is None else budget
    for blk in [target] + [b for b in _A_BLOCK_CANDIDATES if b < target]:
        if _a_chain_cell(blk, h, w, c, chain, ocs, pool, im2col=im2col,
                         oc_block_final=oc_block_final) <= budget:
            return blk
    return 1


def _a_resolve_chain(h, w, c, chain, ocs, pool, oh_block, im2col=True,
                     budget=None, oc_block_final=None):
    dims = _a_chain_dims(h, w, c, chain, ocs)
    oh_f, ow_f = dims[-1][0], dims[-1][1]
    if pool is not None:
        pkh, pkw, psy, psx = pool
        target = (oh_f - pkh) // psy + 1
        if target < 1 or (ow_f - pkw) // psx + 1 < 1:
            raise KernelRaise(f"pool window ({pkh},{pkw}) larger than "
                              f"final conv output ({oh_f},{ow_f})")
    else:
        target = oh_f
    if oh_block is None:
        blk = _a_auto_chain(target, h, w, c, chain, ocs, pool,
                            budget=budget, im2col=im2col,
                            oc_block_final=oc_block_final)
    elif pool is not None:
        ohb = max(1, min(oh_block, oh_f))
        blk = max(1, (ohb - pkh) // psy + 1) if ohb >= pkh else 1
    else:
        blk = oh_block
    return _a_equalize(blk, target)


def _a_auto_oh_pool(oh, ow, wp, c, kh, sy, budget=_A_VMEM_BUDGET,
                    itemsize=4):
    """Pool tiler: the conv candidate walk with weight/oc terms zeroed."""
    return _a_auto_oh(oh, ow, wp, c, kh, 1, sy, 0, budget=budget,
                      itemsize=itemsize, im2col=False)


# ---------------------------------------------------------------------------
# Phase B — the abstract domain
# ---------------------------------------------------------------------------


class Unsupported(Exception):
    """The interpreter met a construct outside its proven subset."""


class KernelRaise(Exception):
    """The interpreted entry raised (ValueError / failed assert)."""


class Aff:
    """Affine integer expression over grid symbols: sum(c_i * g_i) + k."""

    __slots__ = ("coeffs", "const")

    def __init__(self, coeffs=None, const=0):
        self.coeffs = {s: c for s, c in (coeffs or {}).items() if c != 0}
        self.const = const

    @staticmethod
    def lift(v):
        if isinstance(v, Aff):
            return v
        if isinstance(v, bool) or not isinstance(v, int):
            raise Unsupported(f"non-integer in affine arithmetic: {v!r}")
        return Aff({}, v)

    def as_int(self):
        return self.const if not self.coeffs else None

    def __add__(self, other):
        other = Aff.lift(other)
        coeffs = dict(self.coeffs)
        for s, c in other.coeffs.items():
            coeffs[s] = coeffs.get(s, 0) + c
        return Aff(coeffs, self.const + other.const)

    __radd__ = __add__

    def __sub__(self, other):
        other = Aff.lift(other)
        return self + Aff({s: -c for s, c in other.coeffs.items()},
                          -other.const)

    def __rsub__(self, other):
        return Aff.lift(other) - self

    def __mul__(self, other):
        if isinstance(other, Aff):
            if other.coeffs and self.coeffs:
                raise Unsupported("non-affine product of grid symbols")
            if other.coeffs:
                return other * self.const
            other = other.const
        if not isinstance(other, int) or isinstance(other, bool):
            raise Unsupported(f"affine * {other!r}")
        return Aff({s: c * other for s, c in self.coeffs.items()},
                   self.const * other)

    __rmul__ = __mul__

    def bounds(self, sym_ranges):
        """(min, max) over every symbol's range [0, dim)."""
        lo = hi = self.const
        for s, c in self.coeffs.items():
            dim = sym_ranges[s]
            ext = c * (dim - 1)
            lo += min(0, ext)
            hi += max(0, ext)
        return lo, hi

    def __eq__(self, other):  # used by == in interpreted kernel code
        if isinstance(other, Aff):
            same = (self.coeffs == other.coeffs
                    and self.const == other.const)
            if same:
                return True
            other_i = other.as_int()
            if other_i is None:
                raise Unsupported("affine == affine comparison")
            other = other_i
        if isinstance(other, int):
            return Pred(self, other)
        return NotImplemented

    def __hash__(self):
        return hash((tuple(sorted(self.coeffs.items())), self.const))

    def __repr__(self):
        terms = [f"{c}*g{s}" for s, c in sorted(self.coeffs.items())]
        terms.append(str(self.const))
        return " + ".join(terms)


class Pred:
    """``affine == value`` guard predicate (the ``pl.when`` condition)."""

    __slots__ = ("aff", "value")

    def __init__(self, aff: Aff, value: int):
        self.aff = aff
        self.value = value

    def sym_eq(self):
        """As ``(sym, value)`` when the form is ``1*g_s + 0 == value``."""
        if len(self.aff.coeffs) == 1 and self.aff.const == 0:
            (s, c), = self.aff.coeffs.items()
            if c == 1:
                return s, self.value
        raise Unsupported(f"guard predicate not sym==const: {self.aff!r}")

    def __repr__(self):
        return f"({self.aff!r} == {self.value})"


class IotaV:
    """``broadcasted_iota`` along one axis (the chain row index)."""

    __slots__ = ("shape", "axis")

    def __init__(self, shape, axis):
        self.shape = shape
        self.axis = axis


class RowExpr:
    """``affine + iota``: the global row index of each band row."""

    __slots__ = ("aff", "iota")

    def __init__(self, aff, iota):
        self.aff = aff
        self.iota = iota

    def compare(self, op, value):
        if not isinstance(value, int):
            raise Unsupported(f"row compare against {value!r}")
        return RowPred(self, op, value)


class RowPred:
    """One half of a row-range predicate: ``rows >= v`` / ``rows < v``."""

    __slots__ = ("expr", "op", "value")

    def __init__(self, expr, op, value):
        self.expr = expr
        self.op = op
        self.value = value

    def __and__(self, other):
        if isinstance(other, RowPred):
            return RowRange(self, other)
        return NotImplemented


class RowRange:
    """``(rows >= lo) & (rows < hi)`` — a provable row mask."""

    __slots__ = ("lo_pred", "hi_pred")

    def __init__(self, a, b):
        if a.op == "ge" and b.op == "lt":
            self.lo_pred, self.hi_pred = a, b
        elif a.op == "lt" and b.op == "ge":
            self.lo_pred, self.hi_pred = b, a
        else:
            raise Unsupported("row mask is not a [lo, hi) range")
        if self.lo_pred.expr is not self.hi_pred.expr:
            raise Unsupported("row mask bounds test different row exprs")

    def key(self):
        """(coeffs, const, lo, hi) canonical mask identity."""
        aff = self.lo_pred.expr.aff
        return (tuple(sorted(aff.coeffs.items())), aff.const,
                self.lo_pred.value, self.hi_pred.value)


class DtypeMarker:
    """A concrete dtype literal (``jnp.float32`` / ``ACC_DTYPE`` / ...)."""

    __slots__ = ("tag",)

    def __init__(self, tag):
        self.tag = tag


class DtypeOf:
    """``some_ref.dtype`` / ``some_array.dtype`` — a deferred dtype."""

    __slots__ = ("tag", "of_out")

    def __init__(self, tag, of_out):
        self.tag = tag
        self.of_out = of_out


_DT_ORDER = ("weak", "bool", "i32", "f32", "io", "f64")


def _dt_join(a: str, b: str) -> str:
    return a if _DT_ORDER.index(a) >= _DT_ORDER.index(b) else b


def _broadcast(sa, sb):
    out = []
    for da, db in zip(((1,) * (len(sb) - len(sa)) + tuple(sa)),
                      ((1,) * (len(sa) - len(sb)) + tuple(sb))):
        if da == db or db == 1:
            out.append(da)
        elif da == 1:
            out.append(db)
        else:
            raise Unsupported(f"broadcast mismatch {sa} vs {sb}")
    return tuple(out)


class AArray:
    """Abstract array: concrete shape + precision-flow metadata.

    ``dt``         the dtype lattice tag ('io' = the dispatch I/O dtype),
    ``downcasts``  how many astype-to-a-ref-dtype casts the value passed,
    ``tainted``    arithmetic happened AFTER a downcast,
    ``from_out``   the value derives from an ``o_ref`` read (RMW),
    ``mask``       canonical row-mask key when the value is provably
                   zero outside an affine row range (chain K104),
    ``row_slice``  ``(start, stop, dim)`` when the value is exactly a
                   contiguous axis-0 row slice of another array (set only
                   by ``lax.slice_in_dim(axis=0)``; any other op clears
                   it) — the carry-discipline proof (K106) uses it to
                   show a scratch store keeps the band's TAIL rows.
    """

    __slots__ = ("shape", "dt", "downcasts", "tainted", "from_out", "mask",
                 "row_slice")

    def __init__(self, shape, dt="io", downcasts=0, tainted=False,
                 from_out=False, mask=None, row_slice=None):
        self.shape = tuple(shape)
        self.dt = dt
        self.downcasts = downcasts
        self.tainted = tainted
        self.from_out = from_out
        self.mask = mask
        self.row_slice = row_slice

    @property
    def ndim(self):
        return len(self.shape)

    def like(self, shape=None, dt=None, mask=None, downcasts=None,
             tainted=None, from_out=None):
        return AArray(self.shape if shape is None else shape,
                      self.dt if dt is None else dt,
                      self.downcasts if downcasts is None else downcasts,
                      self.tainted if tainted is None else tainted,
                      self.from_out if from_out is None else from_out,
                      mask)


def _arr_binop(a, b, interp):
    """Join two operands of an elementwise op into one AArray."""
    arrs = [v for v in (a, b) if isinstance(v, AArray)]
    shape = arrs[0].shape
    for v in arrs[1:]:
        shape = _broadcast(shape, v.shape)
    dt = "weak"
    downcasts = 0
    tainted = from_out = False
    for v in arrs:
        dt = _dt_join(dt, v.dt)
        downcasts = max(downcasts, v.downcasts)
        tainted = tainted or v.tainted
        from_out = from_out or v.from_out
    tainted = tainted or downcasts > 0
    if dt == "f64":
        interp.finding("K103", "arithmetic in float64 inside a kernel "
                               "body — accumulation must stay fp32")
    return AArray(shape, dt, downcasts, tainted, from_out)


class Ref:
    """A VMEM block ref bound to one kernel parameter.

    ``is_scratch`` marks a ``scratch_shapes`` VMEM ref: readable AND
    writable, persistent across grid steps on an 'arbitrary' axis — its
    load/store events feed the K106 carry-discipline proof instead of
    the K102 output-coverage lattice."""

    __slots__ = ("name", "shape", "dt", "is_out", "is_scratch")

    def __init__(self, name, shape, dt, is_out, is_scratch=False):
        self.name = name
        self.shape = tuple(shape)
        self.dt = dt
        self.is_out = is_out
        self.is_scratch = is_scratch

    @property
    def ndim(self):
        return len(self.shape)


class DS:
    """``pl.ds(start, size, stride)``: ``size`` elements from ``start``,
    ``stride`` apart."""
    __slots__ = ("start", "size", "stride")

    def __init__(self, start, size, stride=1):
        self.start = start
        self.size = size
        self.stride = stride


class Closure:
    __slots__ = ("node", "env", "name", "module")

    def __init__(self, node, env, name, module):
        self.node = node
        self.env = env
        self.name = name
        self.module = module


class PyFn:
    """A Phase-A interception: answers a trusted-resolver call."""

    __slots__ = ("fn", "name")

    def __init__(self, fn, name):
        self.fn = fn
        self.name = name


class PartialV:
    __slots__ = ("fn", "args", "kwargs")

    def __init__(self, fn, args, kwargs):
        self.fn = fn
        self.args = args
        self.kwargs = kwargs


class ElementV:
    """A ``pl.Element(size)`` block dim: the index map returns an element
    offset on that axis instead of a block index."""
    __slots__ = ("size",)

    def __init__(self, size):
        self.size = size


class BlockSpecV:
    """``block_shape`` holds plain sizes (``None`` = squeezed);
    ``element_axes`` are the axes declared ``pl.Element``."""
    __slots__ = ("block_shape", "index_map", "element_axes")

    def __init__(self, block_shape, index_map):
        self.element_axes = frozenset(
            a for a, d in enumerate(block_shape) if isinstance(d, ElementV))
        self.block_shape = tuple(d.size if isinstance(d, ElementV) else d
                                 for d in block_shape)
        self.index_map = index_map


class ShapeDtypeV:
    __slots__ = ("shape", "dt")

    def __init__(self, shape, dt):
        self.shape = shape
        self.dt = dt


class CompilerParamsV:
    __slots__ = ("dimension_semantics",)

    def __init__(self, dimension_semantics):
        self.dimension_semantics = dimension_semantics


class PlWhenV:
    """``pl.when(pred)`` decorator: runs the body under a guard."""

    __slots__ = ("pred",)

    def __init__(self, pred):
        self.pred = pred


class PallasV:
    """The configured ``pl.pallas_call(...)`` awaiting its operands."""

    __slots__ = ("kernel", "grid", "in_specs", "out_specs", "out_shape",
                 "dimension_semantics", "scratch_shapes")

    def __init__(self, kernel, grid, in_specs, out_specs, out_shape,
                 dimension_semantics, scratch_shapes=None):
        self.kernel = kernel
        self.grid = grid
        self.in_specs = in_specs
        self.out_specs = out_specs
        self.out_shape = out_shape
        self.dimension_semantics = dimension_semantics
        self.scratch_shapes = list(scratch_shapes or [])


class ModuleHandle:
    """``jnp`` / ``jax`` / ``pl`` / ... — attribute access namespaces."""

    __slots__ = ("name",)

    def __init__(self, name):
        self.name = name


class Store:
    """One recorded ``o_ref`` store event."""

    __slots__ = ("guards", "value", "full_block", "line")

    def __init__(self, guards, value, full_block, line):
        self.guards = tuple(guards)
        self.value = value
        self.full_block = full_block
        self.line = line


class _Return(Exception):
    def __init__(self, value):
        self.value = value


class BoundMethod:
    __slots__ = ("obj", "name")

    def __init__(self, obj, name):
        self.obj = obj
        self.name = name


class _ModFn:
    """A function reached through a module handle (``jnp.pad`` ...)."""

    __slots__ = ("path",)

    def __init__(self, path):
        self.path = path


_BUILTINS = {"range": range, "len": len, "min": min, "max": max,
             "enumerate": enumerate, "zip": zip, "tuple": tuple,
             "list": list, "int": int, "float": float, "abs": abs,
             "sum": sum}


class Env:
    __slots__ = ("vars", "parent")

    def __init__(self, vars=None, parent=None):
        self.vars = vars if vars is not None else {}
        self.parent = parent

    def get(self, name):
        env = self
        while env is not None:
            if name in env.vars:
                return env.vars[name]
            env = env.parent
        if name in _BUILTINS:
            return _BUILTINS[name]
        raise Unsupported(f"unresolved name {name!r}")

    def set(self, name, value):
        self.vars[name] = value


def _tag_of(dtype_arg):
    """Dtype tag of a dtype-position argument."""
    if isinstance(dtype_arg, DtypeMarker):
        return dtype_arg.tag
    if isinstance(dtype_arg, DtypeOf):
        return dtype_arg.tag
    raise Unsupported(f"unrecognized dtype argument {dtype_arg!r}")


class Interp:
    """Concrete-plus-affine AST interpreter for kernel source modules."""

    def __init__(self, modules, label, findings):
        self.modules = modules          # module name -> Env
        self.label = label
        self.findings = findings
        self.sym_ranges: Dict[int, int] = {}
        self.guards: List[Pred] = []
        self.stores: List[Store] = []
        self.scratch_events: List[Tuple] = []
        self.band_conv_masks: List[Any] = []
        self.line = 0

    # -- findings ----------------------------------------------------------

    def finding(self, rule, detail, severity="error"):
        f = Finding(severity, f"{self.label}:L{self.line}", rule, detail)
        if f not in self.findings:
            self.findings.append(f)

    # -- calls -------------------------------------------------------------

    def call(self, fn, args, kwargs):
        if isinstance(fn, PyFn):
            return fn.fn(*args, **kwargs)
        if isinstance(fn, PartialV):
            merged_kw = dict(fn.kwargs)
            merged_kw.update(kwargs)
            return self.call(fn.fn, list(fn.args) + list(args), merged_kw)
        if isinstance(fn, PlWhenV):
            (closure,) = args
            self.guards.append(fn.pred)
            try:
                self.call(closure, [], {})
            finally:
                self.guards.pop()
            return None
        if isinstance(fn, Closure):
            return self.call_closure(fn, args, kwargs)
        if isinstance(fn, _ModFn):
            return self.call_modfn(fn, args, kwargs)
        if isinstance(fn, PallasV):
            return self.analyze_dispatch(fn, args)
        if isinstance(fn, BoundMethod):
            return self.call_method(fn, args, kwargs)
        if callable(fn) and not isinstance(fn, (AArray, Ref, Aff)):
            return fn(*args, **kwargs)
        raise Unsupported(f"call of non-callable {fn!r}")

    def call_closure(self, clos, args, kwargs):
        if clos.name == "_band_conv" and args:
            x = args[0]
            self.band_conv_masks.append(
                x.mask if isinstance(x, AArray) else None)
        node = clos.node
        a = node.args
        if a.posonlyargs:
            raise Unsupported("positional-only parameters")
        env = Env(parent=clos.env)
        names = [p.arg for p in a.args]
        defaults = a.defaults
        n_required = len(names) - len(defaults)
        pos = list(args)
        kw = dict(kwargs)
        for i, name in enumerate(names):
            if pos:
                env.set(name, pos.pop(0))
            elif name in kw:
                env.set(name, kw.pop(name))
            elif i >= n_required:
                env.set(name,
                        self.eval(defaults[i - n_required], clos.env))
            else:
                raise Unsupported(
                    f"missing argument {name!r} calling {clos.name}")
        if a.vararg is not None:
            env.set(a.vararg.arg, tuple(pos))
            pos = []
        if pos:
            raise Unsupported(f"too many arguments calling {clos.name}")
        for p, dflt in zip(a.kwonlyargs, a.kw_defaults):
            if p.arg in kw:
                env.set(p.arg, kw.pop(p.arg))
            elif dflt is not None:
                env.set(p.arg, self.eval(dflt, clos.env))
            else:
                raise Unsupported(
                    f"missing keyword argument {p.arg!r} in {clos.name}")
        if kw:
            raise Unsupported(
                f"unexpected keyword(s) {sorted(kw)} calling {clos.name}")
        if isinstance(node, ast.Lambda):
            return self.eval(node.body, env)
        try:
            self.exec_block(node.body, env, clos.module)
        except _Return as r:
            return r.value
        return None

    def call_method(self, bm, args, kwargs):
        obj, name = bm.obj, bm.name
        if isinstance(obj, list) and name == "append":
            obj.append(args[0])
            return None
        if isinstance(obj, AArray) and name == "astype":
            (target,) = args
            if isinstance(target, DtypeOf):
                return obj.like(dt=target.tag,
                                downcasts=obj.downcasts + 1)
            tag = _tag_of(target)
            if tag == "f64":
                self.finding("K103", "astype to float64 inside a kernel "
                                     "body — accumulation must stay fp32")
            return obj.like(dt=tag, mask=obj.mask)
        if isinstance(obj, AArray) and name == "reshape":
            dims = list(args[0]) if len(args) == 1 and isinstance(
                args[0], (tuple, list)) else list(args)
            total = 1
            for d in obj.shape:
                total *= d
            if dims.count(-1) > 1:
                raise Unsupported("reshape with multiple -1 dims")
            if -1 in dims:
                known = 1
                for d in dims:
                    if d != -1:
                        known *= d
                dims[dims.index(-1)] = total // max(known, 1)
            prod = 1
            for d in dims:
                prod *= d
            if prod != total:
                self.finding("K100", f"reshape {obj.shape} -> {tuple(dims)}"
                                     " changes element count")
            return obj.like(shape=tuple(dims))
        raise Unsupported(f"method {name!r} on {type(obj).__name__}")

    # -- statements --------------------------------------------------------

    def exec_block(self, stmts, env, module):
        for st in stmts:
            self.exec_stmt(st, env, module)

    def exec_stmt(self, st, env, module):
        self.line = getattr(st, "lineno", self.line)
        if isinstance(st, ast.FunctionDef):
            clos = Closure(st, env, st.name, module)
            result = clos
            for dec in reversed(st.decorator_list):
                result = self.call(self.eval(dec, env), [result], {})
            env.set(st.name, result)
        elif isinstance(st, ast.Return):
            raise _Return(self.eval(st.value, env)
                          if st.value is not None else None)
        elif isinstance(st, ast.Assign):
            value = self.eval(st.value, env)
            for tgt in st.targets:
                self.assign(tgt, value, env)
        elif isinstance(st, ast.AugAssign):
            if isinstance(st.target, ast.Name):
                cur = env.get(st.target.id)
                env.set(st.target.id,
                        self.binop(st.op, cur, self.eval(st.value, env)))
            elif isinstance(st.target, ast.Subscript):
                ref = self.eval(st.target.value, env)
                if not isinstance(ref, Ref):
                    raise Unsupported("augmented store to non-ref")
                idx = self.eval_index(st.target.slice, env)
                cur = self.ref_load(ref, idx)
                self.ref_store(ref, idx,
                               self.binop(st.op, cur,
                                          self.eval(st.value, env)))
            else:
                raise Unsupported("augmented assignment target")
        elif isinstance(st, ast.Expr):
            self.eval(st.value, env)
        elif isinstance(st, ast.If):
            if self.truth(self.eval(st.test, env)):
                self.exec_block(st.body, env, module)
            else:
                self.exec_block(st.orelse, env, module)
        elif isinstance(st, ast.For):
            for item in self.iterate(self.eval(st.iter, env)):
                self.assign(st.target, item, env)
                self.exec_block(st.body, env, module)
            if st.orelse:
                self.exec_block(st.orelse, env, module)
        elif isinstance(st, ast.Raise):
            raise KernelRaise(self.describe_raise(st, env))
        elif isinstance(st, ast.Assert):
            if not self.truth(self.eval(st.test, env)):
                raise KernelRaise(f"assert failed at line {self.line}")
        elif isinstance(st, ast.ImportFrom):
            self.import_from(st, env)
        elif isinstance(st, ast.Pass):
            pass
        else:
            raise Unsupported(f"statement {type(st).__name__}")

    def describe_raise(self, st, env):
        if st.exc is None:
            return "bare raise"
        try:
            if isinstance(st.exc, ast.Call) and st.exc.args:
                msg = self.eval(st.exc.args[0], env)
                return str(msg)
        except Unsupported:
            pass
        return f"raise at line {self.line}"

    def import_from(self, st, env):
        mod = st.module or ""
        for known, envname in (("repro.kernels.conv2d.kernels", "conv2d"),
                               ("repro.kernels.pool2d.kernels", "pool2d"),
                               ("repro.kernels.matmul_fused.kernel",
                                "matmul")):
            if mod == known:
                src = self.modules.get(envname)
                if src is None:
                    raise Unsupported(f"import from unloaded module {mod}")
                for alias in st.names:
                    env.set(alias.asname or alias.name,
                            src.get(alias.name))
                return
        if mod == "repro.kernels.common":
            for alias in st.names:
                if alias.name == "ACC_PRECISION":
                    env.set(alias.asname or alias.name, "highest")
                    continue
                if alias.name != "ACC_DTYPE":
                    raise Unsupported(f"unknown common import {alias.name}")
                env.set(alias.asname or alias.name, DtypeMarker("f32"))
            return
        raise Unsupported(f"import from {mod!r} inside a kernel function")

    def assign(self, tgt, value, env):
        if isinstance(tgt, ast.Name):
            env.set(tgt.id, value)
        elif isinstance(tgt, (ast.Tuple, ast.List)):
            items = list(self.iterate(value))
            if len(items) != len(tgt.elts):
                raise Unsupported(
                    f"unpacking {len(items)} values into "
                    f"{len(tgt.elts)} targets")
            for t, v in zip(tgt.elts, items):
                self.assign(t, v, env)
        elif isinstance(tgt, ast.Subscript):
            ref = self.eval(tgt.value, env)
            if not isinstance(ref, Ref):
                raise Unsupported("subscript store to non-ref")
            self.ref_store(ref, self.eval_index(tgt.slice, env), value)
        else:
            raise Unsupported(f"assignment target {type(tgt).__name__}")

    def iterate(self, value):
        if isinstance(value, (list, tuple, range)):
            return list(value)
        if isinstance(value, (zip, enumerate)):
            return list(value)
        raise Unsupported(f"iteration over {type(value).__name__}")

    def truth(self, value):
        if value is None or isinstance(value, (bool, int, float, str,
                                               tuple, list)):
            return bool(value)
        raise Unsupported(
            f"truthiness of abstract value {type(value).__name__}")

    # -- expressions -------------------------------------------------------

    def eval(self, node, env):
        self.line = getattr(node, "lineno", self.line)
        if isinstance(node, ast.Constant):
            return node.value
        if isinstance(node, ast.Name):
            return env.get(node.id)
        if isinstance(node, ast.Attribute):
            return self.attribute(self.eval(node.value, env), node.attr)
        if isinstance(node, ast.Tuple):
            return tuple(self.eval(e, env) for e in node.elts)
        if isinstance(node, ast.List):
            return [self.eval(e, env) for e in node.elts]
        if isinstance(node, ast.BinOp):
            return self.binop(node.op, self.eval(node.left, env),
                              self.eval(node.right, env))
        if isinstance(node, ast.UnaryOp):
            return self.unaryop(node.op, self.eval(node.operand, env))
        if isinstance(node, ast.BoolOp):
            result = None
            for v in node.values:
                result = self.eval(v, env)
                t = self.truth(result)
                if isinstance(node.op, ast.And) and not t:
                    return result
                if isinstance(node.op, ast.Or) and t:
                    return result
            return result
        if isinstance(node, ast.Compare):
            return self.compare(node, env)
        if isinstance(node, ast.IfExp):
            branch = (node.body if self.truth(self.eval(node.test, env))
                      else node.orelse)
            return self.eval(branch, env)
        if isinstance(node, ast.Call):
            fn = self.eval(node.func, env)
            args = []
            for a in node.args:
                if isinstance(a, ast.Starred):
                    args.extend(self.iterate(self.eval(a.value, env)))
                else:
                    args.append(self.eval(a, env))
            kwargs = {}
            for kw in node.keywords:
                if kw.arg is None:
                    raise Unsupported("** call expansion")
                kwargs[kw.arg] = self.eval(kw.value, env)
            return self.call(fn, args, kwargs)
        if isinstance(node, ast.Subscript):
            obj = self.eval(node.value, env)
            idx = self.eval_index(node.slice, env)
            return self.subscript(obj, idx)
        if isinstance(node, ast.Lambda):
            return Closure(node, env, "<lambda>", None)
        if isinstance(node, (ast.GeneratorExp, ast.ListComp)):
            return self.comprehension(node, env)
        if isinstance(node, ast.JoinedStr):
            parts = []
            for v in node.values:
                if isinstance(v, ast.Constant):
                    parts.append(str(v.value))
                elif isinstance(v, ast.FormattedValue):
                    parts.append(str(self.eval(v.value, env)))
                else:
                    raise Unsupported("f-string component")
            return "".join(parts)
        raise Unsupported(f"expression {type(node).__name__}")

    def comprehension(self, node, env):
        if len(node.generators) != 1:
            raise Unsupported("nested comprehension")
        gen = node.generators[0]
        if gen.is_async:
            raise Unsupported("async comprehension")
        out = []
        inner = Env(parent=env)
        for item in self.iterate(self.eval(gen.iter, env)):
            self.assign(gen.target, item, inner)
            if all(self.truth(self.eval(c, inner)) for c in gen.ifs):
                out.append(self.eval(node.elt, inner))
        return out

    def eval_index(self, node, env):
        """Evaluate a subscript index; slices stay as python slices."""
        if isinstance(node, ast.Tuple):
            return tuple(self.eval_index(e, env) for e in node.elts)
        if isinstance(node, ast.Slice):
            return slice(
                None if node.lower is None else self.eval(node.lower, env),
                None if node.upper is None else self.eval(node.upper, env),
                None if node.step is None else self.eval(node.step, env))
        return self.eval(node, env)

    def attribute(self, obj, attr):
        if isinstance(obj, ModuleHandle):
            return self.module_attr(obj.name, attr)
        if isinstance(obj, (Ref, AArray)):
            if attr == "shape":
                return obj.shape
            if attr == "ndim":
                return len(obj.shape)
            if attr == "dtype":
                is_out = isinstance(obj, Ref) and obj.is_out
                return DtypeOf(obj.dt, is_out)
            if attr in ("astype", "reshape") and isinstance(obj, AArray):
                return BoundMethod(obj, attr)
            raise Unsupported(f"attribute .{attr} on array/ref")
        if isinstance(obj, list) and attr == "append":
            return BoundMethod(obj, attr)
        raise Unsupported(f"attribute .{attr} on {type(obj).__name__}")

    _JNP_DTYPES = {"float32": "f32", "float64": "f64", "int32": "i32",
                   "bfloat16": "io"}

    def module_attr(self, mod, attr):
        if mod == "jnp":
            if attr in self._JNP_DTYPES:
                return DtypeMarker(self._JNP_DTYPES[attr])
            if attr == "inf":
                return float("inf")
            return _ModFn(("jnp", attr))
        if mod == "jax":
            if attr == "lax":
                return ModuleHandle("jax.lax")
            if attr == "ShapeDtypeStruct":
                return _ModFn(("jax", "ShapeDtypeStruct"))
            raise Unsupported(f"jax.{attr}")
        if mod == "jax.lax":
            return _ModFn(("lax", attr))
        if mod == "pl":
            return _ModFn(("pl", attr))
        if mod == "pltpu":
            return _ModFn(("pltpu", attr))
        if mod == "functools":
            if attr == "partial":
                return _ModFn(("functools", "partial"))
            raise Unsupported(f"functools.{attr}")
        raise Unsupported(f"module {mod}.{attr}")

    # -- operators ---------------------------------------------------------

    def binop(self, op, a, b):
        if isinstance(op, ast.BitAnd):
            if isinstance(a, RowPred) and isinstance(b, RowPred):
                return a & b
            if isinstance(a, int) and isinstance(b, int):
                return a & b
            raise Unsupported("& on non-predicates")
        if isinstance(a, AArray) or isinstance(b, AArray):
            if isinstance(op, (ast.Add, ast.Sub, ast.Mult, ast.Div,
                               ast.Pow)):
                return _arr_binop(a, b, self)
            raise Unsupported(f"array op {type(op).__name__}")
        if isinstance(a, IotaV) or isinstance(b, IotaV):
            iota = a if isinstance(a, IotaV) else b
            other = b if isinstance(a, IotaV) else a
            if not isinstance(op, ast.Add):
                raise Unsupported("iota only supports addition")
            return RowExpr(Aff.lift(other), iota)
        if isinstance(a, RowExpr) or isinstance(b, RowExpr):
            re = a if isinstance(a, RowExpr) else b
            other = b if isinstance(a, RowExpr) else a
            if not isinstance(op, ast.Add):
                raise Unsupported("row expr only supports addition")
            return RowExpr(re.aff + Aff.lift(other), re.iota)
        if isinstance(a, Aff) or isinstance(b, Aff):
            a, b = Aff.lift(a), Aff.lift(b)
            if isinstance(op, ast.Add):
                r = a + b
            elif isinstance(op, ast.Sub):
                r = a - b
            elif isinstance(op, ast.Mult):
                r = a * b
            else:
                raise Unsupported(
                    f"affine op {type(op).__name__} on grid indices")
            ri = r.as_int()
            return r if ri is None else ri
        table = {ast.Add: lambda x, y: x + y,
                 ast.Sub: lambda x, y: x - y,
                 ast.Mult: lambda x, y: x * y,
                 ast.Div: lambda x, y: x / y,
                 ast.FloorDiv: lambda x, y: x // y,
                 ast.Mod: lambda x, y: x % y,
                 ast.Pow: lambda x, y: x ** y}
        fn = table.get(type(op))
        if fn is None:
            raise Unsupported(f"operator {type(op).__name__}")
        return fn(a, b)

    def unaryop(self, op, v):
        if isinstance(op, ast.USub):
            if isinstance(v, (int, float)):
                return -v
            if isinstance(v, Aff):
                return v * -1
            if isinstance(v, AArray):
                return _arr_binop(v, v, self)
            raise Unsupported("unary minus on abstract value")
        if isinstance(op, ast.Not):
            return not self.truth(v)
        raise Unsupported(f"unary {type(op).__name__}")

    def compare(self, node, env):
        left = self.eval(node.left, env)
        result = True
        for op, comp in zip(node.ops, node.comparators):
            right = self.eval(comp, env)
            r = self.compare_one(left, op, right)
            if isinstance(r, (Pred, RowPred)):
                if len(node.ops) > 1:
                    raise Unsupported("chained abstract comparison")
                return r
            if not r:
                return False
            left = right
        return result

    def compare_one(self, left, op, right):
        if isinstance(op, (ast.Is, ast.IsNot)):
            if left is not None and right is not None:
                raise Unsupported("identity comparison of non-None values")
            same = left is right
            return same if isinstance(op, ast.Is) else not same
        if isinstance(left, RowExpr) or isinstance(right, RowExpr):
            re = left if isinstance(left, RowExpr) else right
            other = right if isinstance(left, RowExpr) else left
            flip = re is right
            if isinstance(op, ast.GtE) and not flip:
                return re.compare("ge", other)
            if isinstance(op, ast.Lt) and not flip:
                return re.compare("lt", other)
            raise Unsupported("row comparison form")
        if isinstance(left, Aff) or isinstance(right, Aff):
            diff = Aff.lift(left) - Aff.lift(right)
            di = diff.as_int()
            if di is not None:
                table = {ast.Eq: di == 0, ast.NotEq: di != 0,
                         ast.Lt: di < 0, ast.LtE: di <= 0,
                         ast.Gt: di > 0, ast.GtE: di >= 0}
                if type(op) in table:
                    return table[type(op)]
                raise Unsupported("comparison on grid indices")
            if isinstance(op, ast.Eq) and isinstance(left, Aff) \
                    and isinstance(right, int):
                return Pred(left, right)
            raise Unsupported("abstract comparison on grid indices")
        table = {ast.Eq: lambda x, y: x == y,
                 ast.NotEq: lambda x, y: x != y,
                 ast.Lt: lambda x, y: x < y,
                 ast.LtE: lambda x, y: x <= y,
                 ast.Gt: lambda x, y: x > y,
                 ast.GtE: lambda x, y: x >= y,
                 ast.In: lambda x, y: x in y,
                 ast.NotIn: lambda x, y: x not in y}
        fn = table.get(type(op))
        if fn is None:
            raise Unsupported(f"comparison {type(op).__name__}")
        return fn(left, right)

    # -- subscripts, loads, stores ----------------------------------------

    def subscript(self, obj, idx):
        if isinstance(obj, (tuple, list, str)):
            if isinstance(idx, (int, slice)):
                return obj[idx]
            raise Unsupported(f"sequence index {idx!r}")
        if isinstance(obj, Ref):
            return self.ref_load(obj, idx)
        if isinstance(obj, AArray):
            shape = self.index_shape(obj.shape, idx, f"<{obj.dt} array>")
            return obj.like(shape=shape, mask=None)
        raise Unsupported(f"subscript on {type(obj).__name__}")

    def _axis_bounds(self, start, size, dim, name, axis):
        """K101 check: [start, start+size) must sit inside [0, dim)."""
        aff = Aff.lift(start)
        lo, hi = aff.bounds(self.sym_ranges)
        if lo < 0 or hi + size > dim:
            self.finding(
                "K101",
                f"{name} axis {axis}: rows [{lo}, {hi + size}) can leave "
                f"the block/operand extent [0, {dim})")

    def index_shape(self, shape, idx, name):
        """Result shape of an index expression, bounds-checked (K101)."""
        if not isinstance(idx, tuple):
            idx = (idx,)
        if any(i is Ellipsis for i in idx):
            if len(idx) == 1:
                return tuple(shape)
            raise Unsupported("partial Ellipsis index")
        out = []
        for axis, dim in enumerate(shape):
            if axis >= len(idx):
                out.append(dim)
                continue
            i = idx[axis]
            if isinstance(i, (int, Aff)):
                if isinstance(i, int) and i < 0:
                    i += dim
                self._axis_bounds(i, 1, dim, name, axis)
                continue  # squeezed
            if isinstance(i, DS):
                self._axis_bounds(i.start, (i.size - 1) * i.stride + 1, dim,
                                  name, axis)
                out.append(i.size)
                continue
            if isinstance(i, slice):
                if i.step not in (None, 1):
                    raise Unsupported("strided slice subscript")
                lo = 0 if i.start is None else i.start
                hi = dim if i.stop is None else i.stop
                if lo < 0:
                    lo += dim
                if hi < 0:
                    hi += dim
                hi = min(hi, dim)
                if lo < 0 or hi < lo:
                    self.finding("K101",
                                 f"{name} axis {axis}: slice [{lo}, {hi}) "
                                 f"outside [0, {dim})")
                    lo, hi = 0, dim
                out.append(hi - lo)
                continue
            if i is None:
                out.append(1)
                continue
            raise Unsupported(f"index component {i!r}")
        return tuple(out)

    def ref_load(self, ref, idx):
        shape = self.index_shape(ref.shape, idx, ref.name)
        if ref.is_scratch:
            self.scratch_events.append(
                ("load", ref, None, tuple(self.guards), self.line))
        return AArray(shape, ref.dt, from_out=ref.is_out)

    def ref_store(self, ref, idx, value):
        if ref.is_scratch:
            # scratch carry stores feed the K106 discipline proof, not
            # the K102 output lattice
            if not isinstance(value, AArray):
                raise Unsupported(
                    f"scratch store of non-array into {ref.name}")
            full = idx is Ellipsis or (
                isinstance(idx, tuple) and len(idx) == 1
                and idx[0] is Ellipsis)
            if not full:
                self.finding("K106", f"partial scratch store into "
                             f"{ref.name} — the carry must replace the "
                             "whole scratch block")
            elif value.shape != ref.shape:
                self.finding("K106", f"scratch store shape {value.shape} "
                             f"does not match {ref.name}'s block "
                             f"{ref.shape}")
            self.scratch_events.append(
                ("store", ref, value, tuple(self.guards), self.line))
            return
        if not ref.is_out:
            raise Unsupported(f"store into input ref {ref.name}")
        if not isinstance(value, AArray):
            value = AArray(ref.shape, "weak")
        full = idx is Ellipsis or (isinstance(idx, tuple) and len(idx) == 1
                                   and idx[0] is Ellipsis)
        if not full:
            self.index_shape(ref.shape, idx, ref.name)
        self.stores.append(Store(self.guards, value, full, self.line))

    # -- jnp / lax / pl dispatch ------------------------------------------

    def call_modfn(self, fn, args, kwargs):
        path = ".".join(fn.path)
        if path == "functools.partial":
            return PartialV(args[0], args[1:], kwargs)
        if path == "jax.ShapeDtypeStruct":
            return ShapeDtypeV(tuple(args[0]), _tag_of(args[1]))
        if path.startswith("jnp."):
            return self.call_jnp(fn.path[1], args, kwargs)
        if path.startswith("lax."):
            return self.call_lax(fn.path[1], args, kwargs)
        if path == "pl.pallas_call":
            kernel = args[0]
            cp = kwargs.get("compiler_params")
            sem = cp.dimension_semantics if isinstance(
                cp, CompilerParamsV) else None
            out_specs = kwargs["out_specs"]
            if isinstance(out_specs, (tuple, list)):
                raise Unsupported("multiple output specs")
            scratch = kwargs.get("scratch_shapes")
            if scratch is not None and not all(
                    isinstance(s, ShapeDtypeV) for s in scratch):
                raise Unsupported("non-VMEM scratch_shapes entry")
            return PallasV(kernel, tuple(kwargs["grid"]),
                           list(kwargs["in_specs"]), out_specs,
                           kwargs["out_shape"], sem, scratch)
        if path == "pl.BlockSpec":
            return BlockSpecV(tuple(args[0]), args[1])
        if path == "pl.Element":
            return ElementV(args[0])
        if path == "pl.program_id":
            axis = args[0]
            if axis not in self.sym_ranges:
                raise Unsupported(f"program_id({axis}) outside a kernel "
                                  "body or beyond the grid rank")
            return Aff({axis: 1}, 0)
        if path == "pl.when":
            pred = args[0]
            if not isinstance(pred, Pred):
                raise Unsupported("pl.when on a non-affine predicate")
            return PlWhenV(pred)
        if path == "pl.ds":
            stride = kwargs.get("stride", args[2] if len(args) > 2 else 1)
            if not isinstance(stride, int) or stride < 1:
                raise Unsupported(f"pl.ds stride {stride!r}")
            return DS(args[0], args[1], stride)
        if path == "pltpu.CompilerParams":
            return CompilerParamsV(tuple(kwargs["dimension_semantics"]))
        if path == "pltpu.VMEM":
            return ShapeDtypeV(tuple(args[0]), _tag_of(args[1]))
        raise Unsupported(f"call to {path}")

    def call_jnp(self, name, args, kwargs):
        if name == "pad":
            arr, widths = args[0], args[1]
            if not isinstance(arr, AArray):
                raise Unsupported("jnp.pad of non-array")
            if widths and not isinstance(widths[0], (tuple, list)):
                widths = [tuple(widths)]
            widths = [tuple(w) for w in widths]
            if len(widths) != len(arr.shape):
                raise Unsupported("jnp.pad width rank mismatch")
            shape = tuple(d + lo + hi
                          for d, (lo, hi) in zip(arr.shape, widths))
            keep_mask = arr.mask is not None and widths[0] == (0, 0)
            return arr.like(shape=shape,
                            mask=arr.mask if keep_mask else None)
        if name in ("zeros", "full"):
            shape = tuple(args[0]) if isinstance(
                args[0], (tuple, list)) else (args[0],)
            dt_arg = args[-1] if len(args) > (1 if name == "zeros" else 2) \
                else kwargs.get("dtype")
            tag = _tag_of(dt_arg) if dt_arg is not None else "f32"
            return AArray(shape, tag)
        if name == "zeros_like":
            src = args[0]
            if isinstance(src, (Ref, AArray)):
                return AArray(src.shape, src.dt)
            raise Unsupported("zeros_like of non-array")
        if name == "maximum":
            return _arr_binop(args[0], args[1], self)
        if name == "concatenate":
            seq = [a for a in self.iterate(args[0])]
            axis = kwargs.get("axis", args[1] if len(args) > 1 else 0)
            if not seq or not all(isinstance(a, AArray) for a in seq):
                raise Unsupported("concatenate of non-arrays")
            nd = seq[0].ndim
            axis = axis % nd
            base = list(seq[0].shape)
            total = 0
            joined = seq[0]
            for a in seq:
                if len(a.shape) != nd or any(
                        a.shape[i] != base[i] for i in range(nd)
                        if i != axis):
                    self.finding("K100", "concatenate shape mismatch "
                                         f"{[s.shape for s in seq]}")
                total += a.shape[axis]
                # join dtype/taint metadata only: pieces may differ in
                # width along the concatenation axis
                joined = _arr_binop(joined, a.like(shape=joined.shape), self)
            base[axis] = total
            return joined.like(shape=tuple(base), mask=None)
        if name == "dot":
            a, b = args[0], args[1]
            if not (isinstance(a, AArray) and isinstance(b, AArray)):
                raise Unsupported("dot of non-arrays")
            if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
                self.finding("K100", f"dot shape mismatch {a.shape} x "
                                     f"{b.shape}")
            pet = kwargs.get("preferred_element_type")
            if pet is None or _tag_of(pet) != "f32":
                self.finding("K103", "jnp.dot without "
                             "preferred_element_type=float32 — the MXU "
                             "accumulator dtype is unpinned")
            for op in (a, b):
                if op.dt not in ("f32", "weak"):
                    self.finding("K103", f"jnp.dot operand has dtype "
                                 f"{op.dt!r} — operands must be upcast "
                                 "to fp32 before accumulation")
            # join metadata directly — contraction shapes don't broadcast
            return AArray((a.shape[0], b.shape[1] if b.ndim == 2 else 1),
                          "f32", a.downcasts + b.downcasts,
                          a.tainted or b.tainted or a.downcasts > 0
                          or b.downcasts > 0,
                          a.from_out or b.from_out)
        if name == "where":
            cond, x, y = args[0], args[1], args[2]
            if isinstance(cond, RowRange):
                zero = (isinstance(y, (int, float)) and y == 0)
                if isinstance(x, AArray) and zero:
                    return x.like(mask=cond.key())
                raise Unsupported("row-masked where with nonzero filler")
            return _arr_binop(x if isinstance(x, AArray) else y,
                              y if isinstance(y, AArray) else x, self)
        if name in ("exp", "tanh"):
            v = args[0]
            if isinstance(v, AArray):
                return _arr_binop(v, v, self)
            raise Unsupported(f"jnp.{name} of non-array")
        raise Unsupported(f"jnp.{name}")

    def call_lax(self, name, args, kwargs):
        if name == "slice":
            x, starts, limits = args[0], tuple(args[1]), tuple(args[2])
            strides = tuple(args[3]) if len(args) > 3 else \
                kwargs.get("strides")
            if not isinstance(x, AArray):
                raise Unsupported("lax.slice of non-array")
            if strides is None:
                strides = (1,) * x.ndim
            shape = []
            for axis, (s, l, st, dim) in enumerate(
                    zip(starts, limits, strides, x.shape)):
                if not all(isinstance(v, int) for v in (s, l, st)):
                    raise Unsupported("non-concrete lax.slice bound")
                if s < 0 or l > dim or s >= l:
                    self.finding(
                        "K101",
                        f"lax.slice axis {axis}: [{s}, {l}) outside the "
                        f"staged array extent [0, {dim})")
                    s, l = 0, dim
                shape.append((l - s + st - 1) // st)
            return x.like(shape=tuple(shape), mask=None)
        if name == "slice_in_dim":
            x, start, stop = args[0], args[1], args[2]
            axis = kwargs.get("axis", args[3] if len(args) > 3 else 0)
            if not isinstance(x, AArray):
                raise Unsupported("slice_in_dim of non-array")
            dim = x.shape[axis]
            if start < 0 or stop > dim or start >= stop:
                self.finding(
                    "K101",
                    f"lax.slice_in_dim axis {axis}: [{start}, {stop}) "
                    f"outside [0, {dim})")
                start, stop = 0, dim
            shape = list(x.shape)
            shape[axis] = stop - start
            out = x.like(shape=tuple(shape), mask=None)
            if axis == 0:
                # contiguous row-slice provenance for the K106 proof
                out.row_slice = (start, stop, dim)
            return out
        if name == "broadcasted_iota":
            shape = tuple(args[1])
            return IotaV(shape, args[2])
        raise Unsupported(f"lax.{name}")

    # -- dispatch analysis -------------------------------------------------

    def eval_index_map(self, index_map, n_syms):
        """Run a BlockSpec index map once, with grid indices as symbols."""
        syms = [Aff({s: 1}, 0) for s in range(n_syms)]
        out = self.call(index_map, syms, {})
        if not isinstance(out, tuple):
            out = (out,)
        return out

    def _squeeze(self, block_shape):
        return tuple(d for d in block_shape if d is not None)

    def analyze_dispatch(self, pv, operands):
        """The heart of Phase B: prove one ``pallas_call`` dispatch."""
        self.stores = []
        self.scratch_events = []
        self.band_conv_masks = []
        self.guards = []
        grid = pv.grid
        if not all(isinstance(g, int) and g > 0 for g in grid):
            raise Unsupported(f"non-concrete grid {grid!r}")
        self.sym_ranges = {s: g for s, g in enumerate(grid)}
        if len(pv.in_specs) != len(operands):
            raise Unsupported("in_specs / operand count mismatch")

        # K101 — spec-level: every block an index map can select must sit
        # inside the (padded) operand it loads from.
        for spec_i, (spec, op) in enumerate(zip(pv.in_specs, operands)):
            if not isinstance(op, AArray):
                raise Unsupported(f"operand {spec_i} is not an array")
            idx = self.eval_index_map(spec.index_map, len(grid))
            if len(idx) != len(spec.block_shape) or \
                    len(idx) != len(op.shape):
                self.finding("K100", f"in_spec {spec_i}: index map rank "
                             f"{len(idx)} vs block rank "
                             f"{len(spec.block_shape)} vs operand rank "
                             f"{len(op.shape)}")
                continue
            for axis, (bd, comp) in enumerate(zip(spec.block_shape, idx)):
                bsize = 1 if bd is None else bd
                aff = Aff.lift(comp)
                start = aff if axis in spec.element_axes else aff * bsize
                lo, hi = start.bounds(self.sym_ranges)
                if lo < 0 or hi + bsize > op.shape[axis]:
                    self.finding(
                        "K101",
                        f"in_spec {spec_i} axis {axis}: blocks span "
                        f"[{lo}, {hi + bsize}) but the operand extent is "
                        f"[0, {op.shape[axis]})")

        # K102 — out-spec: the store lattice must tile the output exactly.
        out_sds = pv.out_shape
        if not isinstance(out_sds, ShapeDtypeV):
            raise Unsupported("out_shape is not a ShapeDtypeStruct")
        ospec = pv.out_specs
        if ospec.element_axes:
            raise Unsupported("element-offset output spec")
        oidx = self.eval_index_map(ospec.index_map, len(grid))
        used_syms = set()
        if len(oidx) != len(ospec.block_shape) or \
                len(oidx) != len(out_sds.shape):
            self.finding("K100", "out_spec rank mismatch")
            return AArray(out_sds.shape, out_sds.dt)
        for axis, (bd, comp) in enumerate(zip(ospec.block_shape, oidx)):
            bsize = 1 if bd is None else bd
            aff = Aff.lift(comp)
            dim = out_sds.shape[axis]
            if aff.coeffs:
                if len(aff.coeffs) > 1 or aff.const != 0:
                    self.finding("K102", f"out axis {axis}: index map is "
                                 f"not a single grid index ({aff!r})")
                    continue
                (s, coef), = aff.coeffs.items()
                if coef != 1:
                    self.finding("K102", f"out axis {axis}: strided index "
                                 f"map ({aff!r}) leaves gaps or overlaps")
                    continue
                if s in used_syms:
                    self.finding("K102", f"out axis {axis}: grid index "
                                 f"g{s} reused across output axes")
                used_syms.add(s)
                if grid[s] * bsize != dim:
                    self.finding(
                        "K102",
                        f"out axis {axis}: {grid[s]} blocks x {bsize} "
                        f"rows cover [0, {grid[s] * bsize}) but the "
                        f"output extent is [0, {dim})")
            else:
                if aff.const != 0:
                    self.finding("K102", f"out axis {axis}: constant "
                                 f"block index {aff.const} != 0")
                if bsize != dim:
                    self.finding(
                        "K102",
                        f"out axis {axis}: single block of {bsize} rows "
                        f"covers [0, {bsize}) of [0, {dim})")
        acc_syms = [s for s in range(len(grid)) if s not in used_syms
                    and grid[s] > 1]

        # interpret the kernel body with the grid indices symbolic
        kernel, preset_args, preset_kw = pv.kernel, [], {}
        while isinstance(kernel, PartialV):
            preset_args = list(kernel.args) + preset_args
            preset_kw = {**kernel.kwargs, **preset_kw}
            kernel = kernel.fn
        if not isinstance(kernel, Closure):
            raise Unsupported("kernel is not an interpretable function")
        refs = []
        for spec_i, (spec, op) in enumerate(zip(pv.in_specs, operands)):
            refs.append(Ref(f"in_ref{spec_i}",
                            self._squeeze(spec.block_shape), op.dt, False))
        o_ref = Ref("o_ref", self._squeeze(ospec.block_shape),
                    out_sds.dt, True)
        scratch_refs = [Ref(f"scratch{i}", sv.shape, sv.dt, False, True)
                        for i, sv in enumerate(pv.scratch_shapes)]
        self._name_refs(kernel, preset_args, refs, o_ref, scratch_refs)
        self.call(PartialV(kernel, preset_args, preset_kw),
                  refs + [o_ref] + scratch_refs, {})

        self._check_store_discipline(o_ref, grid, acc_syms,
                                     pv.dimension_semantics)
        if scratch_refs:
            self._check_carry_discipline(scratch_refs, grid,
                                         pv.dimension_semantics)
        stages = preset_kw.get("stages")
        if stages is not None:
            self._check_chain_masks(stages, grid)
        return AArray(out_sds.shape, out_sds.dt)

    def _name_refs(self, kernel, preset_args, refs, o_ref, scratch_refs):
        """Give refs their kernel-parameter names for findings."""
        params = [p.arg for p in kernel.node.args.args]
        params = params[len(preset_args):]
        bound = refs + [o_ref] + scratch_refs
        for name, ref in zip(params, bound):
            ref.name = name
        if len(bound) > len(params) and not scratch_refs:
            # *refs vararg: last one is o_ref
            for i, ref in enumerate(bound[len(params):-1]):
                ref.name = f"refs[{i}]"

    def _normalize_guards(self, store, grid):
        """Guards as {sym: value}; None if the store can never execute."""
        gv = {}
        for pred in store.guards:
            s, v = pred.sym_eq()
            if v < 0 or v >= grid[s]:
                return None  # dead store
            if grid[s] == 1:
                continue  # trivially true
            if s in gv and gv[s] != v:
                return None
            gv[s] = v
        return gv

    def _check_store_discipline(self, o_ref, grid, acc_syms, dim_sem):
        live = []
        for st in self.stores:
            gv = self._normalize_guards(st, grid)
            if gv is not None:
                live.append((st, gv))
        if not live:
            self.finding("K102", "kernel body never stores to the output "
                         "ref — every element stays uninitialized")
            return
        first_st, first_gv = live[0]
        if first_st.value.from_out:
            self.finding("K102", "first output store is a read-modify-"
                         "write — it reads uninitialized VMEM")
        for st, gv in live:
            if not st.full_block:
                self.finding("K102", f"partial output store at line "
                             f"{st.line} — stores must cover the whole "
                             "block")
            for s in gv:
                if s not in acc_syms:
                    self.finding("K102", f"store at line {st.line} is "
                                 f"guarded on covered grid axis g{s} — "
                                 "some blocks are never written")
            if st.value.from_out:
                continue  # RMW accumulation step
            for s in acc_syms:
                if gv.get(s) != 0:
                    self.finding(
                        "K102",
                        f"overwrite store at line {st.line} re-executes "
                        f"for every value of accumulation axis g{s} — "
                        "earlier partial sums are discarded")
        for s in acc_syms:
            sem = (dim_sem[s] if dim_sem is not None and s < len(dim_sem)
                   else None)
            if sem != "arbitrary":
                self.finding(
                    "K102",
                    f"accumulation axis g{s} has dimension_semantics "
                    f"{sem!r} — revisiting an output block requires "
                    "'arbitrary'")
            if not any(gv.get(s) == 0 and not st.value.from_out
                       for st, gv in live):
                self.finding(
                    "K102",
                    f"no initializing overwrite store guarded to "
                    f"g{s} == 0 — the first visit accumulates into "
                    "uninitialized VMEM")
        # K103: per-store precision flow
        for st, _ in live:
            v = st.value
            if o_ref.dt == "io":
                if v.dt != "io" or v.downcasts != 1:
                    self.finding(
                        "K103",
                        f"store at line {st.line}: value has dtype tag "
                        f"{v.dt!r} after {v.downcasts} downcast(s) — "
                        "expected exactly one astype(o_ref.dtype) at "
                        "the store")
                elif v.tainted:
                    self.finding(
                        "K103",
                        f"store at line {st.line}: arithmetic happened "
                        "after the downcast — the cast must be the "
                        "final operation")
            else:  # fp32 output: no downcast at all
                if v.dt not in ("f32", "weak") or v.downcasts != 0:
                    self.finding(
                        "K103",
                        f"store at line {st.line}: value dtype tag "
                        f"{v.dt!r} with {v.downcasts} downcast(s) — "
                        "fp32 outputs must be stored undowncast")

    def _check_carry_discipline(self, scratch_refs, grid, dim_sem):
        """K106: a VMEM scratch carry must be consumed before overwrite,
        and the overwrite must keep the TAIL rows of the fresh band.

        A scratch ref whose first access in the body is a (full) store is
        a staging buffer — the fused pool epilogue's conv band — and
        carries nothing between steps; the discipline applies to the
        refs read before they are written.  The carried axis is the
        innermost grid axis (scratch persists across its steps), so it
        needs 'arbitrary' semantics: a parallel or reordered axis would
        let a step read a carry its predecessor has not produced yet.
        Each step must store exactly the last ``scratch_rows`` rows of
        the fresh band (a provable tail row-slice): a head slice or
        recomputed value would hand the next band stale rows."""
        carried = []
        for ref in scratch_refs:
            events = [e for e in self.scratch_events if e[1] is ref]
            if not events:
                self.finding("K106", f"scratch ref {ref.name} is never "
                             "accessed — dead carry allocation")
            elif events[0][0] == "load":
                carried.append((ref, events))
        if not carried:
            return
        ca = len(grid) - 1
        sem = (dim_sem[ca] if dim_sem is not None and ca < len(dim_sem)
               else None)
        if sem != "arbitrary":
            self.finding(
                "K106",
                f"carried grid axis g{ca} has dimension_semantics "
                f"{sem!r} — scratch carry across steps requires "
                "'arbitrary'")
        for ref, events in carried:
            stores = [e for e in events if e[0] == "store"]
            if not stores:
                self.finding(
                    "K106",
                    f"scratch ref {ref.name} is read but never "
                    "refreshed — every step after the first consumes "
                    "the same stale carry")
            for _, _, value, guards, line in stores:
                if guards:
                    self.finding(
                        "K106",
                        f"scratch store at line {line} is guarded — a "
                        "skipped step would hand the next band a stale "
                        "carry")
                rs = value.row_slice
                if rs is None:
                    self.finding(
                        "K106",
                        f"scratch store at line {line} is not a "
                        "provable contiguous row-slice of the fresh "
                        "band — cannot prove the carry holds the "
                        "band's boundary rows")
                    continue
                start, stop, dim = rs
                rows = ref.shape[0]
                if stop != dim or stop - start != rows:
                    self.finding(
                        "K106",
                        f"scratch store at line {line} keeps rows "
                        f"[{start}, {stop}) of a {dim}-row band — the "
                        f"carry must be the TAIL {rows} rows "
                        f"[{dim - rows}, {dim}); the next band step "
                        "would consume stale rows")

    def _check_chain_masks(self, stages, grid):
        """K104: a stage band with possibly-garbage rows must be masked."""
        n_tiles = grid[1] if len(grid) > 1 else 1
        for si, mask in enumerate(self.band_conv_masks):
            if si == 0:
                continue  # stage 0 consumes the host-padded input band
            prev = stages[si - 1]
            m_prev, oh_valid, a, b0 = prev[5], prev[8], prev[9], prev[10]
            garbage = b0 < 0 or a * (n_tiles - 1) + b0 + m_prev > oh_valid
            if not garbage:
                continue
            expected = (((1, a),), b0, 0, oh_valid)
            if mask is None:
                self.finding(
                    "K104",
                    f"stage {si} consumes stage {si - 1}'s band without "
                    "a row mask, but that band provably contains rows "
                    f"outside [0, {oh_valid}) — conv-of-pad garbage "
                    "flows into the next stage")
            elif mask != expected:
                self.finding(
                    "K104",
                    f"stage {si}: row mask {mask!r} does not match the "
                    f"required zero range (rows {a}*t + {b0} clipped to "
                    f"[0, {oh_valid}))")


# ---------------------------------------------------------------------------
# module loading + Phase-A interception
# ---------------------------------------------------------------------------

#: module key -> kernel source path relative to ``src/repro/kernels``
KERNEL_SOURCES = {"conv2d": "conv2d/kernels.py",
                  "pool2d": "pool2d/kernels.py",
                  "matmul": "matmul_fused/kernel.py"}

_PALLAS_ALIASES = {"pallas": "pl", "tpu": "pltpu"}


def _i_plan_oh_tiles(xp, oh, kh, kw, sy, oh_block, ow, oc_block,
                     im2col=True):
    """Phase-A answer for the un-fused band planner (pads abstractly)."""
    n, hp, wp, c = xp.shape
    ohb = _a_resolve_oh(oh, ow, wp, c, kh, kw, sy, oc_block, oh_block,
                        im2col=im2col)
    n_tiles = _ceil_div(oh, ohb)
    band = _a_band(ohb, kh, sy)
    hp_need = (n_tiles - 1) * ohb * sy + band
    if hp_need > hp:
        xp = xp.like(shape=(n, hp_need, wp, c))
    return xp, ohb, n_tiles, band


def _i_plan_pool_tiles(xp, oh, ow, kh, kw, sy, oh_block, oc_block, pool,
                       im2col=True, oc_halo=0):
    """Phase-A answer for the fused conv+pool band planner."""
    pkh, pkw, psy, psx = pool
    n, hp, wp, c = xp.shape
    ph, pw = (oh - pkh) // psy + 1, (ow - pkw) // psx + 1
    if ph < 1 or pw < 1:
        raise KernelRaise(
            f"pool window ({pkh},{pkw}) larger than conv output "
            f"({oh},{ow})")
    phb, n_tiles = _a_resolve_ph(ph, oh, ow, wp, c, kh, kw, sy, oc_block,
                                 pool, oh_block, im2col=im2col,
                                 oc_halo=oc_halo)
    cband = _a_band(phb, pkh, psy)
    band = _a_band(cband, kh, sy)
    row_step = phb * psy * sy
    hp_need = (n_tiles - 1) * row_step + band
    if hp_need > hp:
        xp = xp.like(shape=(n, hp_need, wp, c))
    return xp, phb, n_tiles, band, cband, ph, pw, row_step


def _i_pool_out_size(size, k, stride):
    return (size - k) // stride + 1


#: trusted resolver names, answered by Phase A instead of interpretation
_INTERCEPTS = {
    "conv2d": {
        "_out_size": _a_out,
        "_band_rows": _a_band,
        "band_intervals": _a_intervals,
        "auto_oh_block": _a_auto_oh,
        "resolve_oh_block": _a_resolve_oh,
        "auto_ph_block": _a_auto_ph,
        "resolve_ph_block": _a_resolve_ph,
        "resolve_lrn_ocb": _a_resolve_lrn_ocb,
        "resolve_pool_carry": _a_resolve_pool_carry,
        "_equalize_bands": _a_equalize,
        "_plan_oh_tiles": _i_plan_oh_tiles,
        "_plan_pool_tiles": _i_plan_pool_tiles,
        "chain_stage_dims": _a_chain_dims,
        "chain_band_geometry": _a_chain_geom,
        "auto_chain_block": _a_auto_chain,
        "resolve_chain_block": _a_resolve_chain,
    },
    "pool2d": {
        "_out_size": _i_pool_out_size,
        "auto_oh_block_pool": _a_auto_oh_pool,
    },
    "matmul": {},
}

_ENV_CACHE: Dict[str, Env] = {}


def _kernel_source(name: str, sources) -> str:
    if sources is not None and name in sources:
        return sources[name]
    root = Path(__file__).resolve().parent.parent / "kernels"
    return (root / KERNEL_SOURCES[name]).read_text()


def load_kernel_modules(sources=None) -> Dict[str, Env]:
    """Parse the kernel sources into abstract module environments.

    ``sources`` maps a ``KERNEL_SOURCES`` key to replacement source text
    (the mutation tests inject seeded defects this way).  The sources are
    parsed with ``ast`` — never imported or executed.
    """
    if sources is None and _ENV_CACHE:
        return dict(_ENV_CACHE)
    envs: Dict[str, Env] = {}
    interp = Interp(envs, "<module>", [])
    for name in ("conv2d", "pool2d", "matmul"):
        env = Env()
        tree = ast.parse(_kernel_source(name, sources))
        for st in tree.body:
            interp.line = getattr(st, "lineno", 0)
            if isinstance(st, ast.Expr) and isinstance(st.value,
                                                       ast.Constant):
                continue  # module docstring
            if isinstance(st, ast.ImportFrom):
                mod = st.module or ""
                if mod == "__future__":
                    continue
                if mod in ("jax.experimental", "jax.experimental.pallas"):
                    for a in st.names:
                        handle = _PALLAS_ALIASES.get(a.name)
                        if handle is None:
                            raise Unsupported(f"from {mod} import "
                                              f"{a.name}")
                        env.set(a.asname or a.name, ModuleHandle(handle))
                    continue
                interp.import_from(st, env)
                continue
            if isinstance(st, ast.Import):
                for a in st.names:
                    tgt = a.asname or a.name.split(".", 1)[0]
                    if a.name == "jax.numpy":
                        env.set(tgt, ModuleHandle("jnp"))
                    elif a.name in ("jax", "functools"):
                        env.set(tgt, ModuleHandle(a.name))
                    else:
                        raise Unsupported(f"import {a.name}")
                continue
            if isinstance(st, ast.Assign):
                value = interp.eval(st.value, env)
                for t in st.targets:
                    interp.assign(t, value, env)
                continue
            if isinstance(st, ast.FunctionDef):
                env.set(st.name, Closure(st, env, st.name, name))
                continue
            raise Unsupported(
                f"module-level {type(st).__name__} in {name}")
        for iname, fn in _INTERCEPTS[name].items():
            if iname in env.vars:
                env.vars[iname] = PyFn(fn, iname)
        envs[name] = env
    if sources is None:
        _ENV_CACHE.update(envs)
    return envs


# ---------------------------------------------------------------------------
# public API — one sanitize_* per dispatch family
# ---------------------------------------------------------------------------


def _run_entry(module, entry, args, kwargs, label, sources,
               expected_shape):
    findings: List[Finding] = []
    try:
        envs = load_kernel_modules(sources)
        interp = Interp(envs, label, findings)
        fn = envs[module].get(entry)
        out = interp.call(fn, args, kwargs)
        if isinstance(out, AArray) and expected_shape is not None \
                and out.shape != tuple(expected_shape):
            findings.append(Finding(
                "error", label, "K100",
                f"entry returned shape {out.shape}, the dispatch config "
                f"implies {tuple(expected_shape)}"))
    except KernelRaise as e:
        findings.append(Finding("error", label, "K100",
                                f"entry raised: {e}"))
    except Unsupported as e:
        findings.append(Finding("error", label, "K100",
                                f"unsupported construct: {e}"))
    except RecursionError:
        findings.append(Finding("error", label, "K100",
                                "interpreter recursion limit"))
    except Exception as e:  # internal inconsistency -> unproven, loudly
        findings.append(Finding(
            "error", label, "K100",
            f"sanitizer internal error ({type(e).__name__}: {e})"))
    return findings


def sanitize_conv2d(x_shape, w_shape, *, stride=(1, 1), padding=(0, 0),
                    relu=False, im2col=True, oc_block=128, oh_block=None,
                    pool_kernel=None, pool_stride=None, pool_kind="max",
                    pool_relu=False, lrn=None, pool_carry=None,
                    lrn_oc_block=None, sources=None, label=None):
    """Prove one (possibly pool/LRN-fused) SIMD conv dispatch.

    ``x_shape`` NHWC, ``w_shape`` HWIO — pass the PADDED operand shapes
    the engine actually dispatches.  ``pool_carry``/``lrn_oc_block``
    mirror the dispatch knobs (None = the resolvers' auto rule, re-
    derived here by Phase A).  Returns ``(findings, geom)`` where
    ``geom`` is the Phase-A band geometry for the K105 cross-check —
    ``carry`` is the input rows the sliding-window accumulator carries
    between bands (0 for classic cells) and ``steps`` the physical grid
    steps on the band axis (``n_tiles + 1`` with carry: step 0 is the
    sacrificial seed band).
    """
    n, h, wd, c = x_shape
    kh, kw, _, oc = w_shape
    sy, sx = stride
    py, px = padding
    entry = "conv2d_advanced_simd" if im2col else "conv2d_basic_simd"
    label = label or f"{entry}[{'x'.join(map(str, x_shape))}]"
    oh, ow = _a_out(h, kh, sy, py), _a_out(wd, kw, sx, px)
    wp = wd + 2 * px
    if pool_kernel is not None:
        pkh, pkw = pool_kernel
        psy, psx = pool_stride if pool_stride is not None else pool_kernel
        pool = (pkh, pkw, psy, psx)
    else:
        pool = None
    if not im2col:
        ocb, oc_halo = oc, 0
    elif lrn is not None and pool is not None:
        ocb, oc_halo = _a_resolve_lrn_ocb(oc, oc_block, lrn, lrn_oc_block,
                                          ow, wp, c, kh, kw, sy, pool)
    elif lrn is not None:
        ocb, oc_halo = oc, 0  # the entry raises (LRN needs a pool tail)
    else:
        ocb, oc_halo = min(oc_block, oc), 0
    kwargs = dict(stride=stride, padding=padding, relu=relu,
                  oh_block=oh_block, pool_kernel=pool_kernel,
                  pool_stride=pool_stride, pool_kind=pool_kind,
                  pool_relu=pool_relu, lrn=lrn)
    if im2col:
        kwargs["oc_block"] = oc_block
        kwargs["pool_carry"] = pool_carry
        kwargs["lrn_oc_block"] = lrn_oc_block
    if pool_kernel is not None:
        ph, pw = (oh - pkh) // psy + 1, (ow - pkw) // psx + 1
        if ph < 1 or pw < 1:
            return [Finding("error", label, "K100",
                            "pool window larger than conv output")], None
        blk, n_tiles = _a_resolve_ph(ph, oh, ow, wp, c, kh, kw, sy, ocb,
                                     pool, oh_block, im2col=im2col,
                                     oc_halo=oc_halo)
        carry_on = _a_resolve_pool_carry(pool_carry if im2col else False,
                                         im2col, lrn, pool, blk, n_tiles)
        carry = (pkh - psy) * sy if carry_on else 0
        geom = {"kind": "fused", "blk": blk, "n_tiles": n_tiles,
                "total": ph,
                "band": _a_band(_a_band(blk, pkh, psy), kh, sy) - carry,
                "row_step": blk * psy * sy, "in_base": 0,
                "carry": carry,
                "steps": n_tiles + (1 if carry_on else 0)}
        expected = (n, ph, pw, oc)
    else:
        blk = _a_resolve_oh(oh, ow, wp, c, kh, kw, sy, ocb, oh_block,
                            im2col=im2col)
        geom = {"kind": "conv", "blk": blk,
                "n_tiles": _ceil_div(oh, blk), "total": oh,
                "band": _a_band(blk, kh, sy), "row_step": blk * sy,
                "in_base": 0, "carry": 0, "steps": _ceil_div(oh, blk)}
        expected = (n, oh, ow, oc)
    x = AArray(x_shape, "io")
    w = AArray(w_shape, "io")
    b = AArray((oc,), "io")
    findings = _run_entry("conv2d", entry, [x, w, b], kwargs, label,
                          sources, expected)
    return findings, geom


def sanitize_pool2d(x_shape, *, kernel=(2, 2), stride=(2, 2), kind="max",
                    relu=False, oh_block=None, sources=None, label=None):
    """Prove one standalone Pallas pooling dispatch."""
    n, h, wd, c = x_shape
    kh, kw = kernel
    sy, sx = stride
    label = label or f"pool2d_nhwc[{'x'.join(map(str, x_shape))}]"
    oh, ow = _i_pool_out_size(h, kh, sy), _i_pool_out_size(wd, kw, sx)
    if oh < 1 or ow < 1:
        return [Finding("error", label, "K100",
                        "pool window larger than input")], None
    if oh_block is None:
        blk = _a_auto_oh_pool(oh, ow, wd, c, kh, sy)
    else:
        blk = max(1, min(oh_block, oh))
    geom = {"kind": "pool", "blk": blk, "n_tiles": _ceil_div(oh, blk),
            "total": oh, "band": _a_band(blk, kh, sy),
            "row_step": blk * sy, "in_base": 0, "carry": 0,
            "steps": _ceil_div(oh, blk)}
    x = AArray(x_shape, "io")
    findings = _run_entry(
        "pool2d", "pool2d_nhwc", [x],
        dict(kernel=kernel, stride=stride, kind=kind, relu=relu,
             oh_block=oh_block), label, sources, (n, oh, ow, c))
    return findings, geom


def sanitize_chain(x_shape, w_shapes, *, strides, paddings, relus,
                   im2col=True, oh_block=None, pool_kernel=None,
                   pool_stride=None, pool_kind="max", pool_relu=False,
                   lrn=None, oc_block_final=None, sources=None,
                   label=None):
    """Prove one fused conv→conv(→pool→LRN) chain dispatch.

    ``oc_block_final`` mirrors the dispatch knob: the final stage's oc
    grid is blocked (its channels nothing inside the cell consumes) and
    the Phase-A block walk re-derives the band under the shrunken
    resident-weights model.
    """
    n, h, wd, c = x_shape
    label = label or f"conv2d_chain_simd[{len(w_shapes)} stages]"
    chain = tuple((ws[0], ws[1], st[0], st[1], pd[0], pd[1])
                  for ws, st, pd in zip(w_shapes, strides, paddings))
    ocs = tuple(ws[3] for ws in w_shapes)
    if pool_kernel is not None:
        pkh, pkw = pool_kernel
        psy, psx = pool_stride if pool_stride is not None else pool_kernel
        pool = (pkh, pkw, psy, psx)
    else:
        pool = None
    obf = oc_block_final
    if obf is not None and (lrn is not None or obf >= ocs[-1]):
        obf = None  # the dispatch normalizes/rejects identically
    try:
        dims = _a_chain_dims(h, wd, c, chain, ocs)
        oh_f, ow_f, _, oc_f = dims[-1]
        if pool is not None:
            target = (oh_f - pool[0]) // pool[2] + 1
            out_cols = (ow_f - pool[1]) // pool[3] + 1
        else:
            target, out_cols = oh_f, ow_f
        blk, n_tiles = _a_resolve_chain(h, wd, c, chain, ocs, pool,
                                        oh_block, im2col=im2col,
                                        oc_block_final=obf)
        _, _, band, in_step, in_base = _a_chain_geom(blk, chain, pool)
    except KernelRaise as e:
        return [Finding("error", label, "K100",
                        f"chain geometry failed: {e}")], None
    geom = {"kind": "chain", "blk": blk, "n_tiles": n_tiles,
            "total": target, "band": band, "row_step": in_step,
            "in_base": in_base, "carry": 0, "steps": n_tiles}
    x = AArray(x_shape, "io")
    ws = [AArray(s, "io") for s in w_shapes]
    bs = [AArray((s[3],), "io") for s in w_shapes]
    findings = _run_entry(
        "conv2d", "conv2d_chain_simd", [x, ws, bs, strides, paddings,
                                        relus],
        dict(im2col=im2col, oh_block=oh_block, pool_kernel=pool_kernel,
             pool_stride=pool_stride, pool_kind=pool_kind,
             pool_relu=pool_relu, lrn=lrn, oc_block_final=oc_block_final),
        label, sources, (n, target, out_cols, oc_f))
    return findings, geom


def sanitize_matmul(x_shape, w_shape, *, has_bias=True, act="none",
                    sources=None, label=None):
    """Prove one fused bias+activation matmul dispatch."""
    m_dim, k_dim = x_shape
    _, n_dim = w_shape
    label = label or f"matmul_fused_pallas[{m_dim}x{k_dim}x{n_dim}]"
    x = AArray(x_shape, "io")
    w = AArray(w_shape, "io")
    b = AArray((n_dim,), "io") if has_bias else None
    findings = _run_entry("matmul", "matmul_fused_pallas", [x, w, b],
                          dict(act=act), label, sources, (m_dim, n_dim))
    return findings, None
