"""Engine-level fusion planner: conv-chain[+pool][+lrn] → super-layers.

CNNdroid's headline wins come from eliminating redundant memory passes
(fused bias/ReLU epilogues, the Fig. 5 overlap).  This module extends
that idea across layers: it scans a ``NetworkDef`` and greedily groups a
run of CONSECUTIVE conv layers (interleaved standalone ReLUs absorbed),
an optional immediately-following pool layer, and an optional trailing
LRN layer into one ``FusedLayerSpec``.  The engine executes a group as a
single dispatch — on the Pallas path the chain cell keeps every
intermediate conv band (halo included) in VMEM and writes only the final
activation (no intermediate of the run ever touches HBM); on the XLA
path the whole group runs in one NHWC pass with a single layout
round-trip.  AlexNet's conv3→conv4→conv5+pool5 — the MAC-heaviest
stretch of the paper's Table 2 networks — becomes one dispatch writing
only the pooled band.

A group needs at least two layers: a lone conv (no following conv or
pool) stays on the per-layer ladder; a conv chain of length ≥ 2 fuses
with or without a pool tail.

Correctness fallbacks — layers stay on the per-layer ladder when:

* a conv's execution method is not a SIMD method (``seq_ref`` and
  ``basic_parallel`` keep the paper's un-fused per-layer semantics), or
  two consecutive convs resolve to *different* methods (a chain cell
  runs one method; the chain breaks between them),
* the pool kind is not max/avg,
* the pool window is larger than the conv output (shape-checked by
  propagating spatial dims through the net),
* a conv after the first has a stride (a chain's later stage reads a
  VMEM value, which Mosaic cannot slice with a stride),
* a conv, pool, or lrn layer is named in ``no_fuse`` (per-layer opt-out,
  mirroring ``per_layer_methods``; an opted-out LRN only drops the LRN
  from the group, an opted-out conv breaks the chain at that conv),
* a standalone ReLU follows a conv but ``fuse_relu`` is off (we will not
  reorder an activation we were told not to fold: the chain ends before
  it and no pool is absorbed across it),
* the VMEM working-set check fails (Pallas path — the engine passes
  ``vmem_check=use_pallas``, since the one-pass XLA analogue has no VMEM
  ceiling): the fused kernel shrinks its final-row band to fit the
  budget, but its floor cell is one final row — when even THAT cell's
  modelled footprint (``kernels.fused_cell_bytes`` for single-conv
  groups, ``kernels.chain_cell_bytes`` — every stage's full-width
  weights resident plus the peak per-stage band/patch live set — for
  chains) exceeds the budget, the planner first drops the LRN tail,
  then falls back to successively SHORTER chains (the detached tail
  layers re-enter the scan and may group among themselves) before
  declining fusion outright,
* the graph branches: a layer joins a group only if it reads the
  group's last output and is that tensor's one reader, and a padded
  pool never joins one.

Residual joins (Pallas path, ``residual=True``): an ``add`` runs as the
epilogue of a conv that feeds it — ``relu?(conv + b + shortcut)`` in
one dispatch — when the add is the conv's one reader, the conv has no
ReLU of its own and runs on a SIMD method.  Such a conv runs alone: it
is decided before chains are, so a chain stops before it, and the group
takes the add's place in the plan (the shortcut may be computed after
the conv in network order).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Iterable, List, Optional, Tuple, Union

from repro.core.methods import Method
from repro.core.netdefs import (
    LayerSpec,
    NetworkDef,
    layer_inputs,
    layer_readers,
)

#: methods whose kernels support the fused pooling epilogue
FUSABLE_METHODS = frozenset({
    Method.BASIC_SIMD, Method.ADVANCED_SIMD_4, Method.ADVANCED_SIMD_8,
})

#: methods whose fused kernel stages a full im2col patch matrix (the
#: advanced oc-blocked kernels; basic_simd holds one [rows, C] slice)
IM2COL_METHODS = frozenset({Method.ADVANCED_SIMD_4, Method.ADVANCED_SIMD_8})

SUPPORTED_POOL_KINDS = frozenset({"max", "avg"})

#: oc tile width both advanced methods' kernels run with: ``conv2d.ops``
#: raises the paper's 4/8 outputs per thread to the 128-lane MXU tile
#: (``kernels.LANES``), the narrowest lane-dim block Mosaic accepts
ADVANCED_OC_BLOCK = 128


@dataclass(frozen=True)
class FusedLayerSpec:
    """A conv→[ReLU]→…→conv→[ReLU]→[pool]→[ReLU]→[LRN] super-layer
    (one dispatch).  ``convs`` is the chain of consecutive conv stages;
    ``relus[i]`` is the ReLU after stage i (the conv's own or an absorbed
    standalone one).  ``pool`` is None for a chain fused without a pool
    tail."""
    convs: Tuple[LayerSpec, ...]
    relus: Tuple[bool, ...]
    pool: Optional[LayerSpec]
    pool_relu: bool   # ReLU after the pool (pool's own or absorbed)
    names: Tuple[str, ...]  # original layer names this group covers
    lrn: Optional[LayerSpec] = None  # trailing LRN absorbed into the cell
    #: chain-only: oc-grid block the final stage runs with (None = full
    #: width).  Set by the planner's admission ladder when a chain's
    #: full-width resident weights bust the budget; incompatible with a
    #: fused LRN tail (the kernel raises).
    oc_block_final: Optional[int] = None
    #: residual join: the ``add`` layer this single conv's epilogue runs
    #: and the tensor it adds (no pool or LRN then)
    add: Optional[LayerSpec] = None
    shortcut: Optional[str] = None

    kind = "fused"  # sentinel so plan items can be dispatched on .kind

    @property
    def conv(self) -> LayerSpec:
        """The first conv of the chain (single-conv groups: THE conv)."""
        return self.convs[0]

    @property
    def relu(self) -> bool:
        """ReLU between the last conv stage and the pool."""
        return self.relus[-1]

    @property
    def name(self) -> str:
        return "+".join(self.names)


PlanItem = Union[LayerSpec, FusedLayerSpec]


def _conv_out_hw(h: int, w: int, spec: LayerSpec) -> Tuple[int, int]:
    kh, kw = spec.kernel
    return ((h + 2 * spec.padding[0] - kh) // spec.stride[0] + 1,
            (w + 2 * spec.padding[1] - kw) // spec.stride[1] + 1)


def _pool_out_hw(h: int, w: int, spec: LayerSpec) -> Tuple[int, int]:
    kh, kw = spec.kernel
    ph, pw = spec.padding
    return ((h + 2 * ph - kh) // spec.stride[0] + 1,
            (w + 2 * pw - kw) // spec.stride[1] + 1)


def layer_shapes(net: NetworkDef) -> Dict[str, Tuple[int, ...]]:
    """The output shape of every layer of ``net`` by name, and the
    frame's as ``"input"``: ``(C, H, W)`` while spatial, ``(D,)`` once
    flattened.  An fc reads all of a spatial activation; an add, lrn,
    relu or softmax keeps its first input's shape."""
    shapes: Dict[str, Tuple[int, ...]] = {"input": tuple(net.input_shape)}
    for spec, ins in zip(net.layers, layer_inputs(net).values()):
        missing = [n for n in ins if n not in shapes]
        if missing:
            raise ValueError(f"{spec.name} reads {missing}: not the frame "
                             "or an earlier layer")
        cur = shapes[ins[0]]
        if spec.kind == "conv":
            cur = (spec.out_channels, *_conv_out_hw(cur[1], cur[2], spec))
        elif spec.kind == "pool":
            cur = (cur[0], *_pool_out_hw(cur[1], cur[2], spec))
        elif spec.kind in ("flatten", "fc"):
            flat = 1
            for d in cur:
                flat *= int(d)
            cur = (flat if spec.kind == "flatten" else spec.out_channels,)
        shapes[spec.name] = cur
    return shapes


def fused_working_set(conv: LayerSpec, pool: LayerSpec, method: Method,
                      cin: int, w_in: int, *,
                      lrn: bool = False,
                      lrn_n: Optional[int] = None) -> int:
    """Modelled VMEM bytes of the smallest possible fused grid cell (one
    pooled row — one pool window of conv rows) for this conv+pool pair.

    Mirrors what ``conv2d.ops`` + the kernels will actually stage: the
    input channel count is padded to the sublane multiple, the advanced
    methods charge a full im2col patch matrix and the 4/8-wide oc tile
    their fused kernel runs with.  With ``lrn`` set the oc width follows
    ``kernels.resolve_lrn_ocb``: the historical full-width tile when the
    full-width floor cell fits the budget, else the two-pass
    channel-halo cell's ``ocb + lrn_n - 1`` widened tile (``lrn_n`` is
    the LRN window; ``None`` keeps the conservative full-width charge —
    basic_simd is always full width).
    """
    from repro.kernels.conv2d import kernels as K  # deferred: keeps the
    from repro.kernels.conv2d.ops import SUBLANES  # planner importable
    # without pulling Pallas in at module-import time

    c = -(-cin // SUBLANES) * SUBLANES
    oc = conv.out_channels
    im2col = method in IM2COL_METHODS
    _, ow = _conv_out_hw(0, w_in, conv)  # h unused for the width
    wp = w_in + 2 * conv.padding[1]
    pool_t = (pool.kernel[0], pool.kernel[1], pool.stride[0],
              pool.stride[1])
    kh, kw = conv.kernel
    sy = conv.stride[0]
    oc_halo = 0
    if lrn and im2col and lrn_n is not None:
        ocb, oc_halo = K.resolve_lrn_ocb(
            oc, ADVANCED_OC_BLOCK, (lrn_n, 1e-4, 0.75, 1.0),
            None, ow, wp, c, kh, kw, sy, pool_t, im2col=im2col)
    elif lrn or not im2col:
        ocb = oc
    else:
        ocb = min(ADVANCED_OC_BLOCK, oc)
    return K.fused_cell_bytes(1, ow, wp, c, kh, kw, sy, ocb, pool_t,
                              im2col=im2col, oc_halo=oc_halo)


def layers_as_chain(convs) -> Tuple[Tuple, Tuple]:
    """``LayerSpec`` convs → the kernels' chain description: per-stage
    ``(kh, kw, sy, sx, py, px)`` tuples plus the SUBLANES-padded
    per-stage output-channel counts (what ``conv2d.ops`` will actually
    stage — inter-stage channel padding composes through the chain)."""
    from repro.kernels.conv2d.ops import SUBLANES

    chain = tuple((cv.kernel[0], cv.kernel[1], cv.stride[0], cv.stride[1],
                   cv.padding[0], cv.padding[1]) for cv in convs)
    ocs = tuple(-(-cv.out_channels // SUBLANES) * SUBLANES for cv in convs)
    return chain, ocs


def chain_working_set(convs, pool, method: Optional[Method],
                      cin: int, h_in: int, w_in: int,
                      oc_block_final: Optional[int] = None) -> int:
    """Modelled VMEM bytes of the smallest possible chain grid cell (one
    final row — one pool window of final-conv rows when ``pool`` is set)
    for this run of consecutive convs.  Chains run every *intermediate*
    stage at full output-channel width (the next stage consumes every
    channel), so the dominant term is the resident weights of all stages
    (``kernels.chain_cell_bytes``); ``oc_block_final`` restores oc-grid
    blocking on the final stage, shrinking its resident-weights and
    output-band terms."""
    from repro.kernels.conv2d import kernels as K
    from repro.kernels.conv2d.ops import SUBLANES

    c = -(-cin // SUBLANES) * SUBLANES
    chain, ocs = layers_as_chain(convs)
    pool_t = (None if pool is None else
              (pool.kernel[0], pool.kernel[1], pool.stride[0],
               pool.stride[1]))
    im2col = method is None or method in IM2COL_METHODS
    return K.chain_cell_bytes(1, h_in, w_in, c, chain, ocs, pool_t,
                              im2col=im2col,
                              oc_block_final=oc_block_final)


#: a fusion cost gate: ``gate(candidate_group, method, in_shape) -> bool``
#: — True admits the group, False sends the planner down the same
#: shorter-chain fallback ladder the VMEM check uses.  Built by
#: ``repro.core.cost.fusion_cost_gate``.
CostGate = Callable[["FusedLayerSpec", Optional[Method],
                     Tuple[int, int, int]], bool]


def plan_fusion(net: NetworkDef, *,
                method_for: Optional[Callable[[str], Method]] = None,
                no_fuse: Iterable[str] = (),
                fuse_relu: bool = True,
                vmem_budget: Optional[int] = None,
                vmem_check: bool = True,
                cost_gate: Optional[CostGate] = None,
                residual: bool = False) -> List[PlanItem]:
    """Greedy left-to-right grouping of conv-chain[+relu][+pool][+lrn]
    runs, after the residual joins (``residual=True``; the engine passes
    its ``use_pallas``).

    ``method_for`` maps a conv layer name to its execution ``Method`` (the
    engine passes its per-layer resolution; ``None`` assumes the widest
    fused working set, the advanced im2col kernels).  ``vmem_budget``
    overrides the VMEM budget the working-set check runs against (None =
    ``kernels.VMEM_BUDGET_BYTES`` for single-conv groups and
    ``kernels.CHAIN_VMEM_BUDGET_BYTES`` for chains, whose grid-invariant
    resident weights are not double-buffered); ``vmem_check=False`` skips
    the check entirely — the engine passes its ``use_pallas`` here, since
    the one-NHWC-pass XLA analogue has no VMEM ceiling to respect.

    ``cost_gate`` (the cost-model flag) REPLACES the raw budget check:
    each candidate group is admitted by the gate instead of by
    ``_fits_vmem``, so a group can be declined for being modelled SLOWER
    than its per-layer ladder even though it fits VMEM (and the gate is
    consulted on the XLA path too, where there is no VMEM ceiling).  A
    declined candidate walks the same fallback ladder: drop the LRN
    tail, then trailing convs, then decline outright.
    Returns the layer sequence with each fused run replaced by one
    ``FusedLayerSpec`` (a residual join in its add's place); ungrouped
    layers pass through unchanged.
    """
    no_fuse = frozenset(no_fuse)
    layers = list(net.layers)
    inputs = layer_inputs(net)
    readers = layer_readers(net)
    shapes = layer_shapes(net)

    def feeds(src: str, layer: LayerSpec) -> bool:
        """``layer`` reads ``src`` alone and is its one reader."""
        return (inputs[layer.name] == (src,)
                and readers.get(src) == [layer.name])

    # a conv that can run its add's epilogue joins no chain, on either
    # path; the XLA path then runs the conv and the add as two steps
    joins = _residual_joins(layers, inputs, readers, method_for, no_fuse)
    held = {cv.name for cv, _ in joins.values()}
    plan: List[PlanItem] = []
    i = 0
    while i < len(layers):
        spec = layers[i]
        if residual and spec.name in joins:
            cv, shortcut = joins[spec.name]
            plan.append(FusedLayerSpec(
                convs=(cv,), relus=(False,), pool=None, pool_relu=False,
                names=(cv.name, spec.name), add=spec, shortcut=shortcut))
        elif spec.kind == "conv" and spec.name not in held:
            cin, h, w = shapes[inputs[spec.name][0]]
            group = _try_group(layers, i, method_for, no_fuse, fuse_relu,
                               cin, h, w, vmem_budget, vmem_check, cost_gate,
                               feeds=feeds, held=held)
            if group is not None:
                plan.append(group)
                i += len(group.names)
                continue
            plan.append(spec)
        elif not (residual and spec.name in held):
            plan.append(spec)
        i += 1
    return plan


def _residual_joins(layers, inputs, readers, method_for, no_fuse):
    """``{add name: (conv, shortcut name)}``: each two-input add whose
    epilogue a conv feeding it can run — the conv's one reader is the
    add, it has no ReLU of its own and runs on a SIMD method."""
    by_name = {spec.name: spec for spec in layers}
    joins = {}
    for add in layers:
        ins = inputs[add.name]
        if add.kind != "add" or add.name in no_fuse or len(ins) != 2:
            continue
        for src, shortcut in (ins, ins[::-1]):
            cv = by_name.get(src)
            if (cv is not None and cv.kind == "conv" and not cv.relu
                    and cv.name not in no_fuse
                    and readers.get(src) == [add.name]
                    and (method_for is None
                         or method_for(src) in FUSABLE_METHODS)):
                joins[add.name] = (cv, shortcut)
                break
    return joins


def _try_group(layers, i, method_for, no_fuse, fuse_relu, cin, h_in, w_in,
               vmem_budget, vmem_check=True,
               cost_gate: Optional[CostGate] = None, *, feeds, held,
               ) -> Optional[FusedLayerSpec]:
    """A FusedLayerSpec for the run starting at conv ``layers[i]``, or
    None when any eligibility check fails (the per-layer fallback).
    ``feeds(src, layer)`` says whether ``layer`` is the one reader of
    ``src`` and reads nothing else; ``held`` names the convs that run a
    residual join, which no chain absorbs."""
    first = layers[i]
    if first.name in no_fuse:
        return None
    method = method_for(first.name) if method_for is not None else None
    if method is not None and method not in FUSABLE_METHODS:
        return None
    # -- collect the maximal conv chain (absorbing standalone ReLUs) -------
    convs = [first]
    relus = [first.relu]
    conv_names = [[first.name]]  # per-stage names incl. absorbed ReLUs
    h, w = _conv_out_hw(h_in, w_in, first)
    j = i + 1
    blocked_by_relu = False  # an un-foldable standalone ReLU ends the run
    while True:
        if (j < len(layers) and layers[j].kind == "relu"
                and feeds(conv_names[-1][-1], layers[j])):
            if not fuse_relu:
                blocked_by_relu = True
                break
            relus[-1] = True
            conv_names[-1].append(layers[j].name)
            j += 1
        nxt = layers[j] if j < len(layers) else None
        # a later stage reads a VMEM value, which Mosaic cannot slice
        # with a stride: strided convs only lead a chain
        if (nxt is None or nxt.kind != "conv" or nxt.name in no_fuse
                or nxt.name in held or nxt.stride != (1, 1)
                or not feeds(conv_names[-1][-1], nxt)
                or (method_for is not None
                    and method_for(nxt.name) != method)):
            break
        oh2, ow2 = _conv_out_hw(h, w, nxt)
        if oh2 < 1 or ow2 < 1:
            break
        convs.append(nxt)
        relus.append(nxt.relu)
        conv_names.append([nxt.name])
        h, w = oh2, ow2
        j += 1
    # -- optional pool (+ReLU) and LRN tail on the last conv ---------------
    pool = None
    pool_relu = False
    pool_names: List[str] = []
    lrn = None
    if (not blocked_by_relu and j < len(layers) and layers[j].kind == "pool"
            and feeds(conv_names[-1][-1], layers[j])):
        p = layers[j]
        pkh, pkw = p.kernel
        if (p.name not in no_fuse and p.pool_kind in SUPPORTED_POOL_KINDS
                and p.padding == (0, 0)
                and pkh >= 1 and pkw >= 1
                and p.stride[0] >= 1 and p.stride[1] >= 1
                and pkh <= h and pkw <= w):
            pool = p
            pool_relu = p.relu
            pool_names = [p.name]
            k = j + 1
            if (fuse_relu and k < len(layers) and layers[k].kind == "relu"
                    and feeds(p.name, layers[k])):
                pool_relu = True
                pool_names.append(layers[k].name)
                k += 1
            if (k < len(layers) and layers[k].kind == "lrn"
                    and layers[k].name not in no_fuse
                    and feeds(pool_names[-1], layers[k])):
                lrn = layers[k]
    # -- admission check with shorter-chain fallback -----------------------
    # Raw VMEM working-set check (Pallas path only): the fused kernel
    # shrinks its final-row band to fit, but never below one final row —
    # when even that floor cell busts the budget, first drop the LRN
    # tail, then trailing convs (the detached pool/convs re-enter the
    # greedy scan), and only decline outright at a single conv+pool that
    # still cannot fit.  A ``cost_gate`` REPLACES the raw check (and
    # binds on the XLA path too): the same fallback ladder, but a group
    # is declined when the cost model scores it slower than its
    # per-layer ladder, not only when it busts VMEM.
    oc_block_final = None
    if vmem_check or cost_gate is not None:
        while True:
            if len(convs) == 1 and pool is None:
                return None
            if cost_gate is not None:
                cand = FusedLayerSpec(
                    convs=tuple(convs), relus=tuple(relus), pool=pool,
                    pool_relu=pool_relu,
                    names=(tuple(n for stage in conv_names for n in stage)
                           + tuple(pool_names)
                           + ((lrn.name,) if lrn is not None else ())),
                    lrn=lrn, oc_block_final=oc_block_final)
                admitted = cost_gate(cand, method, (cin, h_in, w_in))
            else:
                admitted = _fits_vmem(convs, pool, method, cin, h_in, w_in,
                                      lrn, vmem_budget, oc_block_final)
            if admitted:
                break
            if lrn is not None:
                lrn = None
                continue
            if len(convs) > 1 and oc_block_final is None:
                # chain rung: block the final stage's oc grid (its
                # channels feed no further stage) before shortening the
                # chain — incompatible with LRN, which is gone by here
                oc_block_final = ADVANCED_OC_BLOCK
                continue
            if len(convs) == 1:
                return None  # single conv+pool whose floor cell busts
            convs.pop()
            relus.pop()
            conv_names.pop()
            pool, pool_relu, pool_names = None, False, []
            oc_block_final = None
    if len(convs) == 1 and pool is None:
        return None  # a lone conv is not a super-layer
    names = (tuple(n for stage in conv_names for n in stage)
             + tuple(pool_names) + ((lrn.name,) if lrn is not None else ()))
    return FusedLayerSpec(convs=tuple(convs), relus=tuple(relus), pool=pool,
                          pool_relu=pool_relu, names=names, lrn=lrn,
                          oc_block_final=oc_block_final)


def _fits_vmem(convs, pool, method, cin, h_in, w_in, lrn,
               vmem_budget, oc_block_final=None) -> bool:
    from repro.kernels.conv2d import kernels as K

    if len(convs) > 1:
        # chain cells: full width at every intermediate stage, resident
        # weights — checked against the near-full-VMEM chain budget
        # (method=None charges im2col staging, the widest any fusable
        # method stages); ``oc_block_final`` shrinks the final stage
        budget = (K.CHAIN_VMEM_BUDGET_BYTES if vmem_budget is None
                  else vmem_budget)
        return chain_working_set(convs, pool, method, cin, h_in, w_in,
                                 oc_block_final=oc_block_final) <= budget
    budget = K.VMEM_BUDGET_BYTES if vmem_budget is None else vmem_budget
    # unknown method (method_for=None): charge the widest cell any
    # fusable method would stage — basic_simd's full-width oc terms and
    # the advanced kernels' im2col staging dominate different regimes
    methods = ((method,) if method is not None
               else (Method.BASIC_SIMD, Method.ADVANCED_SIMD_8))
    lrn_n = None if lrn is None else lrn.lrn_n
    return max(fused_working_set(convs[0], pool, m, cin, w_in,
                                 lrn=lrn is not None, lrn_n=lrn_n)
               for m in methods) <= budget


def group_fits_vmem(group: FusedLayerSpec, method: Optional[Method],
                    in_shape: Tuple[int, int, int],
                    vmem_budget: Optional[int] = None) -> bool:
    """The planner's working-set admission check, for an already-formed
    group: True when the group's one-final-row floor cell fits the
    (chain or fused) VMEM budget.  This is the budget leg a cost-model
    gate (``repro.core.cost.fusion_cost_gate``) runs before comparing
    modelled latencies — same accounting, public entry point."""
    c, h, w = in_shape
    return _fits_vmem(list(group.convs), group.pool, method, c, h, w,
                      group.lrn, vmem_budget, group.oc_block_final)


def fusion_summary(plan: Iterable[PlanItem]) -> List[Tuple[str, ...]]:
    """The fused groups in a plan, as tuples of original layer names."""
    return [it.names for it in plan if isinstance(it, FusedLayerSpec)]


def group_band_params(group: FusedLayerSpec, method: Method,
                      in_shape: Tuple[int, int, int],
                      oh_block: Optional[int], *,
                      pool_carry: Optional[bool] = None,
                      lrn_oc_block: Optional[bool] = None) -> dict:
    """The FULL resolved band geometry + VMEM accounting of one fused
    group's Pallas cell, re-derived from the same kernel resolvers the
    dispatch path runs (``resolve_ph_block`` / ``resolve_chain_block`` /
    ``chain_band_geometry``) — the single source the engine's geometry
    report AND the static plan verifier read.

    Keys:

    * ``kind``: ``"fused"`` (single conv + pool) or ``"chain"``,
    * ``blk`` / ``n_tiles`` / ``total``: final rows per grid cell, bands
      per frame, and the valid final-row count they must partition,
    * ``band`` / ``row_step`` / ``in_base``: input rows one cell stages,
      the per-band input-row advance, and the stage-0 padded-coordinate
      offset of band 0 (≤ 0: the kernel pre-pads ``-in_base`` extra top
      zero rows),
    * ``carry`` / ``steps``: input rows re-used from VMEM scratch each
      band step (``K*sy`` for the sliding-window carry cell, 0
      otherwise) and the physical grid steps along the band axis
      (``n_tiles + 1`` for the carry cell's sacrificial seed step,
      ``n_tiles`` otherwise),
    * ``stride_eff`` / ``window_eff``: the group collapsed to ONE
      effective conv — input rows advanced per final row, and input rows
      one final row reads (``band == (blk-1)*stride_eff + window_eff``),
    * ``padded_h``: the genuine zero-padded input frame height (rows at
      or past it that a band touches are bottom overshoot — pad fetched
      and sliced off),
    * ``cell_bytes`` / ``floor_bytes`` / ``budget``: the modelled VMEM
      working set of the resolved cell, of the one-final-row floor cell,
      and the budget both are admitted against,
    * ``out_hw``: the group's output spatial size.
    """
    from repro.kernels.conv2d import kernels as K
    from repro.kernels.conv2d.ops import SUBLANES

    if group.add is not None:  # a residual join banks like its conv
        return conv_band_params(group.conv, method, in_shape, oh_block)
    c, h, w = in_shape
    im2col = method in IM2COL_METHODS
    cp = -(-c // SUBLANES) * SUBLANES
    pool_t = (None if group.pool is None else
              (group.pool.kernel[0], group.pool.kernel[1],
               group.pool.stride[0], group.pool.stride[1]))
    if len(group.convs) == 1:
        # single conv + pool: the oc-blocked epilogue kernel
        cv = group.convs[0]
        oh, ow = _conv_out_hw(h, w, cv)
        wp = w + 2 * cv.padding[1]
        oc = cv.out_channels
        kh, kw = cv.kernel
        sy = cv.stride[0]
        lrn_t = None
        if group.lrn is not None:
            lg = group.lrn
            lrn_t = (lg.lrn_n, lg.lrn_alpha, lg.lrn_beta, lg.lrn_k)
        if not im2col:
            ocb, oc_halo = oc, 0  # basic_simd: always full oc width
        else:
            ocb, oc_halo = K.resolve_lrn_ocb(
                oc, ADVANCED_OC_BLOCK, lrn_t, lrn_oc_block, ow,
                wp, cp, kh, kw, sy, pool_t)
        pkh, _, psy, _ = pool_t
        ph = (oh - pkh) // psy + 1
        blk, n_tiles = K.resolve_ph_block(
            ph, oh, ow, wp, cp, kh, kw, sy, ocb, pool_t, oh_block,
            im2col=im2col, oc_halo=oc_halo)
        carry_on = K.resolve_pool_carry(pool_carry, im2col, lrn_t, pool_t,
                                        blk, n_tiles)
        stride_eff = psy * sy          # input rows per pooled row
        window_eff = (pkh - 1) * sy + kh
        carry = (pkh - psy) * sy if carry_on else 0
        geo = {
            "kind": "fused", "blk": blk, "n_tiles": n_tiles, "total": ph,
            "band": (blk - 1) * stride_eff + window_eff - carry,
            "row_step": blk * stride_eff, "in_base": 0,
            "carry": carry, "steps": n_tiles + (1 if carry_on else 0),
            "stride_eff": stride_eff, "window_eff": window_eff,
            "padded_h": h + 2 * cv.padding[0],
            "cell_bytes": K.fused_cell_bytes(blk, ow, wp, cp, kh, kw, sy,
                                             ocb, pool_t, im2col=im2col,
                                             oc_halo=oc_halo),
            "floor_bytes": K.fused_cell_bytes(1, ow, wp, cp, kh, kw, sy,
                                              ocb, pool_t, im2col=im2col,
                                              oc_halo=oc_halo),
            "budget": K.VMEM_BUDGET_BYTES,
        }
    else:
        chain, ocs = layers_as_chain(group.convs)
        obf = group.oc_block_final
        blk, n_tiles = K.resolve_chain_block(h, w, cp, chain, ocs, pool_t,
                                             oh_block, im2col=im2col,
                                             oc_block_final=obf)
        _, _, band, in_step, in_base = K.chain_band_geometry(blk, chain,
                                                             pool_t)
        hh, ww = h, w
        for cv in group.convs:
            hh, ww = _conv_out_hw(hh, ww, cv)
        if pool_t is not None:
            total = (hh - pool_t[0]) // pool_t[2] + 1
        else:
            total = hh
        stride_eff = in_step // blk    # in_step is blk whole strides
        geo = {
            "kind": "chain", "blk": blk, "n_tiles": n_tiles, "total": total,
            "band": band, "row_step": in_step, "in_base": in_base,
            "carry": 0, "steps": n_tiles,
            "stride_eff": stride_eff,
            "window_eff": band - (blk - 1) * stride_eff,
            "padded_h": h + 2 * chain[0][4],
            "cell_bytes": K.chain_cell_bytes(blk, h, w, cp, chain, ocs,
                                             pool_t, im2col=im2col,
                                             oc_block_final=obf),
            "floor_bytes": K.chain_cell_bytes(1, h, w, cp, chain, ocs,
                                              pool_t, im2col=im2col,
                                              oc_block_final=obf),
            "budget": K.CHAIN_VMEM_BUDGET_BYTES,
        }
    for cv in group.convs:
        h, w = _conv_out_hw(h, w, cv)
    if group.pool is not None:
        h, w = _pool_out_hw(h, w, group.pool)
    geo["out_hw"] = [h, w]
    return geo


def conv_band_params(spec: LayerSpec, method: Method,
                     in_shape: Tuple[int, int, int],
                     oh_block: Optional[int]) -> dict:
    """The band geometry of one conv's own Pallas cell, as
    ``group_band_params`` gives a group's: the output-row band
    ``resolve_oh_block`` picks, with no pool or LRN epilogue."""
    from repro.kernels.conv2d import kernels as K
    from repro.kernels.conv2d.ops import SUBLANES

    c, h, w = in_shape
    kh, kw = spec.kernel
    sy = spec.stride[0]
    oh, ow = _conv_out_hw(h, w, spec)
    cp = -(-c // SUBLANES) * SUBLANES
    wp = w + 2 * spec.padding[1]
    im2col = method in IM2COL_METHODS
    ocb = (min(ADVANCED_OC_BLOCK, spec.out_channels) if im2col else
           K.resolve_basic_ocb(spec.out_channels, ow, wp, cp, kh, kw, sy))
    blk = K.resolve_oh_block(oh, ow, wp, cp, kh, kw, sy, ocb, oh_block,
                             im2col=im2col)
    return {
        "kind": "conv", "blk": blk, "n_tiles": -(-oh // blk),
        "total": oh, "band": K._band_rows(blk, kh, sy),
        "row_step": blk * sy, "in_base": 0, "stride_eff": sy,
        "carry": 0, "steps": -(-oh // blk),
        "window_eff": kh, "padded_h": h + 2 * spec.padding[0],
        "cell_bytes": K.conv_cell_bytes(blk, ow, wp, cp, kh, kw, sy,
                                        ocb, im2col=im2col),
        "floor_bytes": K.conv_cell_bytes(1, ow, wp, cp, kh, kw, sy,
                                         ocb, im2col=im2col),
        "budget": K.VMEM_BUDGET_BYTES, "out_hw": [oh, ow],
    }


def group_geometry(group: FusedLayerSpec, method: Method,
                   in_shape: Tuple[int, int, int],
                   oh_block: Optional[int], *,
                   pool_carry: Optional[bool] = None,
                   lrn_oc_block: Optional[bool] = None) -> dict:
    """The executed geometry of one fused group: the final-row band the
    Pallas cell resolves (``rows_per_cell`` pooled/final rows per grid
    cell × ``n_tiles`` bands per frame) plus the group's output spatial
    size.  A compact view over ``group_band_params`` — the report IS
    what a Pallas run would execute (the XLA analogue runs each group as
    one un-banded pass).  ``in_shape`` is the ``(C, H, W)`` activation
    entering the group — the plan IR carries it pre-resolved on each
    fused step."""
    geo = group_band_params(group, method, in_shape, oh_block,
                            pool_carry=pool_carry,
                            lrn_oc_block=lrn_oc_block)
    row = {"group": group.name, "convs": len(group.convs),
           "rows_per_cell": geo["blk"], "n_tiles": geo["n_tiles"],
           "out_hw": geo["out_hw"]}
    if group.shortcut is not None:
        row["shortcut"] = group.shortcut
    return row
