"""Static plan verifier: prove an ``ExecutionPlan``'s geometry on paper.

``verify_plan(plan)`` walks the compiled ``PlanStep``s and checks, with
no kernel execution:

* **shape flow** (V1xx) — every step's output shape re-derives from its
  input shape + layer spec, consecutive steps chain, and conv/fc
  parameter geometry matches ``infer_param_shapes``,
* **band coverage** (V2xx) — for every banded step (SIMD conv, fused
  conv+pool, chain, Pallas pool) the geometry is re-resolved through
  the SAME kernel resolvers the dispatch path runs
  (``resolve_oh_block`` / ``resolve_ph_block`` / ``resolve_chain_block``
  via ``fusion.group_band_params``) and the per-cell interval lists
  (``kernels.band_intervals``) are proven to cover: output bands
  partition ``[0, OH)`` exactly once, every input halo band stays at or
  below the pre-padded frame origin and contains every row its output
  band's windows read, ragged last bands are equalized (the PR 3
  over-fetch regression, statically),
* **VMEM budget** (V3xx) — the modelled working set of the resolved
  cell AND of the one-final-row floor cell are audited against the
  budget the planner admitted with.  Severity is ``error`` only where
  the bust would bind: the Pallas path with auto band resolution; an
  explicit ``oh_block`` override downgrades to ``warning`` (the user
  asked for it) and the XLA path to ``info`` (no VMEM ceiling).

``compile_plan(verify=True)`` — the default — raises
``PlanVerificationError`` on any error finding, so every engine
construction and ``deploy.load_model`` self-checks before a batch
arrives.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from repro.analysis.findings import (  # noqa: F401  (re-exported for
    Finding,                           # compile_plan's deferred import)
    PlanVerificationError,
)
from repro.core.fusion import (
    _conv_out_hw,
    _pool_out_hw,
    conv_band_params,
    group_band_params,
)
from repro.core.methods import Method
from repro.core.netdefs import NetworkDef
from repro.core.plan import ExecutionPlan, PlanStep, infer_param_shapes

#: methods that band their output rows on the Pallas path (seq_ref and
#: basic_parallel run whole frames per grid cell — nothing to cover)
_BANDED_METHODS = frozenset({
    Method.BASIC_SIMD, Method.ADVANCED_SIMD_4, Method.ADVANCED_SIMD_8,
})


def check_band_coverage(geo: dict, step: str, *,
                        equalized: bool = True) -> List[Finding]:
    """Pure coverage checker over one resolved band geometry (the dict
    shape of ``fusion.group_band_params``).  Everything here is
    arithmetic over the interval lists — the unit the mutation tests
    drive directly with hand-built geometries."""
    from repro.kernels.conv2d import kernels as K

    blk, n_tiles, total = geo["blk"], geo["n_tiles"], geo["total"]
    # the sliding-window carry cell re-uses ``carry`` input rows from
    # VMEM scratch each band step, so the FRESH fetch of logical band t
    # starts ``carry`` rows below the classic halo band (the carried
    # rows were fetched — and convolved — by the previous physical step;
    # step 0 is the sacrificial seed that fills the scratch)
    carry = geo.get("carry", 0)
    out_iv, in_iv = K.band_intervals(n_tiles, blk, total, geo["row_step"],
                                     geo["band"],
                                     base=geo["in_base"] + carry)
    findings: List[Finding] = []
    # V201: output bands partition [0, total) exactly once
    pos = 0
    contiguous = True
    for start, rows in out_iv:
        if start != pos or rows < 0:
            contiguous = False
            break
        pos = start + rows
    if not contiguous or pos != total:
        findings.append(Finding(
            "error", step, "V201",
            f"output bands {out_iv} do not partition [0, {total}) "
            f"(gap/overlap or wrong coverage)"))
    # V205: the scalars must agree with the effective-conv model (the
    # carry cell's fresh band is the classic band minus the carried rows,
    # and its sacrificial seed adds one physical grid step)
    want_band = (blk - 1) * geo["stride_eff"] + geo["window_eff"] - carry
    want_step = blk * geo["stride_eff"]
    want_phys = n_tiles + (1 if carry else 0)
    if (geo["band"] != want_band or geo["row_step"] != want_step
            or geo.get("steps", n_tiles) != want_phys):
        findings.append(Finding(
            "error", step, "V205",
            f"band={geo['band']} row_step={geo['row_step']} "
            f"steps={geo.get('steps', n_tiles)} inconsistent with "
            f"blk={blk} stride_eff={geo['stride_eff']} "
            f"window_eff={geo['window_eff']} carry={carry} "
            f"(want band={want_band}, row_step={want_step}, "
            f"steps={want_phys})"))
    # V202: no halo band may start above the pre-padded frame origin
    for t, (start, _rows) in enumerate(in_iv):
        if start < geo["in_base"]:
            findings.append(Finding(
                "error", step, "V202",
                f"band {t} input start {start} is above the pre-padded "
                f"frame origin {geo['in_base']}"))
    # V203: each halo band must contain every row its output windows read
    # — in carry mode the first ``carry`` rows of the demand are served
    # from scratch (band t-1's tail; band 0's from the seed step), so the
    # fresh fetch must start EXACTLY ``carry`` rows into the demand: any
    # other offset consumes stale or misaligned scratch rows
    for t, ((o0, o_rows), (i0, i_rows)) in enumerate(zip(out_iv, in_iv)):
        if o_rows <= 0:
            continue
        need_lo = geo["in_base"] + o0 * geo["stride_eff"]
        need_hi = (geo["in_base"] + (o0 + o_rows - 1) * geo["stride_eff"]
                   + geo["window_eff"])
        if carry:
            if i0 - need_lo != carry or i0 + i_rows < need_hi:
                findings.append(Finding(
                    "error", step, "V203",
                    f"band {t} stages fresh input rows [{i0}, "
                    f"{i0 + i_rows}) + {carry} carried rows but its "
                    f"output rows [{o0}, {o0 + o_rows}) read "
                    f"[{need_lo}, {need_hi}) — carry misalignment or "
                    f"under-fetch"))
        elif i0 > need_lo or i0 + i_rows < need_hi:
            findings.append(Finding(
                "error", step, "V203",
                f"band {t} stages input rows [{i0}, {i0 + i_rows}) but its "
                f"output rows [{o0}, {o0 + o_rows}) read "
                f"[{need_lo}, {need_hi}) — under-fetch"))
    # V204: ragged last band must be equalized to its fair share
    if equalized and blk != -(-total // n_tiles):
        findings.append(Finding(
            "error", step, "V204",
            f"blk={blk} over {n_tiles} bands of {total} rows is not "
            f"equalized (fair share {-(-total // n_tiles)}): the ragged "
            f"last band fetches mostly-pad input rows"))
    return findings


def step_band_params(plan: ExecutionPlan,
                     step: PlanStep) -> Tuple[Optional[dict], bool]:
    """The resolved band geometry of one step (``None`` for steps that
    do not band) and whether its resolver equalizes the ragged band.
    Fused/chain steps read ``fusion.group_band_params``; unfused SIMD
    convs and Pallas pools re-derive the same fields from the kernel
    resolvers their dispatch path runs."""
    from repro.kernels.conv2d import kernels as K
    from repro.kernels.conv2d.ops import SUBLANES

    if step.kind in ("fused", "chain", "residual"):
        kw = step.kwargs or {}
        return (group_band_params(step.group, step.method, step.in_shape,
                                  step.oh_block,
                                  pool_carry=kw.get("pool_carry"),
                                  lrn_oc_block=kw.get("lrn_oc_block")),
                step.kind != "residual")
    if step.kind == "conv" and step.method in _BANDED_METHODS:
        return (conv_band_params(step.spec, step.method, step.in_shape,
                                 step.oh_block), False)
    if step.kind == "pool" and plan.use_pallas:
        from repro.kernels.pool2d.kernels import auto_oh_block_pool

        spec = step.spec
        c, h, w = step.in_shape
        kh, _kw = spec.kernel
        sy = spec.stride[0]
        oh, ow = _pool_out_hw(h, w, spec)
        # the kernel pools the padded frame with VALID windows
        hp, wp = h + 2 * spec.padding[0], w + 2 * spec.padding[1]
        cp = -(-c // SUBLANES) * SUBLANES
        blk = auto_oh_block_pool(oh, ow, wp, cp, kh, sy)
        blk = max(1, min(blk, oh))
        return ({
            "kind": "pool", "blk": blk, "n_tiles": -(-oh // blk),
            "total": oh, "band": K._band_rows(blk, kh, sy),
            "row_step": blk * sy, "in_base": 0, "stride_eff": sy,
            "carry": 0, "steps": -(-oh // blk),
            "window_eff": kh, "padded_h": hp,
            "cell_bytes": K.conv_cell_bytes(blk, ow, wp, cp, kh, _kw, sy, 0,
                                            im2col=False),
            "floor_bytes": K.conv_cell_bytes(1, ow, wp, cp, kh, _kw, sy, 0,
                                             im2col=False),
            "budget": K.VMEM_BUDGET_BYTES, "out_hw": [oh, ow],
        }, False)
    return None, False


def _derived_out_shape(step: PlanStep) -> Optional[Tuple[int, ...]]:
    cur = tuple(step.in_shape)
    if step.kind == "conv":
        _, h, w = cur
        h, w = _conv_out_hw(h, w, step.spec)
        return (step.spec.out_channels, h, w)
    if step.kind in ("fused", "chain", "residual"):
        _, h, w = cur
        for cv in step.group.convs:
            h, w = _conv_out_hw(h, w, cv)
        if step.group.pool is not None:
            h, w = _pool_out_hw(h, w, step.group.pool)
        return (step.group.convs[-1].out_channels, h, w)
    if step.kind == "pool":
        c, h, w = cur
        h, w = _pool_out_hw(h, w, step.spec)
        return (c, h, w)
    if step.kind == "flatten":
        return ((int(cur[0] * cur[1] * cur[2]),) if len(cur) == 3 else cur)
    if step.kind == "fc":
        return (step.spec.out_channels,)
    if step.kind in ("lrn", "relu", "softmax", "add"):
        return cur
    return None


def _shape_findings(step: PlanStep, label: str, cur: Tuple[int, ...],
                    added: dict, shapes: dict) -> List[Finding]:
    """``cur`` is the shape of the tensor the step reads first, and
    ``added`` the shapes of the tensors an add or residual step sums
    into its output, by name."""
    findings: List[Finding] = []
    if tuple(step.in_shape) != tuple(cur):
        findings.append(Finding(
            "error", label, "V102",
            f"step input shape {tuple(step.in_shape)} != upstream "
            f"activation {tuple(cur)}"))
    want = _derived_out_shape(step)
    if want is not None:
        if any(d < 1 for d in want):
            findings.append(Finding(
                "error", label, "V101",
                f"derived output shape {want} has a non-positive dim "
                f"(kernel/pool larger than its input)"))
        elif tuple(step.out_shape) != want:
            findings.append(Finding(
                "error", label, "V101",
                f"step output shape {tuple(step.out_shape)} != derived "
                f"{want}"))
    for name, shape in added.items():
        if tuple(shape) != tuple(step.out_shape):
            findings.append(Finding(
                "error", label, "V101",
                f"adds {name} of shape {tuple(shape)} to an output of "
                f"shape {tuple(step.out_shape)}"))
    # parameter geometry vs infer_param_shapes
    if step.kind == "conv":
        kh, kw = step.spec.kernel
        want_w = (step.spec.out_channels, step.in_shape[0], kh, kw)
        if shapes.get(step.spec.name) != want_w:
            findings.append(Finding(
                "error", label, "V103",
                f"conv {step.spec.name} weight {shapes.get(step.spec.name)} "
                f"!= step-derived {want_w}"))
    elif step.kind in ("fused", "chain", "residual"):
        c = step.in_shape[0]
        for cv in step.group.convs:
            kh, kw = cv.kernel
            want_w = (cv.out_channels, c, kh, kw)
            if shapes.get(cv.name) != want_w:
                findings.append(Finding(
                    "error", label, "V103",
                    f"conv {cv.name} weight {shapes.get(cv.name)} != "
                    f"step-derived {want_w}"))
            c = cv.out_channels
    elif step.kind == "fc":
        d_in = (int(step.in_shape[0] * step.in_shape[1] * step.in_shape[2])
                if len(step.in_shape) == 3 else int(step.in_shape[0]))
        want_w = (d_in, step.spec.out_channels)
        if step.d_in != d_in or shapes.get(step.spec.name) != want_w:
            findings.append(Finding(
                "error", label, "V103",
                f"fc {step.spec.name}: weight {shapes.get(step.spec.name)} "
                f"/ step d_in {step.d_in} != step-derived {want_w}"))
    return findings


def _budget_findings(geo: dict, label: str, plan: ExecutionPlan,
                     explicit_block: bool) -> List[Finding]:
    # the planner admits fused/chain groups against the compile-time
    # vmem_budget override; unfused conv/pool cells always auto-fit to
    # the kernel-module constants, so the override does not apply there
    budget = geo["budget"]
    if plan.vmem_budget is not None and geo["kind"] in ("fused", "chain"):
        budget = plan.vmem_budget
    if not plan.use_pallas:
        sev = "info"   # the XLA analogue has no VMEM ceiling
    elif explicit_block:
        sev = "warning"  # the user pinned the band; respect but flag
    else:
        sev = "error"  # auto resolution must always fit
    findings: List[Finding] = []
    rule = "V302" if geo["kind"] == "chain" else "V301"
    if geo["cell_bytes"] > budget:
        findings.append(Finding(
            sev, label, rule,
            f"resolved cell (blk={geo['blk']}) models "
            f"{geo['cell_bytes']} B > budget {budget} B"))
    if geo["floor_bytes"] > budget:
        findings.append(Finding(
            sev, label, "V303",
            f"one-final-row floor cell models {geo['floor_bytes']} B > "
            f"budget {budget} B — the planner should not have admitted "
            f"this step"))
    return findings


def verify_plan(plan: ExecutionPlan, net: Optional[NetworkDef] = None,
                input_shape: Optional[Tuple[int, int, int]] = None,
                ) -> List[Finding]:
    """All findings for ``plan``, most severe first.  ``net`` /
    ``input_shape`` default to the plan's own — pass them to check a
    plan against an independently-trusted definition (deploy does)."""
    net = net if net is not None else plan.net
    # each step reads tensors by name: the frame, or an earlier step's
    # output (V102 where none wrote it)
    produced = {"input": tuple(input_shape if input_shape is not None
                               else net.input_shape)}
    shapes = infer_param_shapes(net)
    findings: List[Finding] = []
    for idx, step in enumerate(plan.steps):
        label = f"step{idx}:{step.label}"
        unknown = [n for n in step.inputs if n not in produced]
        if unknown or not step.inputs:
            findings.append(Finding(
                "error", label, "V102",
                f"step reads {unknown or 'nothing'}: no earlier step "
                f"writes it"))
        cur = produced.get(step.inputs[0] if step.inputs else None,
                           tuple(step.in_shape))
        added = {n: produced[n] for n in step.inputs[1:] if n in produced}
        findings += _shape_findings(step, label, cur, added, shapes)
        geo, equalized = step_band_params(plan, step)
        if geo is not None:
            findings += check_band_coverage(geo, label, equalized=equalized)
            findings += _budget_findings(geo, label, plan,
                                         step.oh_block is not None)
        produced[step.output] = tuple(step.out_shape)
    # headless nets (tests, feature extractors) end wherever they end; a
    # classifier tail must land exactly on the class distribution
    cur = tuple(plan.steps[-1].out_shape) if plan.steps else ()
    if (plan.steps and plan.steps[-1].kind in ("fc", "softmax")
            and tuple(cur) != (net.num_classes,)):
        findings.append(Finding(
            "warning", "plan", "V102",
            f"final activation {tuple(cur)} != (num_classes="
            f"{net.num_classes},)"))
    order = {"error": 0, "warning": 1, "info": 2}
    findings.sort(key=lambda f: order[f.severity])
    return findings
