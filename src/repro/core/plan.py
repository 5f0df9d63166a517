"""Compile-once ExecutionPlan IR — the engine's executor spine.

``compile_plan(net, ...)`` lowers a ``NetworkDef`` into a typed sequence
of resolved ``PlanStep``s, making every decision the old interpreting
``forward`` loop used to re-make per trace:

* **shape resolution** — each step carries its pre-resolved input and
  output activation shape (``(C, H, W)`` while spatial, ``(D,)`` once
  flattened); an fc straight after a conv/pool resolves its ``d_in`` to
  the whole ``c*h*w`` activation and is flagged ``pre_flatten`` so the
  executor reshapes without inspecting ``x.ndim`` semantics,
* **standalone-ReLU folding** — a standalone ``relu`` layer following a
  conv/fc/pool is folded into that step's epilogue at compile time (the
  folded layer's name joins the step's ``names`` so instrumentation
  still sees it); with ``fuse_relu=False`` it stays its own step,
* **super-layer fusion** — ``repro.core.fusion.plan_fusion`` runs once
  at compile time; each ``FusedLayerSpec`` becomes one ``fused`` (single
  conv + pool epilogue) or ``chain`` (multi-conv, VMEM-resident halo)
  step carrying its resolved method, ``oh_block``, and LRN constants,
* **method / oh_block resolution** — per-layer overrides are read off
  the knob maps once; steps store the resolved values.

``ExecutionPlan.execute`` is a thin loop over step executors — no
fusion, folding, or shape decision happens at trace time, so a plan is
compiled once and re-traced cheaply per batch bucket.  The plan also
answers ``fusion_report()`` (executed Pallas geometry) straight off its
steps, and iterating an ``ExecutionPlan`` yields the underlying
``LayerSpec``/``FusedLayerSpec`` items so planner-level helpers
(``fusion_summary``) keep working on it.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Callable, Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.fusion import (
    FUSABLE_METHODS,
    CostGate,
    FusedLayerSpec,
    PlanItem,
    group_geometry,
    layer_shapes,
    plan_fusion,
)
from repro.core.methods import (
    Method,
    conv2d,
    conv2d_chain_fused,
    conv2d_pool_fused,
    fc_fused,
    fc_seq_ref,
    pallas_method_name,
)
from repro.core.netdefs import (
    LayerSpec,
    NetworkDef,
    layer_inputs,
    layer_readers,
)

Shape = Tuple[int, ...]


def infer_param_shapes(net: NetworkDef) -> Dict[str, Tuple]:
    """Propagate shapes through the net's graph to size conv/fc
    parameters (conv: OIHW weight shape; fc: ``(d_in, d_out)``).  An fc
    fed a spatial activation (no flatten layer) consumes the WHOLE
    ``c*h*w`` activation, not just the channel count."""
    shapes = layer_shapes(net)
    inputs = layer_inputs(net)
    params: Dict[str, Tuple] = {}
    for spec in net.layers:
        src = shapes[inputs[spec.name][0]]
        if spec.kind == "conv":
            kh, kw = spec.kernel
            params[spec.name] = (spec.out_channels, src[0], kh, kw)
        elif spec.kind == "fc":
            d_in = 1
            for d in src:
                d_in *= int(d)
            params[spec.name] = (d_in, spec.out_channels)
    return params


#: the conv methods worth sweeping per layer: the three fusable SIMD
#: rungs (seq_ref / basic_parallel are reference semantics, never faster)
SIMD_METHODS: Tuple[Method, ...] = tuple(
    m for m in Method if m in FUSABLE_METHODS)

#: ladder methods with no Pallas kernel Mosaic compiles: the §4.2 kernel
#: keeps channels off the lane axis, and broadcasting its per-channel
#: weights over the spatial map needs a vector reshape Mosaic lacks
NO_TPU_KERNEL: Tuple[Method, ...] = (Method.BASIC_PARALLEL,)

#: default per-layer band-override candidates the autotuner tries on top
#: of the resolver's auto sizing (clipped per layer to its output height)
OH_BLOCK_CANDIDATES: Tuple[int, ...] = (4, 8, 16, 32, 64)


def knob_space(net: NetworkDef, *,
               methods: Tuple[Method, ...] = SIMD_METHODS,
               oh_blocks: Tuple[int, ...] = OH_BLOCK_CANDIDATES,
               ) -> Dict[str, Dict[str, list]]:
    """The per-layer candidate knob grid an offline autotuner sweeps:
    ``{layer_name: {"methods": [...], "oh_blocks": [None, ...],
    "fuse": [True, False]}}``.

    Shapes are propagated through the net so each conv's ``oh_blocks``
    list is clipped to bands strictly smaller than its output height
    (``None`` — the resolver's VMEM-model auto sizing — always leads).
    Conv layers also expose the second-generation fused-cell axes:
    ``pool_carry`` (sliding-window pool accumulator; None = auto) and
    ``lrn_oc_block`` (two-pass channel-halo LRN blocking; None = auto)
    bind when the conv leads a fused conv+pool group, ``oc_block_final``
    binds when the conv ENDS a fused chain (final-stage oc-grid
    blocking).  Pool and LRN layers expose only the ``fuse`` axis (their
    method/band geometry is owned by the group they fuse into); fc and
    the other pointwise tail layers expose no tunable axis today.
    """
    space: Dict[str, Dict[str, list]] = {}
    shapes = layer_shapes(net)
    for spec in net.layers:
        if spec.kind == "conv":
            oh = shapes[spec.name][1]
            space[spec.name] = {
                "methods": list(methods),
                "oh_blocks": [None] + [b for b in oh_blocks if b < oh],
                "fuse": [True, False],
                "pool_carry": [None, False],
                "lrn_oc_block": [None, True, False],
                "oc_block_final": [None, 4, 8],
            }
        elif spec.kind in ("pool", "lrn"):
            space[spec.name] = {"fuse": [True, False]}
    return space


@dataclass(frozen=True)
class PlanStep:
    """One resolved executor step.  ``kind`` selects the executor:
    conv | fused (single conv + pool epilogue) | chain (multi-conv) |
    residual (single conv + add epilogue) | pool | lrn | flatten | fc |
    relu | add | softmax.  ``names`` are the original layer names the
    step covers (folded standalone ReLUs included) —
    ``execute(collect=...)`` records the step's output under every one
    of them, matching the per-layer interpreter.  ``inputs`` are the
    tensors the step reads (``"input"``: the frame; a residual step's
    shortcut last) and ``output`` the one it writes, by name;
    ``in_shape`` is the first input's shape."""
    kind: str
    names: Tuple[str, ...]
    in_shape: Shape
    out_shape: Shape
    inputs: Tuple[str, ...]
    output: str
    spec: Optional[LayerSpec] = None          # per-layer steps
    group: Optional[FusedLayerSpec] = None    # fused / chain steps
    method: Optional[Method] = None           # conv / fc / fused / chain
    oh_block: Optional[int] = None            # conv / fused / chain
    relu: bool = False                        # folded epilogue ReLU
    pre_flatten: bool = False                 # fc fed a spatial activation
    d_in: Optional[int] = None                # fc input features
    kwargs: Optional[Mapping] = None          # fused/chain tail constants

    @property
    def label(self) -> str:
        """The step's name, ``"+".join(names)`` (``conv1+pool1+norm1``):
        ``execute`` runs the step under ``jax.named_scope(label)``, so
        every operation of the compiled program names its step in its
        ``op_name`` (see ``CNNEngine.op_steps``)."""
        return "+".join(self.names)


def _lrn_kwargs(lrn: Optional[LayerSpec]) -> Dict:
    return dict(
        lrn_n=lrn.lrn_n if lrn is not None else None,
        lrn_alpha=lrn.lrn_alpha if lrn is not None else 1e-4,
        lrn_beta=lrn.lrn_beta if lrn is not None else 0.75,
        lrn_k=lrn.lrn_k if lrn is not None else 1.0)


# -- step executors (dispatch on PlanStep.kind; every decision is already
# resolved in the step, the executors only route tensors) -------------------


def _pool(x, spec: LayerSpec, use_pallas: bool = False, relu: bool = False):
    """Pooling over the frame padded by ``spec.padding`` (-inf for max,
    zeros counted in the divisor for avg); ``relu`` is the folded
    standalone activation (applied on top of the spec's own)."""
    do_relu = spec.relu or relu
    if use_pallas:
        from repro.kernels.pool2d import ops as pool_ops

        return pool_ops.pool2d(x, spec.kernel, spec.stride, spec.pool_kind,
                               relu=do_relu, padding=spec.padding)
    from repro.kernels.pool2d.ref import pool2d_ref

    return pool2d_ref(x, spec.kernel, spec.stride, spec.pool_kind,
                      relu=do_relu, padding=spec.padding)


def _lrn(x, spec: LayerSpec):
    """Local response normalization across channels (AlexNet-style): one
    channel-axis ``reduce_window`` (fp32) instead of ``lrn_n`` slice+adds."""
    sq = x.astype(jnp.float32) ** 2
    n = spec.lrn_n
    # window [c - n//2, c + (n-1)//2]: asymmetric padding keeps the output
    # at C channels for even n too (symmetric pad would yield C+1)
    acc = jax.lax.reduce_window(
        sq, 0.0, jax.lax.add, (1, n, 1, 1), (1, 1, 1, 1),
        ((0, 0), (n // 2, n - 1 - n // 2), (0, 0), (0, 0)),
    )
    denom = (spec.lrn_k + spec.lrn_alpha * acc) ** spec.lrn_beta
    return (x.astype(jnp.float32) / denom).astype(x.dtype)


def _exec_conv(plan: "ExecutionPlan", step: PlanStep, params, x):
    p = params[step.spec.name]
    return conv2d(x, p["w"], p["b"], step.method, step.spec.stride,
                  step.spec.padding, step.relu, plan.use_pallas,
                  step.oh_block)


def _exec_fused(plan: "ExecutionPlan", step: PlanStep, params, x):
    # single conv + pool[+LRN]: the oc-blocked epilogue kernel
    g = step.group
    p = params[g.conv.name]
    return conv2d_pool_fused(
        x, p["w"], p["b"], step.method, g.conv.stride, g.conv.padding,
        g.relu, g.pool.kernel, g.pool.stride, g.pool.pool_kind, g.pool_relu,
        plan.use_pallas, step.oh_block, **step.kwargs)


def _exec_chain(plan: "ExecutionPlan", step: PlanStep, params, x):
    # conv chain (optional pool/LRN tail): the full-width chain cell,
    # VMEM-resident halo between stages
    g = step.group
    pool = g.pool
    return conv2d_chain_fused(
        x, tuple(params[cv.name]["w"] for cv in g.convs),
        tuple(params[cv.name]["b"] for cv in g.convs),
        step.method, tuple(cv.stride for cv in g.convs),
        tuple(cv.padding for cv in g.convs), g.relus,
        pool_kernel=pool.kernel if pool is not None else None,
        pool_stride=pool.stride if pool is not None else None,
        pool_kind=pool.pool_kind if pool is not None else "max",
        pool_relu=g.pool_relu, use_pallas=plan.use_pallas,
        oh_block=step.oh_block, **step.kwargs)


def _exec_residual(plan: "ExecutionPlan", step: PlanStep, params, x,
                   shortcut):
    # relu?(conv + b + shortcut): the single-conv cell's add epilogue
    from repro.kernels.conv2d import ops as conv_ops

    cv = step.group.conv
    p = params[cv.name]
    return conv_ops.conv2d_residual(
        x, p["w"], p["b"], shortcut, cv.stride, cv.padding, step.relu,
        method=pallas_method_name(step.method, "a residual join"),
        oh_block=step.oh_block)


def _exec_add(plan, step, params, x, *rest):
    for y in rest:
        x = x + y
    return jnp.maximum(x, 0.0) if step.relu else x


def _exec_pool(plan, step, params, x):
    return _pool(x, step.spec, plan.use_pallas, relu=step.relu)


def _exec_lrn(plan, step, params, x):
    return _lrn(x, step.spec)


def _exec_flatten(plan, step, params, x):
    return x.reshape(x.shape[0], -1)


def _exec_fc(plan, step, params, x):
    if step.pre_flatten:  # fc fed a spatial activation (no flatten layer)
        x = x.reshape(x.shape[0], -1)
    p = params[step.spec.name]
    if step.method == Method.SEQ_REF:
        return fc_seq_ref(x, p["w"], p["b"], step.relu)
    return fc_fused(x, p["w"], p["b"], step.relu, plan.use_pallas)


def _exec_relu(plan, step, params, x):
    return jnp.maximum(x, 0.0)


def _exec_softmax(plan, step, params, x):
    return jax.nn.softmax(x.astype(jnp.float32), axis=-1)


_EXECUTORS: Dict[str, Callable] = {
    "conv": _exec_conv,
    "fused": _exec_fused,
    "chain": _exec_chain,
    "residual": _exec_residual,
    "pool": _exec_pool,
    "lrn": _exec_lrn,
    "flatten": _exec_flatten,
    "fc": _exec_fc,
    "relu": _exec_relu,
    "add": _exec_add,
    "softmax": _exec_softmax,
}


@dataclass(frozen=True)
class ExecutionPlan:
    """The compiled forward path: a tuple of resolved ``PlanStep``s plus
    the pre-IR ``PlanItem`` sequence (iterating the plan yields the
    items, so ``fusion_summary`` and planner-level introspection work on
    an ``ExecutionPlan`` unchanged)."""
    net: NetworkDef
    fuse: bool
    use_pallas: bool
    steps: Tuple[PlanStep, ...]
    items: Tuple[PlanItem, ...]
    #: the vmem_budget override compile_plan was called with (None = the
    #: kernel-module defaults) — recorded so the static verifier audits
    #: the same ceiling the fusion planner admitted against
    vmem_budget: Optional[int] = None

    def __iter__(self):
        return iter(self.items)

    def __len__(self) -> int:
        return len(self.items)

    def execute(self, params, x, collect: Optional[dict] = None):
        """x: [N, C, H, W].  A thin loop over the step executors — every
        fusion/folding/shape decision was resolved at compile time.  Each
        step reads its inputs by name and traces inside
        ``jax.named_scope(step.label)``, which costs nothing at run time;
        a tensor is dropped after its last reader."""
        last = {n: i for i, s in enumerate(self.steps) for n in s.inputs}
        live = {"input": x}
        for i, step in enumerate(self.steps):
            args = [live[n] for n in step.inputs]
            with jax.named_scope(step.label):
                x = _EXECUTORS[step.kind](self, step, params, *args)
            for n in step.inputs:
                if last[n] == i:
                    live.pop(n, None)
            live[step.output] = x
            if collect is not None:
                for n in step.names:
                    collect[n] = x
        return x

    def fusion_report(self) -> List[dict]:
        """Executed geometry of every fused group, read straight off the
        plan steps (each already carries its resolved input shape, method
        and band override) — see ``fusion.group_geometry``; a residual
        group's row names its shortcut tensor."""
        return [group_geometry(
                    s.group, s.method, s.in_shape, s.oh_block,
                    pool_carry=(s.kwargs or {}).get("pool_carry"),
                    lrn_oc_block=(s.kwargs or {}).get("lrn_oc_block"))
                for s in self.steps
                if s.kind in ("fused", "chain", "residual")]

    def cost(self, model=None, batch: int = 1):
        """Modelled cost of this plan: a ``repro.core.cost.PlanCost``
        with per-step FLOPs / HBM bytes / VMEM working set and, under
        ``model`` (a fitted ``CostModel``; None = unit coefficients),
        predicted microseconds.  Deferred import — the cost model sits
        above the plan IR, not under it."""
        from repro.core.cost import plan_cost

        return plan_cost(self, model=model, batch=batch)


def compile_plan(net: NetworkDef, *,
                 method: Method = Method.ADVANCED_SIMD_8,
                 per_layer_methods: Optional[Mapping[str, Method]] = None,
                 oh_block: Optional[int] = None,
                 per_layer_oh_blocks: Optional[Mapping[str, int]] = None,
                 fuse: bool = True,
                 fuse_relu: bool = True,
                 per_layer_fuse: Optional[Mapping[str, bool]] = None,
                 per_layer_pool_carry: Optional[Mapping[str, bool]] = None,
                 per_layer_lrn_oc_block: Optional[Mapping[str, bool]] = None,
                 per_layer_oc_block_final: Optional[Mapping[str, int]] = None,
                 use_pallas: bool = False,
                 vmem_budget: Optional[int] = None,
                 cost_gate: Optional[CostGate] = None,
                 verify: bool = True) -> ExecutionPlan:
    """Lower ``net`` into an ``ExecutionPlan``.

    Subsumes the legacy interpreter's per-call work: runs the fusion
    planner (``fuse=True``; the VMEM working-set check and the residual
    joins bind on the Pallas path only), folds standalone ReLUs into the
    preceding conv/fc/pool step (``fuse_relu``) when they are its one
    reader, resolves every layer's method / ``oh_block`` override, and
    resolves the graph so each step carries the names it reads and
    writes and its input/output geometry.

    ``cost_gate`` (see ``fusion.plan_fusion``) swaps the fusion
    planner's raw VMEM budget check for a cost-model admission decision
    (``repro.core.cost.fusion_cost_gate``) — a group fuses only when the
    model scores the single dispatch faster than its per-layer ladder.

    ``per_layer_pool_carry`` / ``per_layer_lrn_oc_block`` (keyed by the
    conv LEADING a fused conv+pool group) pin that group's
    sliding-window carry / channel-halo LRN blocking (None = the kernel
    resolvers' auto rule); ``per_layer_oc_block_final`` (keyed by the
    conv ENDING a fused chain) forces the chain's final-stage oc block
    (ignored when the chain keeps an LRN tail — the kernel rejects the
    combination).

    ``verify=True`` (the default) runs the static plan verifier
    (``repro.analysis.verifier.verify_plan``) over the compiled plan and
    raises ``PlanVerificationError`` on any error-severity finding —
    every engine construction and ``deploy.load_model`` self-checks its
    geometry before the first batch arrives.
    """
    per_layer_methods = per_layer_methods or {}
    per_layer_oh_blocks = per_layer_oh_blocks or {}
    per_layer_pool_carry = per_layer_pool_carry or {}
    per_layer_lrn_oc_block = per_layer_lrn_oc_block or {}
    per_layer_oc_block_final = per_layer_oc_block_final or {}

    def method_for(name: str) -> Method:
        return per_layer_methods.get(name, method)

    def ohb_for(name: str) -> Optional[int]:
        return per_layer_oh_blocks.get(name, oh_block)

    if fuse:
        no = frozenset(n for n, v in (per_layer_fuse or {}).items() if not v)
        items: List[PlanItem] = plan_fusion(
            net, method_for=method_for, no_fuse=no, fuse_relu=fuse_relu,
            vmem_budget=vmem_budget, vmem_check=use_pallas,
            cost_gate=cost_gate, residual=use_pallas)
    else:
        items = list(net.layers)

    shapes = layer_shapes(net)
    inputs = layer_inputs(net)
    readers = layer_readers(net)
    steps: List[PlanStep] = []
    final_items: List[PlanItem] = []
    for it in items:
        if isinstance(it, FusedLayerSpec):
            ins = inputs[it.conv.name]
            kw = _lrn_kwargs(it.lrn)
            if it.add is not None:
                kind, kw = "residual", None
                ins += (it.shortcut,)
            elif len(it.convs) > 1:
                kind = "chain"
                # explicit final-stage oc block (keyed by the LAST conv —
                # the chain cell's band lives in final-stage rows too)
                # overrides the planner's admission-ladder choice; an LRN
                # tail keeps full width (the kernel rejects the combo)
                obf = per_layer_oc_block_final.get(it.convs[-1].name)
                if obf is not None and it.lrn is None:
                    it = replace(it, oc_block_final=obf)
                kw["oc_block_final"] = it.oc_block_final
            else:
                kind = "fused"
                kw["pool_carry"] = per_layer_pool_carry.get(it.conv.name)
                kw["lrn_oc_block"] = per_layer_lrn_oc_block.get(it.conv.name)
            # a chain cell's band is defined in FINAL-stage rows, so the
            # last conv's oh_block override is the one that maps onto it
            steps.append(PlanStep(
                kind, it.names, shapes[ins[0]], shapes[it.names[-1]],
                ins, it.names[-1], group=it,
                method=method_for(it.conv.name),
                oh_block=ohb_for(it.convs[-1].name),
                relu=it.add is not None and it.add.relu, kwargs=kw))
            final_items.append(it)
            continue
        spec = it
        final_items.append(spec)
        ins = inputs[spec.name]
        in_shape, out_shape = shapes[ins[0]], shapes[spec.name]
        step = PlanStep(spec.kind, (spec.name,), in_shape, out_shape, ins,
                        spec.name, spec=spec)
        if spec.kind == "conv":
            step = replace(step, method=method_for(spec.name),
                           oh_block=ohb_for(spec.name), relu=spec.relu)
        elif spec.kind in ("pool", "add"):
            step = replace(step, relu=spec.relu)
        elif spec.kind == "fc":
            d_in = 1
            for d in in_shape:
                d_in *= int(d)
            step = replace(step, method=method_for(spec.name),
                           relu=spec.relu, pre_flatten=len(in_shape) == 3,
                           d_in=d_in)
        elif spec.kind == "relu":
            # standalone-ReLU folding, resolved HERE not at trace time: a
            # relu that alone reads a conv/fc/pool step's output joins
            # that step's epilogue (its name joins the step so collect
            # still sees it)
            prev = steps[-1] if steps else None
            if (fuse_relu and prev is not None
                    and prev.kind in ("conv", "fc", "pool")
                    and ins == (prev.output,)
                    and readers.get(prev.output) == [spec.name]):
                steps[-1] = replace(prev, relu=True,
                                    names=prev.names + (spec.name,),
                                    output=spec.name)
                continue
        elif spec.kind not in ("lrn", "flatten", "softmax"):
            raise ValueError(spec.kind)
        steps.append(step)
    if use_pallas and jax.default_backend() == "tpu":
        refused = sorted({s.method.value for s in steps
                          if s.kind == "conv" and s.method in NO_TPU_KERNEL})
        if refused:
            raise NotImplementedError(
                f"{net.name}: no TPU Pallas kernel for method(s) {refused}; "
                "pick another method or use_pallas=False")
    plan = ExecutionPlan(net=net, fuse=fuse, use_pallas=use_pallas,
                         steps=tuple(steps), items=tuple(final_items),
                         vmem_budget=vmem_budget)
    if verify:
        # deferred import: analysis imports this module at its top level
        from repro.analysis.verifier import PlanVerificationError, verify_plan

        errors = [f for f in verify_plan(plan) if f.severity == "error"]
        if errors:
            raise PlanVerificationError(errors)
    return plan
