"""Per-``PlanStep`` analytic cost model for compiled ExecutionPlans.

CNNdroid's whole thesis is that the right per-layer execution choice
(method, tiling, fusion) separates real-time from prohibitive; this
module replaces the planner's point heuristics with the per-layer
latency model of "Modeling the Resource Requirements of CNNs on Mobile
Devices" (arxiv 1709.09503), adapted to the plan IR.  Every step is
reduced to three measurable resources:

* **FLOPs** — the arithmetic the step must do (2 × MACs for conv/fc via
  ``kernels.conv_macs``; window/pointwise op counts for the tail kinds),
  attributed to a coefficient bucket: one per conv ladder method (the
  restagings differ in achieved throughput far more than in streamed
  bytes), one shared ``fc`` bucket (the fc path is method-invariant),
  and ``other`` for the cheap pool/lrn/softmax tail,
* **HBM bytes streamed** — input activation + weights + output, charged
  physically: a fused/chain step streams NO intermediate activations
  (the fusion win, visible to the model), and on the Pallas path the
  input charge is multiplied by ``kernels.band_overfetch_factor`` (the
  halo re-fetch cost of the resolved band geometry, so ``oh_block``
  choices move the prediction),
* **VMEM working set** — the resolved grid cell's modelled bytes via
  the existing ``conv_cell_bytes`` / ``fused_cell_bytes`` /
  ``chain_cell_bytes`` accounting (read off the same resolver-derived
  geometry the static verifier audits).  Not a latency term — it is the
  feasibility resource the autotuner trades against the overfetch
  factor.

Predicted microseconds come from fitted per-backend coefficients
(``us_per_gflop[bucket]``, ``us_per_gb``, ``dispatch_us``) loaded from a
committed ``COST_MODEL.json``, calibrated against ``BENCH_network.json``
history by ``benchmarks/cost_fit.py`` (non-negative least squares with a
deterministic fit/holdout split) and regression-gated in CI by
``tools/cost_validate.py`` (Spearman rank correlation between predicted
and measured ``us_per_call``).

Deliberate simplifications (documented so the fit absorbs them): weights
are charged once per dispatch, not per grid cell (the pipeline keeps the
grid-invariant block resident); the per-layer ladder's own band halos
are charged factor 1 (a conv's ``kh − sy`` overlap rows are noise next
to a chain's composed halo — this slightly favours the UNFUSED
alternative, so the fusion gate only fuses on a genuine modelled win);
im2col patch staging is not charged as HBM traffic (it is VMEM-resident
on the Pallas path and fused into the matmul by XLA) — the per-method
FLOP coefficients absorb the restaging cost.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, replace
from pathlib import Path
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

from repro.core.fusion import (
    FUSABLE_METHODS,
    FusedLayerSpec,
    _conv_out_hw,
    _pool_out_hw,
    group_band_params,
    group_fits_vmem,
)
from repro.core.methods import Method
from repro.core.netdefs import LayerSpec
from repro.core.plan import ExecutionPlan, PlanStep

ITEMSIZE = 4  # fp32 staging end to end

def fused_flop_key(method: Method) -> str:
    """The coefficient bucket of a fused/chain dispatch running
    ``method``.  Fused execution is a genuinely different kernel with a
    different achieved throughput (measured fused speedups are 1.4–3.6×
    — far more than its byte/dispatch savings explain), so it earns its
    own per-method coefficient instead of riding the unfused one."""
    return f"{method.value}:fused"


#: coefficient buckets FLOPs are attributed to: one per ladder method,
#: one per fusable method's FUSED restaging, one for the
#: (method-invariant) fc matmul path, one for the cheap
#: pool/lrn/softmax/relu tail work
FLOP_KEYS: Tuple[str, ...] = (
    tuple(m.value for m in Method)
    + tuple(fused_flop_key(m) for m in Method if m in FUSABLE_METHODS)
    + ("fc", "other"))

#: default committed-model location (repo root), resolved relative to
#: this file so tools work from any cwd
DEFAULT_MODEL_PATH = Path(__file__).resolve().parents[3] / "COST_MODEL.json"


# -- resources of one step ---------------------------------------------------


@dataclass(frozen=True)
class StepCost:
    """One step's modelled resources (whole-batch numbers) plus, once a
    ``CostModel`` has priced them, predicted microseconds."""
    label: str
    kind: str
    key: str            # FLOP coefficient bucket (method value/"fc"/"other")
    flops: float
    hbm_bytes: float
    vmem_bytes: int     # resolved grid-cell working set (0: un-banded)
    dispatches: int
    us: float = 0.0


@dataclass(frozen=True)
class PlanCost:
    """A whole plan's modelled cost: per-step ``StepCost`` rows plus
    aggregate views.  ``us`` is meaningful only when built through a
    fitted ``CostModel`` (unit coefficients otherwise).

    ``model_backend``/``model_fallback_from`` echo the pricing model's
    provenance so a table built from cross-backend borrowed
    coefficients says so."""
    steps: Tuple[StepCost, ...]
    batch: int
    model_backend: str = ""
    model_fallback_from: Optional[str] = None

    @property
    def flops(self) -> float:
        return sum(s.flops for s in self.steps)

    @property
    def hbm_bytes(self) -> float:
        return sum(s.hbm_bytes for s in self.steps)

    @property
    def dispatches(self) -> int:
        return sum(s.dispatches for s in self.steps)

    @property
    def us(self) -> float:
        return sum(s.us for s in self.steps)

    @property
    def flops_by_key(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for s in self.steps:
            if s.flops:
                out[s.key] = out.get(s.key, 0.0) + s.flops
        return out

    def table_markdown(self, title: str = "Plan cost") -> str:
        lines = [f"### {title} (batch {self.batch})", "",
                 "| step | kind | bucket | GFLOP | MB streamed "
                 "| VMEM KiB | pred us |",
                 "|---|---|---|---:|---:|---:|---:|"]
        for s in self.steps:
            lines.append(
                f"| {s.label} | {s.kind} | {s.key} | {s.flops / 1e9:.4f} "
                f"| {s.hbm_bytes / 1e6:.2f} | {s.vmem_bytes / 1024:.0f} "
                f"| {s.us:.1f} |")
        lines.append(f"| **total** |  |  | {self.flops / 1e9:.4f} "
                     f"| {self.hbm_bytes / 1e6:.2f} |  | {self.us:.1f} |")
        if self.model_fallback_from:
            lines += ["", f"> **Note**: no fitted coefficients for "
                          f"backend `{self.model_fallback_from}` — priced "
                          f"with the `{self.model_backend}` model "
                          f"(cross-backend fallback; ranks usually "
                          f"transfer, magnitudes do not)."]
        return "\n".join(lines)


# -- fitted coefficients -----------------------------------------------------


@dataclass(frozen=True)
class CostModel:
    """Fitted per-backend coefficients pricing the three resources.

    ``fallback_from`` records a cross-backend substitution made by
    ``load``: the backend that was REQUESTED when no committed entry
    existed for it and another backend's coefficients were returned
    instead.  ``None`` for an exact match.  Callers pricing plans for
    ranking can proceed (rank decisions usually transfer) but must be
    able to surface the substitution — a silently borrowed model looks
    identical to a calibrated one in every downstream report."""
    backend: str
    us_per_gflop: Mapping[str, float]
    us_per_gb: float
    dispatch_us: float
    fallback_from: Optional[str] = None

    def predict(self, flops_by_key: Mapping[str, float], hbm_bytes: float,
                dispatches: int) -> float:
        """Price aggregate features (a whole plan's, or one step's)."""
        us = (dispatches * self.dispatch_us
              + hbm_bytes * 1e-9 * self.us_per_gb)
        for k, f in flops_by_key.items():
            a = self.us_per_gflop.get(k)
            if a is None:
                a = self.us_per_gflop.get("other", 0.0)
            us += f * 1e-9 * a
        return us

    def step_us(self, key: str, flops: float, hbm_bytes: float,
                dispatches: int) -> float:
        return self.predict({key: flops}, hbm_bytes, dispatches)

    @staticmethod
    def unit(backend: str = "unit") -> "CostModel":
        """Unit coefficients: resource accounting without calibration
        (1 us per GFLOP / per GB / per dispatch).  Useful for resource
        comparisons when no committed model applies."""
        return CostModel(backend=backend,
                         us_per_gflop={k: 1.0 for k in FLOP_KEYS},
                         us_per_gb=1.0, dispatch_us=1.0)

    def to_dict(self) -> dict:
        return {"us_per_gflop": dict(self.us_per_gflop),
                "us_per_gb": self.us_per_gb,
                "dispatch_us": self.dispatch_us}

    @classmethod
    def from_dict(cls, d: Mapping, backend: str) -> "CostModel":
        return cls(backend=backend,
                   us_per_gflop=dict(d["us_per_gflop"]),
                   us_per_gb=float(d["us_per_gb"]),
                   dispatch_us=float(d["dispatch_us"]))

    @classmethod
    def load(cls, path: Optional[str] = None,
             backend: str = "cpu") -> "CostModel":
        """Load the committed ``COST_MODEL.json`` (schema:
        ``{"format_version": 1, "backends": {name: coefficients}}``).
        Falls back to the first fitted backend (sorted order) when
        ``backend`` has no entry — coefficient magnitudes will be off
        cross-backend, but rank decisions usually transfer.  The
        substitution is recorded in ``fallback_from`` (the requested
        backend) so reports can flag it instead of presenting borrowed
        coefficients as calibrated."""
        p = Path(path) if path is not None else DEFAULT_MODEL_PATH
        with open(p) as f:
            data = json.load(f)
        backends = data["backends"]
        if backend in backends:
            return cls.from_dict(backends[backend], backend)
        name = sorted(backends)[0]
        return replace(cls.from_dict(backends[name], name),
                       fallback_from=backend)


# -- per-kind resource accounting --------------------------------------------


def _act_bytes(shape: Sequence[int]) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n * ITEMSIZE


def _conv_flops(spec: LayerSpec, in_shape: Tuple[int, int, int]) -> float:
    from repro.kernels.conv2d import kernels as K

    c, h, w = in_shape
    oh, ow = _conv_out_hw(h, w, spec)
    kh, kw = spec.kernel
    return 2.0 * K.conv_macs(oh, ow, c, kh, kw, spec.out_channels)


def _conv_weight_bytes(spec: LayerSpec, cin: int) -> int:
    kh, kw = spec.kernel
    return (spec.out_channels * cin * kh * kw + spec.out_channels) * ITEMSIZE


def _overfetch(geo: Optional[dict]) -> float:
    from repro.kernels.conv2d import kernels as K

    if geo is None:
        return 1.0
    # physical band-axis steps × rows fetched per step.  For the classic
    # cell steps == n_tiles and band is the full halo'd band; a
    # sliding-window carry cell runs one extra (prologue) step but each
    # step fetches `carry` fewer rows — the carried halo rows live in
    # VMEM scratch and are NOT re-streamed, which is exactly the traffic
    # win the model must see.
    steps = geo.get("steps", geo["n_tiles"])
    return K.band_overfetch_factor(steps, geo["band"], geo["padded_h"])


def _group_resources(group: FusedLayerSpec, method: Optional[Method],
                     in_shape: Tuple[int, int, int], batch: int,
                     use_pallas: bool,
                     oh_block: Optional[int] = None,
                     geo: Optional[dict] = None) -> StepCost:
    """Resources of ONE fused/chain dispatch: all conv stages' FLOPs plus
    the pool/LRN tail, input charged with the resolved band geometry's
    overfetch factor (Pallas), and NO intermediate activation traffic —
    that is precisely what fusion buys."""
    c, h, w = in_shape
    flops = 0.0
    weight_bytes = 0
    cc, hh, ww = c, h, w
    for cv in group.convs:
        flops += _conv_flops(cv, (cc, hh, ww))
        weight_bytes += _conv_weight_bytes(cv, cc)
        hh, ww = _conv_out_hw(hh, ww, cv)
        cc = cv.out_channels
    if group.pool is not None:
        ph, pw = _pool_out_hw(hh, ww, group.pool)
        flops += cc * ph * pw * group.pool.kernel[0] * group.pool.kernel[1]
        hh, ww = ph, pw
    if group.lrn is not None:
        flops += cc * hh * ww * (group.lrn.lrn_n + 4)
    flops *= batch
    if use_pallas and geo is None:
        geo = group_band_params(
            group, method if method is not None else Method.ADVANCED_SIMD_8,
            in_shape, oh_block)
    factor = _overfetch(geo) if use_pallas else 1.0
    hbm = (batch * _act_bytes(in_shape) * factor + weight_bytes
           + batch * _act_bytes((cc, hh, ww)))
    key = fused_flop_key(method if method is not None
                         else Method.ADVANCED_SIMD_8)
    kind = "chain" if len(group.convs) > 1 else "fused"
    return StepCost(label=group.name, kind=kind, key=key, flops=flops,
                    hbm_bytes=hbm,
                    vmem_bytes=(int(geo["cell_bytes"])
                                if geo and use_pallas else 0),
                    dispatches=1)


def _unfused_group_resources(group: FusedLayerSpec,
                             method: Optional[Method],
                             in_shape: Tuple[int, int, int],
                             batch: int) -> List[StepCost]:
    """The per-layer-ladder alternative of a candidate group: one
    dispatch per conv / pool / lrn, every intermediate activation
    written and re-read.  Input halos charged factor 1 (see module
    docstring) — an optimistic unfused baseline the fused candidate
    must genuinely beat."""
    key = (method.value if method is not None
           else Method.ADVANCED_SIMD_8.value)
    out: List[StepCost] = []
    c, h, w = in_shape
    for cv in group.convs:
        oh, ow = _conv_out_hw(h, w, cv)
        out.append(StepCost(
            label=cv.name, kind="conv", key=key,
            flops=batch * _conv_flops(cv, (c, h, w)),
            hbm_bytes=(batch * _act_bytes((c, h, w))
                       + _conv_weight_bytes(cv, c)
                       + batch * _act_bytes((cv.out_channels, oh, ow))),
            vmem_bytes=0, dispatches=1))
        c, h, w = cv.out_channels, oh, ow
    if group.pool is not None:
        ph, pw = _pool_out_hw(h, w, group.pool)
        out.append(StepCost(
            label=group.pool.name, kind="pool", key="other",
            flops=batch * c * ph * pw
            * group.pool.kernel[0] * group.pool.kernel[1],
            hbm_bytes=batch * (_act_bytes((c, h, w))
                               + _act_bytes((c, ph, pw))),
            vmem_bytes=0, dispatches=1))
        h, w = ph, pw
    if group.lrn is not None:
        out.append(StepCost(
            label=group.lrn.name, kind="lrn", key="other",
            flops=batch * c * h * w * (group.lrn.lrn_n + 4),
            hbm_bytes=batch * 2 * _act_bytes((c, h, w)),
            vmem_bytes=0, dispatches=1))
    return out


def step_resources(plan: ExecutionPlan, step: PlanStep,
                   batch: int = 1) -> StepCost:
    """The modelled resources of one compiled step (``us`` left 0 — a
    ``CostModel`` prices it).  Banded steps read their resolved geometry
    through ``analysis.verifier.step_band_params`` — the same resolver
    path the dispatch runs and the verifier audits."""
    # deferred: analysis imports core.plan at its top level
    from repro.analysis.verifier import step_band_params

    label = step.label
    if step.kind in ("fused", "chain", "residual"):
        geo, _ = step_band_params(plan, step)
        sc = _group_resources(step.group, step.method, step.in_shape, batch,
                              plan.use_pallas, step.oh_block, geo=geo)
        if step.kind == "residual":  # the shortcut, read once
            sc = replace(sc, kind="residual", hbm_bytes=sc.hbm_bytes
                         + batch * _act_bytes(step.out_shape))
        return replace(sc, label=label)
    if step.kind == "conv":
        geo, _ = step_band_params(plan, step)
        spec = step.spec
        c = step.in_shape[0]
        factor = _overfetch(geo) if plan.use_pallas else 1.0
        return StepCost(
            label=label, kind="conv", key=step.method.value,
            flops=batch * _conv_flops(spec, step.in_shape),
            hbm_bytes=(batch * _act_bytes(step.in_shape) * factor
                       + _conv_weight_bytes(spec, c)
                       + batch * _act_bytes(step.out_shape)),
            vmem_bytes=(int(geo["cell_bytes"])
                        if geo and plan.use_pallas else 0),
            dispatches=1)
    if step.kind == "fc":
        d_in = step.d_in
        d_out = step.spec.out_channels
        return StepCost(
            label=label, kind="fc", key="fc",
            flops=batch * 2.0 * d_in * d_out,
            hbm_bytes=(batch * d_in * ITEMSIZE
                       + (d_in * d_out + d_out) * ITEMSIZE
                       + batch * d_out * ITEMSIZE),
            vmem_bytes=0, dispatches=1)
    if step.kind == "pool":
        geo, _ = step_band_params(plan, step)
        c = step.in_shape[0]
        oh, ow = step.out_shape[1], step.out_shape[2]
        factor = _overfetch(geo) if plan.use_pallas else 1.0
        return StepCost(
            label=label, kind="pool", key="other",
            flops=batch * c * oh * ow
            * step.spec.kernel[0] * step.spec.kernel[1],
            hbm_bytes=batch * (_act_bytes(step.in_shape) * factor
                               + _act_bytes(step.out_shape)),
            vmem_bytes=(int(geo["cell_bytes"])
                        if geo and plan.use_pallas else 0),
            dispatches=1)
    if step.kind == "lrn":
        n_elems = 1
        for d in step.in_shape:
            n_elems *= int(d)
        return StepCost(
            label=label, kind="lrn", key="other",
            flops=batch * n_elems * (step.spec.lrn_n + 4),
            hbm_bytes=batch * 2 * _act_bytes(step.in_shape),
            vmem_bytes=0, dispatches=1)
    if step.kind == "add":  # bytes only: every input read, one written
        return StepCost(
            label=label, kind="add", key="other", flops=0.0,
            hbm_bytes=batch * (len(step.inputs) + 1)
            * _act_bytes(step.out_shape),
            vmem_bytes=0, dispatches=1)
    if step.kind in ("relu", "softmax"):
        n_elems = 1
        for d in step.in_shape:
            n_elems *= int(d)
        per_elem = 1 if step.kind == "relu" else 5
        return StepCost(
            label=label, kind=step.kind, key="other",
            flops=batch * n_elems * per_elem,
            hbm_bytes=batch * 2 * _act_bytes(step.in_shape),
            vmem_bytes=0, dispatches=1)
    # flatten: a metadata reshape under jit — free
    return StepCost(label=label, kind=step.kind, key="other",
                    flops=0.0, hbm_bytes=0.0, vmem_bytes=0, dispatches=0)


def plan_cost(plan: ExecutionPlan, model: Optional[CostModel] = None,
              batch: int = 1) -> PlanCost:
    """Price a whole compiled plan: per-step resources via
    ``step_resources``, microseconds via ``model`` (unit coefficients
    when None — resource totals stay exact, the us column becomes a
    resource blend rather than a latency)."""
    m = model if model is not None else CostModel.unit()
    steps = []
    for step in plan.steps:
        sc = step_resources(plan, step, batch)
        steps.append(replace(
            sc, us=m.step_us(sc.key, sc.flops, sc.hbm_bytes, sc.dispatches)))
    return PlanCost(steps=tuple(steps), batch=batch,
                    model_backend=m.backend,
                    model_fallback_from=m.fallback_from)


# -- cost-model fusion gate --------------------------------------------------


def fusion_cost_gate(model: Optional[CostModel] = None, *, batch: int = 1,
                     use_pallas: bool = False,
                     vmem_budget: Optional[int] = None):
    """Build the ``cost_gate`` callable ``plan_fusion`` accepts: a
    candidate group is admitted only when (a) its floor cell still fits
    the VMEM budget (Pallas path — same ``group_fits_vmem`` accounting
    as the raw check) and (b) the model scores the single fused dispatch
    no slower than its per-layer ladder.  This is the decision the raw
    budget check structurally cannot make: a chain that FITS but whose
    composed-halo overfetch makes it slower than running unfused is
    declined, and the planner's fallback ladder then tries the shorter
    chains."""
    m = model if model is not None else CostModel.unit()

    def gate(group: FusedLayerSpec, method: Optional[Method],
             in_shape: Tuple[int, int, int]) -> bool:
        if use_pallas and not group_fits_vmem(group, method, in_shape,
                                              vmem_budget):
            return False
        fused = _group_resources(group, method, in_shape, batch, use_pallas)
        fused_us = m.step_us(fused.key, fused.flops, fused.hbm_bytes,
                             fused.dispatches)
        unfused_us = sum(
            m.step_us(s.key, s.flops, s.hbm_bytes, s.dispatches)
            for s in _unfused_group_resources(group, method, in_shape, batch))
        return fused_us <= unfused_us

    return gate


# -- fitting + rank validation (numpy only — no scipy in the image) ----------


def _ranks(v) -> "object":
    import numpy as np

    v = np.asarray(v, dtype=float)
    order = np.argsort(v, kind="mergesort")
    ranks = np.empty(v.size, dtype=float)
    ranks[order] = np.arange(1, v.size + 1, dtype=float)
    for val in np.unique(v):  # average ties
        mask = v == val
        if mask.sum() > 1:
            ranks[mask] = ranks[mask].mean()
    return ranks


def spearman(xs: Sequence[float], ys: Sequence[float]) -> float:
    """Spearman rank correlation (average-tie ranks, Pearson of ranks).
    Returns 0.0 for degenerate inputs (n < 2 or a constant series)."""
    import numpy as np

    x = np.asarray(xs, dtype=float)
    y = np.asarray(ys, dtype=float)
    if x.size != y.size:
        raise ValueError(f"length mismatch: {x.size} vs {y.size}")
    if x.size < 2:
        return 0.0
    rx, ry = _ranks(x), _ranks(y)
    sx, sy = rx.std(), ry.std()
    if sx == 0.0 or sy == 0.0:
        return 0.0
    return float(((rx - rx.mean()) * (ry - ry.mean())).mean() / (sx * sy))


def fit_coefficients(rows: Sequence[Mapping], backend: str) -> CostModel:
    """Fit the coefficient vector from measured rows — each row
    ``{"flops_by_key": {bucket: flops}, "hbm_bytes": b, "dispatches": d,
    "us": measured}`` — by RELATIVE least squares (each row scaled by
    1/measured-us, so a lenet5 row at 2 ms and an alexnet row at 12 s
    pull equally — absolute least squares would fit only the biggest
    net) with iterative negative-column pruning (a simplified NNLS: the
    most-negative coefficient is dropped and the system re-solved until
    all remaining are ≥ 0), so every fitted coefficient prices its
    resource non-negatively and the model stays monotone for the
    autotuner.  FLOP buckets never observed in the rows (or pruned
    away) get the LARGEST fitted bucket coefficient — unmeasured
    methods look expensive, never spuriously fast."""
    import numpy as np

    keys = sorted({k for r in rows
                   for k, v in r["flops_by_key"].items() if v > 0})
    cols = list(keys) + ["__gb__", "__dispatch__"]
    A = np.zeros((len(rows), len(cols)))
    y = np.ones(len(rows))  # each row normalized by its measured us
    for i, r in enumerate(rows):
        us = float(r["us"])
        for j, k in enumerate(keys):
            A[i, j] = r["flops_by_key"].get(k, 0.0) * 1e-9 / us
        A[i, len(keys)] = float(r["hbm_bytes"]) * 1e-9 / us
        A[i, len(keys) + 1] = float(r["dispatches"]) / us
    coef = np.zeros(len(cols))
    active = list(range(len(cols)))
    while active:
        sol, _, _, _ = np.linalg.lstsq(A[:, active], y, rcond=None)
        if (sol >= 0).all():
            for j, cj in enumerate(active):
                coef[cj] = float(sol[j])
            break
        drop = int(np.argmin(sol))
        active.pop(drop)
    fitted = {k: coef[j] for j, k in enumerate(keys)}
    positive = [v for v in fitted.values() if v > 0]
    fallback = max(positive) if positive else 1.0
    us_per_gflop = {k: (fitted[k] if fitted.get(k, 0.0) > 0 else fallback)
                    for k in FLOP_KEYS}
    return CostModel(backend=backend, us_per_gflop=us_per_gflop,
                     us_per_gb=float(coef[len(keys)]),
                     dispatch_us=float(coef[len(keys) + 1]))
