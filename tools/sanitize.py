#!/usr/bin/env python
"""Sanitize every bundled netdef's Pallas dispatches — CI gate.

    PYTHONPATH=src python tools/sanitize.py [--json | --md] \
        [--fail-on-findings]

Compiles each network in ``core.netdefs.NETWORKS`` under every SIMD
method x fuse setting x backend (the exact ``tools/verify_sweep.py``
grid — plans only, nothing executes), maps each plan step onto the
padded operand shapes its Pallas dispatch would receive (mirroring
``kernels.conv2d.ops`` / ``kernels.pool2d.ops`` / ``matmul_fused.ops``),
and runs ``repro.analysis.sanitizer`` over every dispatch: an AST-level
abstract interpretation of the kernel source that proves in-bounds loads
(K101), exactly-once output coverage (K102), the fp32-accumulate /
single-downcast contract (K103), and zeroed intermediate-padding rows in
chain cells (K104) — without importing the kernel modules it audits.

This CLI additionally cross-checks the sanitizer's independently derived
band geometry against the verifier's resolver-backed derivation
(``analysis.verifier.step_band_params``): the two derivations are
N-version redundant, so any disagreement is itself a finding (K105).
Unlike ``analysis.sanitizer`` — which must stay import-independent of
the kernels — this tool MAY import the verifier: the cross-check is the
point where the two independent derivations meet.
"""
from __future__ import annotations

import argparse
import sys

from repro.analysis import sanitizer
from repro.analysis.findings import (
    Finding,
    findings_json,
    findings_markdown,
)
from repro.analysis.verifier import _BANDED_METHODS, step_band_params
from repro.core.fusion import ADVANCED_OC_BLOCK, IM2COL_METHODS
from repro.core.methods import Method
from repro.core.netdefs import NETWORKS
from repro.core.plan import compile_plan

METHODS = (Method.BASIC_SIMD, Method.ADVANCED_SIMD_4, Method.ADVANCED_SIMD_8)

#: the band-geometry fields both derivations must agree on (K105) —
#: ``carry``/``steps`` cover the sliding-window pool accumulator (carried
#: input rows between bands, physical band-axis grid steps)
GEOM_KEYS = ("kind", "blk", "n_tiles", "total", "band", "row_step",
             "in_base", "carry", "steps")

#: batch the sweep sanitizes with (any n >= 2 exercises the frame axis)
BATCH = 2

SUBLANES = 8


def _ceil8(c: int) -> int:
    return -(-c // SUBLANES) * SUBLANES


def _lrn_tuple(kwargs) -> tuple | None:
    if kwargs is None or kwargs.get("lrn_n") is None:
        return None
    return (kwargs["lrn_n"], kwargs["lrn_alpha"], kwargs["lrn_beta"],
            kwargs["lrn_k"])


def sanitize_step(plan, step, label: str):
    """Sanitize one plan step's Pallas dispatch.

    Returns ``(findings, geom)``; ``(None, None)`` when the step has no
    banded Pallas dispatch under this config (reference methods, XLA
    pool/fc legs, pointwise steps).  Operand shapes mirror the host-side
    layout work of the ops wrappers: NCHW -> NHWC, channels padded to
    the sublane multiple (chains also pad per-stage output channels).
    """
    if step.kind == "conv":
        if step.method not in _BANDED_METHODS:
            return None, None
        spec = step.spec
        c, h, w = step.in_shape
        cp = _ceil8(c)
        im2col = step.method in IM2COL_METHODS
        kw_extra = {}
        if im2col:
            kw_extra["oc_block"] = ADVANCED_OC_BLOCK
        return sanitizer.sanitize_conv2d(
            (BATCH, h, w, cp), (spec.kernel[0], spec.kernel[1], cp,
                                spec.out_channels),
            stride=spec.stride, padding=spec.padding, relu=step.relu,
            im2col=im2col, label=label, **kw_extra)
    if step.kind == "fused":
        g = step.group
        cv = g.conv
        c, h, w = step.in_shape
        cp = _ceil8(c)
        im2col = step.method in IM2COL_METHODS
        kw_extra = {}
        if im2col:
            kw_extra["oc_block"] = ADVANCED_OC_BLOCK
        kw = step.kwargs or {}
        return sanitizer.sanitize_conv2d(
            (BATCH, h, w, cp), (cv.kernel[0], cv.kernel[1], cp,
                                cv.out_channels),
            stride=cv.stride, padding=cv.padding, relu=g.relu,
            im2col=im2col, oh_block=step.oh_block,
            pool_kernel=g.pool.kernel, pool_stride=g.pool.stride,
            pool_kind=g.pool.pool_kind, pool_relu=g.pool_relu,
            lrn=_lrn_tuple(step.kwargs),
            pool_carry=kw.get("pool_carry"),
            lrn_oc_block=kw.get("lrn_oc_block"), label=label, **kw_extra)
    if step.kind == "chain":
        g = step.group
        c, h, w = step.in_shape
        cp = _ceil8(c)
        w_shapes, cin = [], cp
        for cv in g.convs:
            ocp = _ceil8(cv.out_channels)
            w_shapes.append((cv.kernel[0], cv.kernel[1], cin, ocp))
            cin = ocp
        pool = g.pool
        return sanitizer.sanitize_chain(
            (BATCH, h, w, cp), w_shapes,
            strides=tuple(cv.stride for cv in g.convs),
            paddings=tuple(cv.padding for cv in g.convs), relus=g.relus,
            im2col=step.method in IM2COL_METHODS, oh_block=step.oh_block,
            pool_kernel=pool.kernel if pool is not None else None,
            pool_stride=pool.stride if pool is not None else None,
            pool_kind=pool.pool_kind if pool is not None else "max",
            pool_relu=g.pool_relu, lrn=_lrn_tuple(step.kwargs),
            oc_block_final=g.oc_block_final, label=label)
    if step.kind == "pool" and plan.use_pallas:
        spec = step.spec
        c, h, w = step.in_shape
        return sanitizer.sanitize_pool2d(
            (BATCH, h, w, _ceil8(c)), kernel=spec.kernel,
            stride=spec.stride, kind=spec.pool_kind,
            relu=spec.relu or step.relu, label=label)
    if (step.kind == "fc" and plan.use_pallas
            and step.method != Method.SEQ_REF):
        return sanitizer.sanitize_matmul(
            (BATCH, step.d_in), (step.d_in, step.spec.out_channels),
            has_bias=True, act="relu" if step.relu else "none",
            label=label)
    return None, None


def _cross_check(geom, plan, step, label: str):
    """K105: the sanitizer's Phase-A geometry vs the resolver-backed
    ``step_band_params`` derivation — field-by-field."""
    if geom is None:
        return []
    trusted, _ = step_band_params(plan, step)
    if trusted is None:
        # the verifier sees no banded geometry where the sanitizer
        # derived one (or vice versa below) — that asymmetry is itself
        # a derivation disagreement
        return [Finding("error", label, "K105",
                        f"sanitizer derived {geom['kind']} band geometry "
                        "but step_band_params reports the step unbanded")]
    diffs = [f"{k}: sanitizer={geom[k]!r} verifier={trusted[k]!r}"
             for k in GEOM_KEYS if geom[k] != trusted[k]]
    if diffs:
        return [Finding("error", label, "K105",
                        "band-geometry derivations disagree — "
                        + "; ".join(diffs))]
    return []


#: forced second-generation fused-cell configurations, appended to the
#: default grid: the sliding-window pool carry (LRN opted out so the
#: carry gate opens), the two-pass channel-halo oc-blocked LRN cell, and
#: the oc-blocked chain final stage.  Each is (network, method, extra
#: compile_plan knobs, tag suffix).
EXTRA_CONFIGS = (
    ("alexnet", Method.ADVANCED_SIMD_8,
     dict(per_layer_fuse={"norm1": False, "norm2": False},
          per_layer_pool_carry={"conv1": True, "conv2": True}), "carry"),
    ("alexnet", Method.ADVANCED_SIMD_4,
     dict(per_layer_fuse={"norm1": False, "norm2": False},
          per_layer_pool_carry={"conv1": True, "conv2": True}), "carry"),
    ("alexnet", Method.ADVANCED_SIMD_8,
     dict(per_layer_lrn_oc_block={"conv1": True, "conv2": True}),
     "lrn-oc-block"),
    ("alexnet", Method.ADVANCED_SIMD_4,
     dict(per_layer_lrn_oc_block={"conv1": True, "conv2": True}),
     "lrn-oc-block"),
    ("alexnet", Method.ADVANCED_SIMD_8,
     dict(per_layer_oc_block_final={"conv5": 8}), "oc-block-final"),
    ("alexnet", Method.ADVANCED_SIMD_4,
     dict(per_layer_oc_block_final={"conv5": 4}), "oc-block-final"),
)


def _sanitize_plan(plan, tag, findings):
    n = 0
    for idx, step in enumerate(plan.steps):
        label = f"step{idx}:{'+'.join(step.names)}"
        fs, geom = sanitize_step(plan, step, label)
        if fs is None:
            continue
        n += 1
        fs = list(fs) + _cross_check(geom, plan, step, label)
        for f in fs:
            findings.append(Finding(
                f.severity, f"{tag}::{f.step}", f.rule, f.detail))
    return n


def sweep(networks=None):
    """Sanitize every (network x method x fuse x backend) combination,
    plus the forced second-generation cell configs (``EXTRA_CONFIGS``).

    Same grid and tag format as ``verify_sweep.sweep``; ``networks``
    defaults to the bundled ``NETWORKS`` registry (tests inject seeded
    mutations through the sanitizer's ``sources`` hook instead)."""
    if networks is None:
        networks = NETWORKS
    findings, combos, dispatches = [], 0, 0
    for name in sorted(networks):
        net = networks[name]()
        for method in METHODS:
            for fuse in (False, True):
                for use_pallas in (False, True):
                    combos += 1
                    plan = compile_plan(net, method=method, fuse=fuse,
                                        use_pallas=use_pallas, verify=False)
                    tag = (f"{name}/{method.value}/fuse={fuse}/"
                           f"pallas={use_pallas}")
                    dispatches += _sanitize_plan(plan, tag, findings)
    for name, method, knobs, suffix in EXTRA_CONFIGS:
        if name not in networks:
            continue
        combos += 1
        plan = compile_plan(networks[name](), method=method, fuse=True,
                            use_pallas=True, verify=False, **knobs)
        tag = f"{name}/{method.value}/fuse=True/pallas=True/{suffix}"
        dispatches += _sanitize_plan(plan, tag, findings)
    return findings, combos, dispatches


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--md", action="store_true")
    ap.add_argument("--fail-on-findings", action="store_true",
                    help="exit 1 on any finding (any severity)")
    args = ap.parse_args(argv)

    findings, combos, dispatches = sweep()
    title = (f"Kernel sanitizer sweep — {combos} configurations, "
             f"{dispatches} dispatches proven, {len(findings)} finding(s)")
    if args.json:
        print(findings_json(findings))
    elif args.md:
        print(findings_markdown(findings, title=title), end="")
    else:
        for f in findings:
            print(f)
        print(title)
    if args.fail_on_findings and findings:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
