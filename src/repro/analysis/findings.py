"""Structured findings shared by the plan verifier and the repo lint.

A ``Finding`` is one rule violation: ``severity`` (``error`` — the
configuration is wrong and must not run; ``warning`` — explicitly
requested but suspect; ``info`` — advisory, e.g. a budget note on the
XLA path which has no VMEM ceiling), the ``step`` it anchors to (a plan
step label for V-rules, ``path:line`` for R-rules), the ``rule`` ID,
and a human-readable ``detail``.

``RULES`` is the canonical taxonomy — every emitted finding's ``rule``
must be a key here (enforced by the findings tests), and the README is
generated from the same table.
"""
from __future__ import annotations

import json
from dataclasses import asdict, dataclass
from typing import Iterable, List, Sequence

SEVERITIES = ("error", "warning", "info")

#: rule ID -> (pass, one-line summary).  V1xx: shape/dtype flow.
#: V2xx: band geometry / coverage.  V3xx: VMEM budget audit.
#: R0xx: repo lint (AST).  K1xx: kernel sanitizer (abstract interpretation).
RULES = {
    "V101": ("verifier",
             "step output shape disagrees with its re-derivation from the "
             "step's input shape and layer spec"),
    "V102": ("verifier",
             "activation shapes do not chain: a step's input shape is not "
             "the previous step's output shape (or the plan input)"),
    "V103": ("verifier",
             "conv/fc parameter geometry disagrees with infer_param_shapes "
             "(wrong in-channels, kernel, or fc fan-in)"),
    "V201": ("verifier",
             "output bands do not partition [0, OH) exactly once "
             "(gap or overlap between grid cells)"),
    "V202": ("verifier",
             "an input halo band starts above the pre-padded frame origin"),
    "V203": ("verifier",
             "an input halo band misses rows its output band's windows "
             "read (under-fetch / off-by-one halo)"),
    "V204": ("verifier",
             "ragged last band not equalized to its fair share — the cell "
             "fetches a full band of pad rows (the PR 3 over-fetch class)"),
    "V205": ("verifier",
             "band scalars inconsistent: band != (blk-1)*stride + window "
             "or row_step != blk*stride"),
    "V301": ("verifier",
             "resolved cell working set exceeds the VMEM budget"),
    "V302": ("verifier",
             "chain cell live set exceeds the chain VMEM budget"),
    "V303": ("verifier",
             "even the one-final-row floor cell exceeds the budget — the "
             "fusion planner should never have admitted this group"),
    "R001": ("lint",
             "pl.pallas_call must thread interpret= and compiler_params="),
    "R002": ("lint",
             "engine knob mutation paths must invalidate the plan/jit/"
             "bucket caches (knob name mismatch, _KnobDict mutator not "
             "calling _on_change, or clear_caches missing a cache)"),
    "R003": ("lint",
             "pl.Element index maps must use resolver-named offsets — "
             "no inline numeric arithmetic (literal 0 excepted)"),
    "R004": ("lint",
             "silent exception handler: bare/broad except whose body is "
             "only pass"),
    "R005": ("lint",
             "magic-number byte budget in a comparison — use the named "
             "kernel budget constants"),
    "R006": ("lint",
             "serving/ except handler swallows a supervisor error: it "
             "must re-raise, reference its bound exception, or record a "
             "typed failure result (FailedResult/ShedResult/...)"),
    "R007": ("lint",
             "kernel-body astype must target the named accumulation-dtype "
             "constant (ACC_DTYPE) or a ref's .dtype — no inline dtype "
             "literals inside kernels/"),
    "K100": ("sanitizer",
             "the sanitizer could not complete its proof for a dispatch "
             "(unsupported construct, entry raised, or internal "
             "inconsistency) — the dispatch is unproven, not proven safe"),
    "K101": ("sanitizer",
             "a kernel load (x_ref/w_ref block, slice, or pl.ds) can read "
             "outside the padded operand extents for some grid index"),
    "K102": ("sanitizer",
             "the union of o_ref stores does not cover every output "
             "element exactly once across the grid (gap, overlap, or an "
             "unguarded overwrite on an accumulation axis)"),
    "K103": ("sanitizer",
             "precision flow violates the fp32-accumulate contract: "
             "accumulation not in fp32, or not exactly one downcast at "
             "the final o_ref store"),
    "K104": ("sanitizer",
             "intermediate-padding rows in a chain cell are not provably "
             "zero before the next stage consumes them (missing or "
             "mismatched row mask)"),
    "K105": ("sanitizer",
             "the sanitizer's independently derived band geometry "
             "disagrees with the resolver/verifier derivation — one of "
             "the two redundant derivations is wrong"),
    "K106": ("sanitizer",
             "VMEM scratch carry discipline violated: the carried grid "
             "axis is not 'arbitrary', the carried scratch ref is never "
             "refreshed, or the store is not "
             "the tail row-slice of the fresh band (stale rows would be "
             "re-consumed by the next band step)"),
}


@dataclass(frozen=True)
class Finding:
    severity: str
    step: str
    rule: str
    detail: str

    def __post_init__(self):
        if self.severity not in SEVERITIES:
            raise ValueError(f"unknown severity {self.severity!r}")
        if self.rule not in RULES:
            raise ValueError(f"unknown rule {self.rule!r}")

    def __str__(self) -> str:
        return f"[{self.rule}:{self.severity}] {self.step}: {self.detail}"


class PlanVerificationError(ValueError):
    """Raised by ``compile_plan(verify=True)`` on error-severity findings.

    Subclasses ``ValueError`` so load/validation call sites that already
    guard deployment artifacts with ``except ValueError`` (checksum,
    dtype) treat geometry corruption the same way.  The structured
    findings stay available on ``.findings``.
    """

    def __init__(self, findings: Sequence[Finding]):
        self.findings = list(findings)
        detail = "; ".join(str(f) for f in self.findings)
        super().__init__(
            f"plan verification failed with {len(self.findings)} "
            f"error finding(s): {detail}")


def findings_json(findings: Iterable[Finding]) -> str:
    return json.dumps([asdict(f) for f in findings], indent=2)


def findings_markdown(findings: Iterable[Finding],
                      title: str = "Findings") -> str:
    """A GitHub-flavored markdown table (piped into CI step summaries)."""
    rows: List[Finding] = list(findings)
    out = [f"### {title}", ""]
    if not rows:
        out.append("No findings.")
        return "\n".join(out) + "\n"
    out += ["| severity | rule | where | detail |",
            "| --- | --- | --- | --- |"]
    for f in rows:
        detail = f.detail.replace("|", "\\|")
        out.append(f"| {f.severity} | {f.rule} | `{f.step}` | {detail} |")
    return "\n".join(out) + "\n"
