"""Static analysis over the ExecutionPlan IR and the repo source.

Two passes, both pure Python (no kernel execution, no tracing):

* ``repro.analysis.verifier`` — the **plan verifier**: re-derives every
  compiled ``PlanStep``'s shape flow and Pallas band geometry from the
  same kernel resolvers the dispatch path runs, proves band coverage
  (output bands partition the frame, halo bands cover every row each
  window reads), and audits modelled VMEM working sets against the
  kernel budgets.  ``core.plan.compile_plan(verify=True)`` — the
  default — runs it on every compiled plan.
* ``repro.analysis.lint`` — the **repo lint**: AST rules enforcing the
  repo's kernel/engine invariants (``pallas_call`` kwargs threading,
  knob-mutation cache invalidation, resolver-owned ``pl.Element`` index
  maps, no silent excepts, no magic-number budgets).

See ``repro/analysis/README.md`` for the rule taxonomy and CLI usage
(``tools/lint.py``, ``tools/verify_sweep.py``).
"""
from repro.analysis.findings import (  # noqa: F401
    Finding,
    PlanVerificationError,
    RULES,
    findings_json,
    findings_markdown,
)
