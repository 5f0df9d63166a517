"""Repo lint: baseline cleanliness + seeded-snippet detection per rule."""
from pathlib import Path

import pytest

from repro.analysis.lint import lint_source, lint_tree

REPO = Path(__file__).resolve().parent.parent


def rules_of(findings):
    return {f.rule for f in findings}


def test_repo_baseline_is_clean():
    """src/repro must lint clean — the CI gate enforces this forever."""
    assert lint_tree(REPO / "src" / "repro") == []


# -- R001: pallas_call kwargs ----------------------------------------------

def test_r001_missing_kwargs():
    src = """
out = pl.pallas_call(kern, grid=(n,), out_shape=shape)(x)
"""
    findings = lint_source(src)
    assert rules_of(findings) == {"R001"}
    assert "interpret" in findings[0].detail


def test_r001_threaded_kwargs_clean():
    src = """
out = pl.pallas_call(
    kern, grid=(n,), out_shape=shape,
    compiler_params=pltpu.CompilerParams(
        dimension_semantics=("parallel",)),
    interpret=interpret,
)(x)
"""
    assert lint_source(src) == []


# -- R002: knob invalidation ------------------------------------------------

def test_r002_mutator_without_on_change():
    src = """
class _KnobDict(dict):
    def __setitem__(self, k, v):
        super().__setitem__(k, v)   # stale-plan bug: no invalidation
"""
    findings = lint_source(src)
    assert rules_of(findings) == {"R002"}
    assert "__setitem__" in findings[0].detail


def test_r002_mutator_delegation_clean():
    src = """
class _KnobDict(dict):
    def __setitem__(self, k, v):
        super().__setitem__(k, v)
        self._on_change()

    def __ior__(self, other):
        self.update(other)   # delegation to a checked mutator is fine
        return self
"""
    assert lint_source(src) == []


def test_r002_knob_name_mismatch():
    src = """
class Engine:
    method = _knob("oh_block")   # wraps the WRONG attribute
"""
    findings = lint_source(src)
    assert rules_of(findings) == {"R002"}


def test_r002_clear_caches_missing_cache():
    src = """
class Engine:
    def __init__(self):
        self._plans = {}
        self._jit_cache = {}

    def clear_caches(self):
        self._plans.clear()   # forgets _jit_cache
"""
    findings = lint_source(src)
    assert rules_of(findings) == {"R002"}
    assert "_jit_cache" in findings[0].detail


def test_r002_clear_caches_complete_clean():
    src = """
class Engine:
    def __init__(self):
        self._plans = {}
        self._jit_cache = {}

    def clear_caches(self):
        self._plans.clear()
        self._jit_cache.clear()
"""
    assert lint_source(src) == []


# -- R003: element-offset index maps ---------------------------------------

def test_r003_inline_arithmetic():
    src = """
spec = pl.BlockSpec((1, pl.Element(band), wp, c),
                    lambda i, t: (i, t * 8, 0, 0))
"""
    findings = lint_source(src)
    assert rules_of(findings) == {"R003"}


def test_r003_resolver_named_offset_clean():
    src = """
spec = pl.BlockSpec((1, pl.Element(band), wp, c),
                    lambda i, t: (i, t * row_step, 0, 0))
"""
    assert lint_source(src) == []


def test_r003_blocked_spec_literals_allowed():
    # block-index (no pl.Element dim) specs index in block units; literals
    # fine
    src = """
spec = pl.BlockSpec((None, 4, oh, ow), lambda i, t: (i, t * 2, 0, 0))
"""
    assert lint_source(src) == []


# -- R004: silent excepts ---------------------------------------------------

def test_r004_silent_broad_except():
    src = """
try:
    risky()
except Exception:
    pass
"""
    findings = lint_source(src)
    assert rules_of(findings) == {"R004"}


def test_r004_bare_except_pass():
    src = """
try:
    risky()
except:
    pass
"""
    assert rules_of(lint_source(src)) == {"R004"}


def test_r004_narrow_or_handled_clean():
    src = """
try:
    risky()
except OSError:
    pass

try:
    risky()
except Exception:
    log.warning("risky failed")
"""
    assert lint_source(src) == []


# -- R005: magic byte budgets ----------------------------------------------

@pytest.mark.parametrize("expr", ["8388608", "8 * 1024 * 1024", "14 << 20"])
def test_r005_magic_budget_comparison(expr):
    findings = lint_source(f"ok = cell_bytes <= {expr}\n")
    assert rules_of(findings) == {"R005"}


def test_r005_named_budget_clean():
    src = """
VMEM_BUDGET_BYTES = 8 * 1024 * 1024   # definitions are fine
ok = cell_bytes <= VMEM_BUDGET_BYTES
small = n <= 128
"""
    assert lint_source(src) == []


# -- R006: serving/ supervisor error handling ------------------------------

SERVING_PATH = "src/repro/serving/x.py"


def test_r006_swallowed_serving_except():
    src = """
try:
    run_batch()
except Exception:
    count += 1
"""
    assert rules_of(lint_source(src, path=SERVING_PATH)) == {"R006"}


def test_r006_only_fires_under_serving():
    src = """
try:
    run_batch()
except Exception:
    count += 1
"""
    # outside serving/ the broad-except rule (R004) may speak, R006 not
    assert "R006" not in rules_of(lint_source(src, path="src/repro/core/x.py"))


def test_r006_reraise_clean():
    src = """
try:
    run_batch()
except Exception:
    raise
"""
    assert lint_source(src, path=SERVING_PATH) == []


def test_r006_bound_exception_referenced_clean():
    src = """
try:
    run_batch()
except TransientEngineFault as e:
    last_err = e
"""
    assert lint_source(src, path=SERVING_PATH) == []


def test_r006_typed_failure_result_clean():
    src = """
try:
    run_batch()
except Exception:
    out.append(FailedResult(rid=rid, error="engine_fault", detail="boom",
                            latency_s=0.0, batch_size=1, bucket=1))
"""
    assert lint_source(src, path=SERVING_PATH) == []


# -- R007: kernel-body astype discipline ------------------------------------

KERNEL_PATH = "src/repro/kernels/conv2d/kernels.py"

_R007_KERNEL = """
def _my_kernel(x_ref, o_ref, *, relu):
    acc = x_ref[0].astype({cast})
    o_ref[...] = acc.astype(o_ref.dtype)
"""


def test_r007_inline_dtype_literal():
    src = _R007_KERNEL.format(cast="jnp.float32")
    findings = lint_source(src, path=KERNEL_PATH)
    assert rules_of(findings) == {"R007"}
    assert "ACC_DTYPE" in findings[0].detail


def test_r007_string_dtype_literal():
    src = _R007_KERNEL.format(cast='"bfloat16"')
    assert rules_of(lint_source(src, path=KERNEL_PATH)) == {"R007"}


def test_r007_named_constant_clean():
    src = _R007_KERNEL.format(cast="ACC_DTYPE")
    assert lint_source(src, path=KERNEL_PATH) == []


def test_r007_ref_dtype_clean():
    src = _R007_KERNEL.format(cast="o_ref.dtype")
    assert lint_source(src, path=KERNEL_PATH) == []


def test_r007_only_fires_in_kernel_bodies():
    # a host-side helper (no *_ref parameter) may cast freely
    src = """
def host_pad(x):
    return x.astype(jnp.float32)
"""
    assert lint_source(src, path=KERNEL_PATH) == []


def test_r007_only_fires_under_kernels_tree():
    src = _R007_KERNEL.format(cast="jnp.float32")
    assert lint_source(src, path="src/repro/core/plan.py") == []


def test_tools_and_benchmarks_baseline_clean():
    """The lint default paths grew to tools/ and benchmarks/ — they must
    stay clean too."""
    assert lint_tree(REPO / "tools") == []
    assert lint_tree(REPO / "benchmarks") == []
