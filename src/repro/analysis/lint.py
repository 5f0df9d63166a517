"""Repo lint: AST rules enforcing the kernel/engine invariants.

Rules (see ``findings.RULES`` / ``analysis/README.md``):

* **R001** — every ``pl.pallas_call`` threads ``interpret=`` (so the
  CPU/interpret test path exists for every kernel) and
  ``compiler_params=`` (dimension semantics are part of the kernel's
  contract, never left to the default).
* **R002** — the engine's knob machinery cannot regress into the PR 5
  stale-plan bug: ``_KnobDict`` mutators must reach ``_on_change``
  (directly or by delegating to a checked mutator), class-level
  ``name = _knob("name")`` descriptors must name the attribute they
  wrap, and ``clear_caches`` must clear every cache dict ``__init__``
  creates.
* **R003** — a ``pl.BlockSpec`` with a ``pl.Element(...)`` block dim
  (element-offset indexing) may only scale grid indices by *named*
  offsets (``row_step``, ``in_step``, …) that come from the geometry
  resolvers; inline numeric arithmetic (any literal other than a
  standalone ``0``) hides band math the verifier cannot see.
* **R004** — no silent handlers: a bare/broad ``except`` whose body is
  only ``pass``/``...`` swallows planner and IO failures.
* **R005** — byte budgets appear in comparisons only through the named
  kernel constants, never as magic numbers (≥ 1 MiB literals).
* **R006** — serving-path supervision cannot swallow errors: every
  ``except`` handler in a ``serving/`` module must re-raise, reference
  its bound exception (``except X as e`` + use of ``e`` — recording the
  failure), or name a typed failure result (``FailedResult`` /
  ``ShedResult`` / the engine-fault types).  A handler that does none
  of these turns a supervisor error into a silent drop.
* **R007** — kernel-body ``astype`` discipline: inside ``kernels/``
  functions that take ``*_ref`` parameters (Pallas kernel bodies),
  every ``.astype(...)`` must target the named accumulation constant
  ``ACC_DTYPE`` or a ref's ``.dtype``; inline dtype literals fork the
  fp32-accumulate / single-downcast contract the kernel sanitizer
  proves (K103).

All rules are file-local AST walks — no imports of the linted modules,
so the linter runs on any tree (including deliberately-broken test
snippets).
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import Iterable, List, Optional

from repro.analysis.findings import Finding

#: _KnobDict methods that mutate the mapping (must invalidate)
KNOB_DICT_MUTATORS = frozenset({
    "__setitem__", "__delitem__", "__ior__", "update", "setdefault",
    "pop", "popitem", "clear",
})

#: caches clear_caches must drop (matched against __init__-created dicts)
_CACHE_HINTS = ("plan", "jit", "bucket", "cache")

_MAGIC_BUDGET_MIN = 1 << 20  # 1 MiB: anything this big is a byte budget


def _call_name(node: ast.Call) -> str:
    """Dotted tail of a call target: ``pl.pallas_call`` -> ``pallas_call``."""
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return ""


def _loc(path: str, node: ast.AST) -> str:
    return f"{path}:{getattr(node, 'lineno', 0)}"


# -- R001 -------------------------------------------------------------------

def _r001(tree: ast.AST, path: str) -> List[Finding]:
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _call_name(node) == "pallas_call":
            kws = {kw.arg for kw in node.keywords}
            missing = sorted({"interpret", "compiler_params"} - kws)
            if missing:
                out.append(Finding(
                    "error", _loc(path, node), "R001",
                    f"pallas_call missing keyword(s): {', '.join(missing)}"))
    return out


# -- R002 -------------------------------------------------------------------

def _calls_in(node: ast.AST) -> Iterable[ast.Call]:
    return (n for n in ast.walk(node) if isinstance(n, ast.Call))


def _r002_knob_dict(cls: ast.ClassDef, path: str) -> List[Finding]:
    out = []
    for item in cls.body:
        if not isinstance(item, ast.FunctionDef):
            continue
        if item.name not in KNOB_DICT_MUTATORS or item.name == "__init__":
            continue
        ok = False
        for call in _calls_in(item):
            f = call.func
            if (isinstance(f, ast.Attribute)
                    and isinstance(f.value, ast.Name)
                    and f.value.id == "self"
                    and (f.attr == "_on_change"
                         or f.attr in KNOB_DICT_MUTATORS)):
                ok = True
                break
        if not ok:
            out.append(Finding(
                "error", _loc(path, item), "R002",
                f"_KnobDict.{item.name} mutates without reaching "
                f"_on_change (stale-plan bug class)"))
    return out


def _r002_knob_names(cls: ast.ClassDef, path: str) -> List[Finding]:
    out = []
    for item in cls.body:
        if not (isinstance(item, ast.Assign) and len(item.targets) == 1
                and isinstance(item.targets[0], ast.Name)
                and isinstance(item.value, ast.Call)
                and _call_name(item.value) in ("_knob", "_dict_knob")):
            continue
        args = item.value.args
        if (len(args) != 1 or not isinstance(args[0], ast.Constant)
                or args[0].value != item.targets[0].id):
            out.append(Finding(
                "error", _loc(path, item), "R002",
                f"knob descriptor {item.targets[0].id} must wrap the "
                f"attribute of the same name"))
    return out


def _r002_clear_caches(cls: ast.ClassDef, path: str) -> List[Finding]:
    init = next((f for f in cls.body if isinstance(f, ast.FunctionDef)
                 and f.name == "__init__"), None)
    clear = next((f for f in cls.body if isinstance(f, ast.FunctionDef)
                  and f.name == "clear_caches"), None)
    if init is None or clear is None:
        return []
    caches = set()
    for node in ast.walk(init):
        tgt = None
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            tgt, val = node.targets[0], node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            tgt, val = node.target, node.value
        else:
            continue
        if (isinstance(tgt, ast.Attribute) and isinstance(tgt.value, ast.Name)
                and tgt.value.id == "self" and isinstance(val, ast.Dict)
                and any(h in tgt.attr.lower() for h in _CACHE_HINTS)):
            caches.add(tgt.attr)
    touched = {n.attr for n in ast.walk(clear)
               if isinstance(n, ast.Attribute)
               and isinstance(n.value, ast.Name) and n.value.id == "self"}
    out = []
    for name in sorted(caches - touched):
        out.append(Finding(
            "error", _loc(path, clear), "R002",
            f"clear_caches does not clear self.{name} created in __init__"))
    return out


def _r002(tree: ast.AST, path: str) -> List[Finding]:
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        if node.name == "_KnobDict":
            out += _r002_knob_dict(node, path)
        out += _r002_knob_names(node, path)
        out += _r002_clear_caches(node, path)
    return out


# -- R003 -------------------------------------------------------------------

def _has_element_dim(call: ast.Call) -> bool:
    """True when the spec's block shape declares a ``pl.Element`` dim."""
    shape = call.args[0] if call.args else next(
        (kw.value for kw in call.keywords if kw.arg == "block_shape"), None)
    if shape is None:
        return False
    return any(isinstance(n, ast.Call) and _call_name(n) == "Element"
               for n in ast.walk(shape))


def _r003(tree: ast.AST, path: str) -> List[Finding]:
    out = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and _call_name(node) == "BlockSpec"
                and _has_element_dim(node)):
            continue
        lam = next((a for a in node.args if isinstance(a, ast.Lambda)), None)
        if lam is None:
            lam = next((kw.value for kw in node.keywords
                        if kw.arg == "index_map"
                        and isinstance(kw.value, ast.Lambda)), None)
        if lam is None:
            continue
        for n in ast.walk(lam.body):
            if (isinstance(n, ast.Constant)
                    and isinstance(n.value, (int, float))
                    and n.value != 0):
                out.append(Finding(
                    "error", _loc(path, node), "R003",
                    f"element-offset index map uses inline literal "
                    f"{n.value!r}; "
                    f"offsets must come from a geometry resolver name"))
                break
    return out


# -- R004 -------------------------------------------------------------------

def _is_broad(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:
        return True
    names = []
    for n in ast.walk(t):
        if isinstance(n, ast.Attribute):
            names.append(n.attr)
        elif isinstance(n, ast.Name):
            names.append(n.id)
    return any(n in ("Exception", "BaseException") for n in names)


def _r004(tree: ast.AST, path: str) -> List[Finding]:
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        body_is_silent = all(
            isinstance(s, ast.Pass)
            or (isinstance(s, ast.Expr) and isinstance(s.value, ast.Constant)
                and s.value.value is Ellipsis)
            for s in node.body)
        if body_is_silent and _is_broad(node):
            out.append(Finding(
                "error", _loc(path, node), "R004",
                "silent broad except: narrow the exception and handle (or "
                "at least record) the failure"))
    return out


# -- R005 -------------------------------------------------------------------

_FOLD_OPS = {ast.Add: lambda a, b: a + b, ast.Sub: lambda a, b: a - b,
             ast.Mult: lambda a, b: a * b, ast.LShift: lambda a, b: a << b,
             ast.Pow: lambda a, b: a ** b}


def _const_int(node: ast.AST) -> Optional[int]:
    """Fold a constants-only arithmetic expression (``8 * 1024 * 1024``,
    ``14 << 20``) to its int value; None if any leaf is a name."""
    if (isinstance(node, ast.Constant) and isinstance(node.value, int)
            and not isinstance(node.value, bool)):
        return node.value
    if isinstance(node, ast.BinOp) and type(node.op) in _FOLD_OPS:
        left, right = _const_int(node.left), _const_int(node.right)
        if left is not None and right is not None:
            return _FOLD_OPS[type(node.op)](left, right)
    return None


def _r005(tree: ast.AST, path: str) -> List[Finding]:
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Compare):
            continue
        for side in [node.left, *node.comparators]:
            value = _const_int(side)
            if value is not None and value >= _MAGIC_BUDGET_MIN:
                out.append(Finding(
                    "warning", _loc(path, node), "R005",
                    f"magic byte budget {value} in a comparison — use "
                    f"the named kernel budget constants"))
    return out


# -- R006 -------------------------------------------------------------------

#: typed failure results / fault types whose mention in a handler counts
#: as recording the error (the serving failure taxonomy)
R006_TYPED_NAMES = frozenset({
    "FailedResult", "ShedResult", "EngineFault", "TransientEngineFault",
    "PersistentEngineFault", "ServerWedgedError", "NonFiniteInputError",
})


def _r006(tree: ast.AST, path: str) -> List[Finding]:
    """serving/ except handlers must re-raise or record a typed failure
    (no swallowed supervisor errors).  File-scoped: the rule only binds
    on modules under a ``serving/`` directory."""
    if "serving/" not in path.replace("\\", "/"):
        return []
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        body_nodes = [n for stmt in node.body for n in ast.walk(stmt)]
        if any(isinstance(n, ast.Raise) for n in body_nodes):
            continue
        names = {n.id for n in body_nodes if isinstance(n, ast.Name)}
        names |= {n.attr for n in body_nodes if isinstance(n, ast.Attribute)}
        if node.name and node.name in names:
            continue  # the bound exception is used: the error is recorded
        if names & R006_TYPED_NAMES:
            continue  # a typed failure result is produced
        out.append(Finding(
            "error", _loc(path, node), "R006",
            "serving/ except handler neither re-raises, uses its bound "
            "exception, nor records a typed failure result — the "
            "supervisor error is swallowed"))
    return out


# -- R007 -------------------------------------------------------------------


def _is_kernel_fn(fn: ast.FunctionDef) -> bool:
    """A Pallas kernel body: any positional parameter named ``*_ref``."""
    args = fn.args
    params = args.posonlyargs + args.args + args.kwonlyargs
    return any(a.arg.endswith("_ref") for a in params)


def _r007(tree: ast.AST, path: str) -> List[Finding]:
    """kernels/ astype discipline: inside a kernel body every
    ``.astype(ARG)`` must target the named accumulation constant
    (``ACC_DTYPE``) or a ref's ``.dtype`` — an inline dtype literal
    (``jnp.float32``, ``"bfloat16"``) silently forks the accumulate /
    downcast contract the sanitizer proves (K103)."""
    if "kernels/" not in path.replace("\\", "/"):
        return []
    out, seen = [], set()
    for fn in ast.walk(tree):
        if not (isinstance(fn, ast.FunctionDef) and _is_kernel_fn(fn)):
            continue
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "astype" and node.args):
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Name) and arg.id == "ACC_DTYPE":
                continue
            if isinstance(arg, ast.Attribute) and arg.attr == "dtype":
                continue
            key = (node.lineno, node.col_offset)
            if key in seen:  # nested kernel fns walk the same call twice
                continue
            seen.add(key)
            out.append(Finding(
                "error", _loc(path, node), "R007",
                "kernel-body astype must target ACC_DTYPE or a ref's "
                ".dtype — inline dtype arguments break the fp32 "
                "accumulate/single-downcast contract"))
    return out


_RULES = (_r001, _r002, _r003, _r004, _r005, _r006, _r007)


def lint_source(src: str, path: str = "<string>",
                rules: Optional[Iterable] = None) -> List[Finding]:
    """Lint one source string (the unit the seeded-snippet tests use)."""
    tree = ast.parse(src)
    out: List[Finding] = []
    for rule in (rules or _RULES):
        out += rule(tree, path)
    return out


def lint_file(path: Path, root: Optional[Path] = None) -> List[Finding]:
    rel = str(path.relative_to(root)) if root else str(path)
    return lint_source(path.read_text(), rel)


def lint_tree(root: Path) -> List[Finding]:
    """Lint every ``*.py`` under ``root`` (sorted, deterministic)."""
    root = Path(root)
    out: List[Finding] = []
    for path in sorted(root.rglob("*.py")):
        out += lint_file(path, root.parent)
    return out
