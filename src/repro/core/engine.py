"""The CNNdroid inference engine: forward-path executor with per-layer
method selection (the paper's core deliverable).

The engine compiles its network into the **ExecutionPlan IR**
(``repro.core.plan``) once per fuse setting and executes it with a thin
step loop — shape propagation, standalone-ReLU folding, super-layer
fusion grouping (``repro.core.fusion.plan_fusion``), and per-layer
method/``oh_block`` resolution all happen at ``compile_plan`` time, not
per trace.  The engine owns:

* parameter init / loading (via ``core.deploy`` — the Caffe→device path),
* the compiled plans (memoized per fuse flag) and their jitted forwards,
  including a **batch-bucketed jit cache**: ``forward_batched`` rounds a
  request batch up to its power-of-two bucket, pads with zero frames,
  runs the bucket's memoized jitted plan, and slices the real rows back
  out — arbitrary batch sizes in ``1..max_batch`` cost at most
  ``log2(max_batch)+1`` compilations instead of one per distinct size
  (the paper's §6.2 deployment is batched frames; ``serving.cnn`` is
  built on this path),
* knob invalidation: assigning ``method`` / ``oh_block`` / ``fuse_pool``
  / ``fuse_relu`` / ``use_pallas``, or mutating the ``per_layer_*``
  maps, drops every memoized plan and jitted forward so the next call
  re-compiles against the new configuration (the old behaviour —
  silently serving the stale plan — was a bug),
* per-layer instrumentation used by the benchmark harness (``collect``
  forces the un-fused plan so every activation is observable).

Execution semantics live in ``repro.core.plan``'s step executors:
pooling runs through the Pallas ``pool2d`` kernels when ``use_pallas``
is set, else as an XLA ``reduce_window``; LRN is a single channel-axis
``reduce_window`` (fp32); fused groups dispatch to
``methods.conv2d_pool_fused`` / ``conv2d_chain_fused``.
"""
from __future__ import annotations

import re
import time
from functools import partial
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.analysis.findings import Finding, PlanVerificationError
from repro.core.methods import Method, conv2d
from repro.core.netdefs import NetworkDef, layer_inputs
from repro.core.plan import (  # noqa: F401  (_pool/_lrn re-exported: the
    ExecutionPlan,             # executors moved to the plan IR but their
    _lrn,                      # home here predates it)
    _pool,
    compile_plan,
    infer_param_shapes,
)


class _KnobDict(dict):
    """A per-layer knob map that invalidates the owning engine's caches
    on any mutation — ``eng.per_layer_fuse["conv1"] = False`` after a
    forward must re-plan, not keep serving the memoized stale plan."""

    def __init__(self, on_change, data=None):
        super().__init__(data or {})
        self._on_change = on_change

    def __setitem__(self, k, v):
        # no-op writes don't invalidate: a loop idempotently re-asserting
        # config must keep its warm jit caches
        changed = k not in self or self[k] != v
        super().__setitem__(k, v)
        if changed:
            self._on_change()

    def __delitem__(self, k):
        super().__delitem__(k)
        self._on_change()

    def update(self, *args, **kwargs):
        before = dict(self)
        super().update(*args, **kwargs)
        if dict(self) != before:
            self._on_change()

    def __ior__(self, other):
        # dict.__ior__ bypasses update(): |= must invalidate too
        self.update(other)
        return self

    def setdefault(self, k, default=None):
        if k in self:  # pure read
            return self[k]
        super().__setitem__(k, default)
        self._on_change()
        return default

    def pop(self, *args):
        out = super().pop(*args)
        self._on_change()
        return out

    def popitem(self):
        out = super().popitem()
        self._on_change()
        return out

    def clear(self):
        super().clear()
        self._on_change()


_UNSET = object()

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')


def program_steps(hlo_text: str, labels: Iterable[str]) -> Dict[str, str]:
    """``{instruction: step label}`` for every instruction of the entry
    computation of a compiled program's text (``compiled.as_text()``):
    the first component of its ``op_name`` that is one of ``labels``,
    or ``""`` where none is (parameters, and the copies that layout
    assignment inserts carry no step's scope).  Names are bare
    (``conv2d.3``), as a profiler trace's operation text starts."""
    labels = set(labels)
    out: Dict[str, str] = {}
    in_entry = False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY "):
            in_entry = True
        elif in_entry:
            if line.startswith("}"):
                break
            m = _INSTRUCTION.match(line)
            if m:
                op = _OP_NAME.search(line)
                scopes = op.group(1).split("/") if op else ()
                out[m.group(1)] = next((p for p in scopes if p in labels), "")
    return out


def pad_batch(x, bucket: int):
    """``x`` with zero frames appended up to ``bucket`` rows."""
    if x.shape[0] == bucket:
        return x
    pad = jnp.zeros((bucket - x.shape[0], *x.shape[1:]), x.dtype)
    return jnp.concatenate([x, pad], axis=0)


def _knob(name: str):
    """A config property whose assignment drops the memoized plans and
    jitted forwards (mutating engine config used to silently keep
    serving the stale plan).  Re-assigning the current value is a no-op:
    warm caches survive idempotent config re-assertion."""
    attr = "_" + name

    def get(self):
        return getattr(self, attr)

    def set_(self, value):
        cur = getattr(self, attr, _UNSET)
        if cur is not _UNSET and (cur is value or cur == value):
            return
        setattr(self, attr, value)
        self.clear_caches()

    return property(get, set_)


def _dict_knob(name: str):
    """A per-layer map knob: reassignment re-wraps into a ``_KnobDict``
    (invalidating only on a real content change); in-place mutation
    invalidates via the wrapper."""
    attr = "_" + name

    def get(self):
        return getattr(self, attr)

    def set_(self, value):
        changed = dict(getattr(self, attr, {})) != dict(value or {})
        setattr(self, attr, _KnobDict(self.clear_caches, value))
        if changed:
            self.clear_caches()

    return property(get, set_)


class CNNEngine:
    """Forward-path executor for a trained CNN."""

    method = _knob("method")
    use_pallas = _knob("use_pallas")
    fuse_relu = _knob("fuse_relu")
    fuse_pool = _knob("fuse_pool")
    oh_block = _knob("oh_block")
    per_layer_methods = _dict_knob("per_layer_methods")
    per_layer_oh_blocks = _dict_knob("per_layer_oh_blocks")
    per_layer_fuse = _dict_knob("per_layer_fuse")
    per_layer_pool_carry = _dict_knob("per_layer_pool_carry")
    per_layer_lrn_oc_block = _dict_knob("per_layer_lrn_oc_block")
    per_layer_oc_block_final = _dict_knob("per_layer_oc_block_final")

    def __init__(self, net: NetworkDef, method: Method = Method.ADVANCED_SIMD_8,
                 use_pallas: bool = False, fuse_relu: bool = True,
                 per_layer_methods: Optional[Dict[str, Method]] = None,
                 oh_block: Optional[int] = None,
                 per_layer_oh_blocks: Optional[Dict[str, int]] = None,
                 fuse_pool: bool = True,
                 per_layer_fuse: Optional[Dict[str, bool]] = None,
                 per_layer_pool_carry: Optional[Dict[str, bool]] = None,
                 per_layer_lrn_oc_block: Optional[Dict[str, bool]] = None,
                 per_layer_oc_block_final: Optional[Dict[str, int]] = None):
        self.net = net
        # plan + jit caches (created first: the knob setters below clear
        # them on every assignment, including these initial ones)
        self._plans: Dict[bool, ExecutionPlan] = {}
        self._jit_cache: Dict[bool, "jax.stages.Wrapped"] = {}
        # batch-bucketed jits: (fuse, bucket) -> jitted forward.  Each
        # bucket jit only ever sees ONE batch shape (inputs are padded up
        # to the bucket), so len(_bucket_jits) IS the compile count.
        self._bucket_jits: Dict[Tuple[bool, int], "jax.stages.Wrapped"] = {}
        self._bucket_compiles = 0
        self.method = method
        self.use_pallas = use_pallas
        self.fuse_relu = fuse_relu
        self.per_layer_methods = per_layer_methods or {}
        # spatial tile (output-row band) for the Pallas SIMD conv kernels;
        # None = auto from the VMEM budget, overridable per layer like the
        # execution method itself
        self.oh_block = oh_block
        self.per_layer_oh_blocks = per_layer_oh_blocks or {}
        # super-layer fusion (conv[+relu][+pool] groups); per_layer_fuse
        # maps a conv/pool layer name -> False to opt it out of fusion,
        # mirroring per_layer_methods
        self.fuse_pool = fuse_pool
        self.per_layer_fuse = per_layer_fuse or {}
        # second-generation fused-cell knobs (None/absent = the kernel
        # resolvers' auto rule), keyed like per_layer_methods
        self.per_layer_pool_carry = per_layer_pool_carry or {}
        self.per_layer_lrn_oc_block = per_layer_lrn_oc_block or {}
        self.per_layer_oc_block_final = per_layer_oc_block_final or {}
        self._shapes = infer_param_shapes(net)

    def clear_caches(self) -> None:
        """Drop the memoized execution plans and every jitted forward
        (per-fuse and batch-bucketed).  Called automatically by the knob
        setters; only direct mutation of private state needs it by hand."""
        self._plans.clear()
        self._jit_cache.clear()
        self._bucket_jits.clear()
        self._bucket_compiles = 0  # the count tracks the live cache

    # -- parameters -----------------------------------------------------------
    def init(self, key) -> Dict[str, Dict[str, jnp.ndarray]]:
        """Seeded weights: for each conv and fc layer in order, split the
        key and draw He-normal weights (``N(0, 2/fan_in)``), times the
        layer's ``gain`` where it is not 1; zero biases."""
        params = {}
        for spec in self.net.layers:
            if spec.kind not in ("conv", "fc"):
                continue
            shape = self._shapes[spec.name]
            fan_in = (int(np.prod(shape[1:])) if spec.kind == "conv"
                      else shape[0])
            key, k1 = jax.random.split(key)
            w = (2.0 / fan_in) ** 0.5 * jax.random.normal(k1, shape,
                                                         jnp.float32)
            if spec.gain != 1.0:
                w = spec.gain * w
            out = shape[0] if spec.kind == "conv" else shape[1]
            params[spec.name] = {"w": w, "b": jnp.zeros((out,), jnp.float32)}
        return params

    # -- forward ----------------------------------------------------------------
    def _oh_block_for(self, name: str) -> Optional[int]:
        return self.per_layer_oh_blocks.get(name, self.oh_block)

    def plan(self, fuse: Optional[bool] = None) -> ExecutionPlan:
        """The compiled ``ExecutionPlan`` for this engine configuration,
        memoized per fuse flag (iterating it yields the layer/group
        items, so ``fusion_summary(eng.plan(True))`` keeps working)."""
        use_fuse = self.fuse_pool if fuse is None else bool(fuse)
        if use_fuse not in self._plans:
            # the VMEM working-set check only binds on the Pallas path;
            # the XLA analogue fuses regardless of cell size
            self._plans[use_fuse] = compile_plan(
                self.net, method=self.method,
                per_layer_methods=self.per_layer_methods,
                oh_block=self.oh_block,
                per_layer_oh_blocks=self.per_layer_oh_blocks,
                fuse=use_fuse, fuse_relu=self.fuse_relu,
                per_layer_fuse=self.per_layer_fuse,
                per_layer_pool_carry=self.per_layer_pool_carry,
                per_layer_lrn_oc_block=self.per_layer_lrn_oc_block,
                per_layer_oc_block_final=self.per_layer_oc_block_final,
                use_pallas=self.use_pallas)
        return self._plans[use_fuse]

    def verify(self, fuse: Optional[bool] = None) -> List[Finding]:
        """Run the static plan verifier over this engine's compiled plan
        and return ALL findings (``compile_plan`` already raises on
        error-severity ones; this surfaces the warnings/infos too —
        the knob-sweep oracle the autotuner arc builds on)."""
        from repro.analysis.verifier import verify_plan

        return verify_plan(self.plan(fuse))

    #: knob names switch_verified accepts — exactly the cache-invalidating
    #: configuration surface (the _knob/_dict_knob descriptors above)
    KNOBS = ("method", "use_pallas", "fuse_relu", "fuse_pool", "oh_block",
             "per_layer_methods", "per_layer_oh_blocks", "per_layer_fuse",
             "per_layer_pool_carry", "per_layer_lrn_oc_block",
             "per_layer_oc_block_final")

    def switch_verified(self, **knobs) -> Tuple[bool, List[Finding]]:
        """Atomically apply a candidate knob configuration, but only if
        its compiled plan passes static verification with no
        error-severity findings — otherwise roll every knob back and
        report why.  This is the degradation ladder's gate: a rung is
        never served until ``CNNEngine.verify()`` has blessed it.

        Returns ``(switched, findings)``: ``findings`` is the full list
        (warnings/infos included on success, the error findings on
        rollback).  Unknown knob names raise — a typo must not silently
        verify the unchanged configuration."""
        unknown = set(knobs) - set(self.KNOBS)
        if unknown:
            raise ValueError(f"unknown knob(s): {sorted(unknown)}")
        snapshot = {k: (dict(getattr(self, k)) if k.startswith("per_layer")
                        else getattr(self, k)) for k in knobs}
        for k, v in knobs.items():
            setattr(self, k, v)
        try:
            findings = self.verify()
        except PlanVerificationError as e:
            findings = e.findings
        if any(f.severity == "error" for f in findings):
            for k, v in snapshot.items():
                setattr(self, k, v)
            return False, findings
        return True, findings

    def forward(self, params, x, collect: Optional[dict] = None,
                fuse: Optional[bool] = None):
        """x: [N, C, H, W] (a batch of frames, paper §4).  ``collect``
        (optional dict) receives per-layer outputs for inspection — it
        forces the un-fused plan so every activation exists.  ``fuse``
        overrides the engine-level ``fuse_pool`` for this call.  All
        fusion/folding decisions were made at ``compile_plan`` time;
        this is a thin loop of step executors."""
        if collect is not None:
            fuse = False  # instrumentation needs every per-layer output
        return self.plan(fuse).execute(params, x, collect=collect)

    def jit_forward(self, fuse: Optional[bool] = None):
        """The jitted forward, memoized per fuse setting — repeated calls
        (``time_forward``, every bench iteration) reuse one compilation."""
        key = self.fuse_pool if fuse is None else bool(fuse)
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(
                partial(self.forward, fuse=key))
        return self._jit_cache[key]

    # -- batch-bucketed forward (serving path) --------------------------------
    @staticmethod
    def batch_bucket(n: int) -> int:
        """The power-of-two bucket a batch of ``n`` requests rounds up
        to: every batch size in ``1..max_batch`` lands in one of the
        ``log2(max_batch)+1`` buckets ``{1, 2, 4, ..., max_batch}``."""
        if n < 1:
            raise ValueError(f"batch must be >= 1, got {n}")
        return 1 << (int(n) - 1).bit_length()

    def _bucket_jit(self, fuse: bool, bucket: int):
        key = (fuse, bucket)
        if key not in self._bucket_jits:
            forward = partial(self.forward, fuse=fuse)

            def run(params, x):
                return forward(params, x)

            # the program's name: a trace's module line says which
            # bucket ran
            run.__name__ = run.__qualname__ = f"forward_b{bucket}"
            self._bucket_jits[key] = jax.jit(run)
            self._bucket_compiles += 1
        return self._bucket_jits[key]

    def forward_batched(self, params, x, fuse: Optional[bool] = None):
        """``forward`` through the batch-bucketed jit cache: pad the
        batch up to its power-of-two bucket with zero frames, run the
        bucket's memoized jitted plan, slice the real rows back out.
        Arbitrary request batch sizes hit at most ``log2(max_batch)+1``
        compiled variants — the steady-state serving path (``CNNServer``)
        never recompiles once its buckets are warm.  Under a profiler the
        call is the host span ``cnn.forward_batched`` (arguments ``n``
        and ``bucket``), the zero-padding ``cnn.pad`` inside it."""
        use_fuse = self.fuse_pool if fuse is None else bool(fuse)
        n = x.shape[0]
        bucket = self.batch_bucket(n)
        with TraceAnnotation("cnn.forward_batched", n=n, bucket=bucket):
            fn = self._bucket_jit(use_fuse, bucket)
            if bucket != n:
                with TraceAnnotation("cnn.pad"):
                    x = pad_batch(x, bucket)
            return fn(params, x)[:n]

    def op_steps(self, params, x,
                 fuse: Optional[bool] = None) -> Dict[str, str]:
        """Which plan step each device operation of ``forward_batched``
        on a batch shaped like ``x`` belongs to: ``program_steps`` of the
        bucket's compiled program, whose instruction names are those of
        the operations in a profiler trace of it.  The program is the
        one ``forward_batched`` runs, taken from JAX's caches: after a
        warm call this compiles nothing."""
        use_fuse = self.fuse_pool if fuse is None else bool(fuse)
        bucket = self.batch_bucket(x.shape[0])
        compiled = self._bucket_jit(use_fuse, bucket).lower(
            params, pad_batch(x, bucket)).compile()
        return program_steps(compiled.as_text(),
                             [s.label for s in self.plan(use_fuse).steps])

    def bucket_stats(self) -> dict:
        """Bucketed-jit cache introspection: the live (fuse, bucket)
        keys and the total number of bucket compilations this engine has
        paid (monotone until ``clear_caches`` — the compile-count tests
        assert repeat batch sizes within a bucket add nothing)."""
        return {"buckets": sorted(self._bucket_jits),
                "compiles": self._bucket_compiles}

    # -- instrumentation ----------------------------------------------------------
    def fusion_report(self, fuse: Optional[bool] = None) -> List[dict]:
        """Executed geometry of every fused group — read straight off the
        compiled plan's steps (each carries its resolved input shape,
        method, and band override): the layer names covered, the chain
        depth (``convs``), the group's output spatial size, and the
        final-row band the Pallas cell resolves (``rows_per_cell`` ×
        ``n_tiles``; the XLA analogue runs each group un-banded)."""
        return self.plan(fuse).fusion_report()

    def time_forward(self, params, x, iters: int = 3,
                     fuse: Optional[bool] = None) -> float:
        fn = self.jit_forward(fuse)
        fn(params, x).block_until_ready()  # compile + warm (cached)
        t0 = time.perf_counter()
        for _ in range(iters):
            fn(params, x).block_until_ready()
        return (time.perf_counter() - t0) / iters

    def heaviest_conv(self, params, x) -> Tuple[str, "jnp.ndarray"]:
        """The conv layer with the most MACs (paper Table 4 target) and its
        input activation."""
        best, best_macs, best_in = None, -1, None
        acts: dict = {"input": x}
        self.forward(params, x, collect=acts)
        inputs = layer_inputs(self.net)
        for spec in self.net.layers:
            if spec.kind == "conv":
                oc, ic, kh, kw = self._shapes[spec.name]
                out = acts[spec.name]
                macs = int(np.prod(out.shape)) * ic * kh * kw
                if macs > best_macs:
                    best, best_macs = spec, macs
                    best_in = acts[inputs[spec.name][0]]
        return best.name, best_in

    def conv_layer_fn(self, name: str, method: Method,
                      oh_block: Optional[int] = None):
        spec = next(s for s in self.net.layers if s.name == name)
        ohb = oh_block if oh_block is not None else self._oh_block_for(name)

        def fn(params, x):
            p = params[name]
            return conv2d(x, p["w"], p["b"], method, spec.stride,
                          spec.padding, True, self.use_pallas, ohb)

        return fn
