"""ExecutionPlan IR tests: compile-time resolution (shapes, standalone-
ReLU folding, fusion grouping), plan↔legacy forward equivalence across
the paper networks × methods × fuse settings, the batch-bucketed jit
cache's compile bound, and knob-setter cache invalidation (the stale-plan
bugfix)."""
import jax
import jax.numpy as jnp
import pytest

import repro.core.plan as plan_mod
from repro.core.engine import CNNEngine
from repro.core.fusion import fusion_summary
from repro.core.methods import Method
from repro.core.netdefs import NETWORKS, LayerSpec, NetworkDef
from repro.core.plan import compile_plan, infer_param_shapes

SIMD = Method.ADVANCED_SIMD_8


# ---------------------------------------------------------------------------
# compile-time resolution
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("use_pallas,raises", [(True, True), (False, False)])
def test_compile_plan_refuses_kernel_without_tpu_lowering(monkeypatch,
                                                          use_pallas,
                                                          raises):
    """On a TPU backend a Pallas plan that selects a method Mosaic cannot
    compile (``basic_parallel``) raises at compile time instead of
    falling back to another path; the XLA path still plans."""
    monkeypatch.setattr(plan_mod.jax, "default_backend", lambda: "tpu")
    net = NETWORKS["lenet5"]()
    if raises:
        with pytest.raises(NotImplementedError, match="basic_parallel"):
            compile_plan(net, method=Method.BASIC_PARALLEL,
                         use_pallas=use_pallas)
    else:
        compile_plan(net, method=Method.BASIC_PARALLEL,
                     use_pallas=use_pallas)
    compile_plan(net, method=SIMD, use_pallas=True)  # main path plans


@pytest.mark.parametrize("net_name", ["lenet5", "cifar10", "alexnet"])
def test_plan_steps_fully_resolved(net_name):
    """Every step carries resolved input/output shapes; fused steps carry
    their method, band override and LRN constants — nothing is left for
    forward to decide."""
    net = NETWORKS[net_name]()
    plan = compile_plan(net, method=SIMD, fuse=True)
    shapes = infer_param_shapes(net)
    cur = tuple(net.input_shape)
    for step in plan.steps:
        assert step.in_shape == cur
        cur = step.out_shape
        if step.kind in ("fused", "chain"):
            assert step.method is SIMD
            assert step.group is not None and step.kwargs is not None
            assert "lrn_n" in step.kwargs
        elif step.kind == "fc":
            assert step.d_in == shapes[step.spec.name][0]
        # the paper nets express activations as conv/pool relu flags, so
        # a fully-folded plan has no standalone relu steps
        assert step.kind != "relu"
    assert plan.steps[-1].kind == "softmax"
    assert cur == (net.num_classes,)
    # every original layer is covered exactly once, in order
    covered = [n for s in plan.steps for n in s.names]
    assert covered == [l.name for l in net.layers]


def _relu_net():
    return NetworkDef("t", (3, 16, 16), 4, (
        LayerSpec("conv", "c", out_channels=4, kernel=(3, 3)),
        LayerSpec("relu", "r"),
        LayerSpec("pool", "p", kernel=(2, 2), stride=(2, 2)),
        LayerSpec("relu", "r2"),
    ))


def test_standalone_relu_folds_at_compile_time():
    plan = compile_plan(_relu_net(), method=SIMD, fuse=False)
    assert [s.kind for s in plan.steps] == ["conv", "pool"]
    assert plan.steps[0].relu and plan.steps[0].names == ("c", "r")
    assert plan.steps[1].relu and plan.steps[1].names == ("p", "r2")
    # fuse_relu=False: the activations stay their own steps, un-reordered
    plan_nf = compile_plan(_relu_net(), method=SIMD, fuse=False,
                           fuse_relu=False)
    assert [s.kind for s in plan_nf.steps] == ["conv", "relu", "pool",
                                               "relu"]
    assert not plan_nf.steps[0].relu


def test_collect_sees_folded_relu_names():
    """Folded standalone ReLUs still report under their own layer name in
    ``collect`` (instrumentation parity with the per-layer interpreter)."""
    net = _relu_net()
    eng = CNNEngine(net, method=SIMD)
    params = eng.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, *net.input_shape),
                          jnp.float32)
    acts = {}
    eng.forward(params, x, collect=acts)
    assert set(acts) == {"c", "r", "p", "r2"}
    assert jnp.array_equal(acts["c"], acts["r"])  # conv records post-fold


def test_planner_runs_once_per_config(monkeypatch):
    """compile_plan subsumes plan_fusion: the planner runs once per
    (config, fuse) — repeated forwards re-use the compiled plan, and only
    a knob mutation forces a re-plan."""
    calls = {"n": 0}
    real = plan_mod.plan_fusion

    def counting(*a, **k):
        calls["n"] += 1
        return real(*a, **k)

    monkeypatch.setattr(plan_mod, "plan_fusion", counting)
    net = NETWORKS["lenet5"]()
    eng = CNNEngine(net, method=SIMD)
    params = eng.init(jax.random.PRNGKey(0))
    x = jnp.ones((2, *net.input_shape), jnp.float32)
    eng.forward(params, x)
    eng.forward(params, x)
    eng.fusion_report()
    assert calls["n"] == 1
    eng.per_layer_fuse["conv1"] = False  # knob mutation -> re-plan
    eng.forward(params, x)
    assert calls["n"] == 2


# ---------------------------------------------------------------------------
# plan ↔ legacy forward equivalence (3 nets × methods × fuse settings)
# ---------------------------------------------------------------------------

_NET_BATCH = {"lenet5": 3, "cifar10": 3, "alexnet": 1}  # ragged on purpose


@pytest.fixture(scope="module", params=["lenet5", "cifar10", "alexnet"])
def net_params_ref(request):
    net = NETWORKS[request.param]()
    eng = CNNEngine(net, method=Method.SEQ_REF)
    params = eng.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (_NET_BATCH[request.param], *net.input_shape),
                          jnp.float32)
    return net, params, x, eng.forward(params, x)


@pytest.mark.parametrize("fuse", [False, True])
@pytest.mark.parametrize("method", [Method.SEQ_REF, Method.BASIC_SIMD,
                                    Method.ADVANCED_SIMD_8])
def test_plan_forward_matches_reference(net_params_ref, method, fuse):
    net, params, x, ref = net_params_ref
    eng = CNNEngine(net, method=method)
    out = eng.forward(params, x, fuse=fuse)
    assert out.shape == ref.shape
    assert jnp.max(jnp.abs(out - ref)) < 1e-4


# ---------------------------------------------------------------------------
# batch-bucketed jit cache
# ---------------------------------------------------------------------------


def test_batch_bucket_rounding():
    assert [CNNEngine.batch_bucket(n) for n in range(1, 10)] == \
        [1, 2, 4, 4, 8, 8, 8, 8, 16]
    with pytest.raises(ValueError):
        CNNEngine.batch_bucket(0)


def test_bucketed_cache_compile_bound():
    """Batch sizes 1..max_batch compile at most log2(max_batch)+1 jitted
    variants, repeat sizes within a bucket add zero, and the padded rows
    never leak into the sliced-back outputs."""
    max_batch = 8
    net = NETWORKS["lenet5"]()
    eng = CNNEngine(net, method=SIMD)
    params = eng.init(jax.random.PRNGKey(0))
    xs = jax.random.normal(jax.random.PRNGKey(1),
                           (max_batch, *net.input_shape), jnp.float32)
    for n in range(1, max_batch + 1):
        out = eng.forward_batched(params, xs[:n])
        assert out.shape == (n, net.num_classes)
    stats = eng.bucket_stats()
    assert stats["compiles"] <= max_batch.bit_length()  # log2(8)+1 = 4
    assert stats["buckets"] == [(True, 1), (True, 2), (True, 4), (True, 8)]
    # repeat every size: zero recompiles (the bucket jits are warm)
    for n in range(1, max_batch + 1):
        eng.forward_batched(params, xs[:n])
    assert eng.bucket_stats()["compiles"] == stats["compiles"]
    # each bucket jit only ever saw its one padded shape
    for fn in eng._bucket_jits.values():
        if hasattr(fn, "_cache_size"):
            assert fn._cache_size() == 1
    # padding correctness: a frame's row is byte-identical whatever its
    # batchmates within a bucket (zero-pad rows are just batchmates)
    a = eng.forward_batched(params, xs[:3])  # bucket 4, one pad row
    b = eng.forward_batched(params, xs[:4])  # bucket 4, no pad
    assert jnp.array_equal(a, b[:3])
    # and the sliced result agrees with the eager per-plan forward
    eager = eng.forward(params, xs[:3])
    assert jnp.max(jnp.abs(a - eager)) < 1e-5


# ---------------------------------------------------------------------------
# knob invalidation (the stale-plan bugfix)
# ---------------------------------------------------------------------------


def test_knob_setters_invalidate_plan_and_jits():
    net = NETWORKS["lenet5"]()
    eng = CNNEngine(net, method=SIMD)
    params = eng.init(jax.random.PRNGKey(0))
    x = jnp.ones((2, *net.input_shape), jnp.float32)
    eng.forward_batched(params, x)
    p0 = eng.plan(True)
    jf0 = eng.jit_forward(True)
    assert eng.bucket_stats()["buckets"]
    eng.oh_block = 4  # scalar knob assignment
    assert eng.plan(True) is not p0
    assert eng.jit_forward(True) is not jf0
    assert eng.bucket_stats()["buckets"] == []  # bucket jits dropped too


def test_noop_knob_writes_keep_warm_caches():
    """Idempotently re-asserting the current config (same scalar value,
    same-key setdefault, equal-content update) must NOT drop the warm
    plans/jits — the steady-state serving loop depends on never
    recompiling."""
    net = NETWORKS["lenet5"]()
    eng = CNNEngine(net, method=SIMD, oh_block=4,
                    per_layer_oh_blocks={"conv1": 2})
    p0 = eng.plan(True)
    jf0 = eng.jit_forward(True)
    eng.method = SIMD
    eng.oh_block = 4
    eng.per_layer_oh_blocks["conv1"] = 2            # same value
    eng.per_layer_oh_blocks.setdefault("conv1", 9)  # pure read
    eng.per_layer_oh_blocks.update({"conv1": 2})    # equal content
    eng.per_layer_fuse |= {}                        # empty merge
    assert eng.plan(True) is p0 and eng.jit_forward(True) is jf0
    eng.oh_block = 8  # a REAL change still invalidates
    assert eng.plan(True) is not p0


def test_per_layer_fuse_mutation_replans():
    """Mutating per_layer_fuse after the first forward used to keep
    serving the memoized old plan; it must re-plan."""
    net = NETWORKS["lenet5"]()
    eng = CNNEngine(net, method=SIMD)
    params = eng.init(jax.random.PRNGKey(0))
    x = jnp.ones((2, *net.input_shape), jnp.float32)
    eng.forward(params, x)  # memoizes the fused plan
    assert ("conv1", "pool1") in fusion_summary(eng.plan(True))
    eng.per_layer_fuse["conv1"] = False  # in-place dict mutation
    assert all("conv1" not in g for g in fusion_summary(eng.plan(True)))
    eng.forward(params, x)  # and the new plan actually executes
    # |= through an alias must invalidate too (dict.__ior__ would
    # bypass the overridden update())
    alias = eng.per_layer_fuse
    alias |= {"conv2": False}
    assert fusion_summary(eng.plan(True)) == []


def test_per_layer_method_mutation_replans():
    net = NETWORKS["lenet5"]()
    eng = CNNEngine(net, method=SIMD)
    assert fusion_summary(eng.plan(True))
    eng.per_layer_methods.update({"conv1": Method.BASIC_PARALLEL})
    groups = fusion_summary(eng.plan(True))
    assert all("conv1" not in g for g in groups)
    eng.method = Method.BASIC_PARALLEL  # engine-wide method reassignment
    assert fusion_summary(eng.plan(True)) == []


def test_clear_caches_covers_bucket_cache():
    net = NETWORKS["lenet5"]()
    eng = CNNEngine(net, method=SIMD)
    params = eng.init(jax.random.PRNGKey(0))
    eng.forward_batched(params, jnp.ones((3, *net.input_shape), jnp.float32))
    assert eng._plans and eng._bucket_jits
    assert eng.bucket_stats()["compiles"] == 1
    eng.clear_caches()
    assert not eng._plans and not eng._jit_cache and not eng._bucket_jits
    # the compile counter tracks the live cache: a post-invalidation
    # sweep starts the bound from zero instead of double-counting
    assert eng.bucket_stats()["compiles"] == 0


# ---------------------------------------------------------------------------
# fusion report reads straight off the plan
# ---------------------------------------------------------------------------


def test_fusion_report_off_plan():
    net = NETWORKS["alexnet"]()
    eng = CNNEngine(net, method=SIMD, use_pallas=True)
    report = eng.fusion_report()
    assert [g["group"] for g in report] == \
        ["+".join(g) for g in fusion_summary(eng.plan(True))]
    for g in report:
        assert g["rows_per_cell"] >= 1 and g["n_tiles"] >= 1
        assert len(g["out_hw"]) == 2
