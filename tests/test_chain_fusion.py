"""Fused conv→conv chain tests: planner chain grouping + shorter-chain
fallback, chain-kernel correctness (interpret-mode Pallas vs the
per-layer ladder), the one-NHWC-pass XLA analogue, and the shared VMEM
working-set model (monotonicity + planner↔kernel agreement)."""
import jax
import jax.numpy as jnp
import pytest

from repro.core.engine import CNNEngine, _lrn
from repro.core.fusion import (
    FusedLayerSpec,
    chain_working_set,
    fused_working_set,
    fusion_summary,
    layers_as_chain,
    plan_fusion,
)
from repro.core.methods import Method, conv2d_chain_fused
from repro.core.netdefs import NETWORKS, LayerSpec, NetworkDef
from repro.kernels.conv2d import kernels as K
from repro.kernels.conv2d.ops import SUBLANES, conv2d_chain
from repro.kernels.conv2d.ref import conv2d_ref
from repro.kernels.pool2d.ref import pool2d_ref

SIMD = Method.ADVANCED_SIMD_8


# ---------------------------------------------------------------------------
# planner: chain grouping
# ---------------------------------------------------------------------------


def _conv(name, oc, k=3, pad=1, relu=True):
    return LayerSpec("conv", name, out_channels=oc, kernel=(k, k),
                     padding=(pad, pad), relu=relu)


def test_planner_chains_alexnet_conv3_to_pool5():
    """The MAC-heaviest stretch of the paper's Table 2 networks fuses as
    ONE group: conv3→conv4→conv5+pool5."""
    plan = plan_fusion(NETWORKS["alexnet"](), method_for=lambda n: SIMD)
    groups = fusion_summary(plan)
    assert ("conv3", "conv4", "conv5", "pool5") in groups
    (chain,) = [it for it in plan if isinstance(it, FusedLayerSpec)
                and len(it.convs) > 1]
    assert [cv.name for cv in chain.convs] == ["conv3", "conv4", "conv5"]
    assert chain.relus == (True, True, True)
    assert chain.pool is not None and chain.pool.name == "pool5"


def test_planner_chain_without_pool_tail():
    net = NetworkDef("t", (3, 16, 16), 4, (
        _conv("c1", 8), _conv("c2", 8),
        LayerSpec("flatten", "flatten"),
        LayerSpec("fc", "f1", out_channels=4),
    ))
    plan = plan_fusion(net, method_for=lambda n: SIMD)
    assert fusion_summary(plan) == [("c1", "c2")]
    (g,) = [it for it in plan if isinstance(it, FusedLayerSpec)]
    assert g.pool is None and len(g.convs) == 2


def test_planner_lone_conv_never_groups():
    net = NetworkDef("t", (3, 16, 16), 4, (
        _conv("c1", 8),
        LayerSpec("flatten", "flatten"),
        LayerSpec("fc", "f1", out_channels=4),
    ))
    assert fusion_summary(plan_fusion(net, method_for=lambda n: SIMD)) == []


def test_planner_chain_absorbs_standalone_relus():
    net = NetworkDef("t", (3, 16, 16), 4, (
        _conv("c1", 8, relu=False), LayerSpec("relu", "r1"),
        _conv("c2", 8, relu=False), LayerSpec("relu", "r2"),
        LayerSpec("pool", "p", kernel=(2, 2), stride=(2, 2)),
    ))
    plan = plan_fusion(net, method_for=lambda n: SIMD)
    assert fusion_summary(plan) == [("c1", "r1", "c2", "r2", "p")]
    (g,) = plan
    assert g.relus == (True, True)


def test_planner_chain_breaks_on_opt_out_and_method_mismatch():
    net = NETWORKS["alexnet"]()
    # conv4 opted out: conv3 is a lone conv (no group), conv4 per-layer,
    # conv5+pool5 still fuse
    groups = fusion_summary(plan_fusion(net, method_for=lambda n: SIMD,
                                        no_fuse={"conv4"}))
    assert ("conv5", "pool5") in groups
    assert not any("conv3" in g or "conv4" in g for g in groups)
    # a method change between conv4 and conv5 splits the chain there
    meth = lambda n: Method.BASIC_SIMD if n == "conv5" else SIMD
    groups = fusion_summary(plan_fusion(net, method_for=meth))
    assert ("conv3", "conv4") in groups
    assert ("conv5", "pool5") in groups


def test_planner_unfoldable_relu_ends_chain_before_pool():
    net = NetworkDef("t", (3, 16, 16), 4, (
        _conv("c1", 8), _conv("c2", 8), LayerSpec("relu", "r"),
        LayerSpec("pool", "p", kernel=(2, 2), stride=(2, 2)),
    ))
    # fuse_relu=False: the chain may not absorb r, so the pool (behind
    # it) stays out — but the conv→conv chain itself still fuses
    assert fusion_summary(plan_fusion(
        net, method_for=lambda n: SIMD,
        fuse_relu=False)) == [("c1", "c2")]


def test_planner_falls_back_to_shorter_chain():
    """When the full chain's floor cell busts the budget, trailing convs
    are dropped one at a time — the detached tail re-enters the scan and
    groups among itself — before fusion is declined outright."""
    net = NetworkDef("t", (64, 16, 64), 4, (
        _conv("c1", 64), _conv("c2", 64), _conv("c3", 64),
        LayerSpec("pool", "p", kernel=(2, 2), stride=(2, 2)),
    ))
    full = fusion_summary(plan_fusion(net, method_for=lambda n: SIMD))
    assert full == [("c1", "c2", "c3", "p")]
    # budget that fits a 2-chain floor cell but not the 3-chain's, not
    # even with the oc-blocked final stage (the new admission rung sits
    # between "full chain" and "drop the trailing conv")
    convs = [l for l in net.layers if l.kind == "conv"]
    pool = net.layers[-1]
    need3 = chain_working_set(convs, pool, SIMD, 64, 16, 64)
    need3_blocked = chain_working_set(convs, pool, SIMD, 64, 16, 64,
                                      oc_block_final=8)
    need2 = chain_working_set(convs[:2], None, SIMD, 64, 16, 64)
    assert need2 < need3_blocked < need3
    groups = fusion_summary(plan_fusion(net, method_for=lambda n: SIMD,
                                        vmem_budget=(need2 + need3) // 2))
    assert groups == [("c1", "c2"), ("c3", "p")]
    # a budget below every floor cell declines fusion entirely
    assert fusion_summary(plan_fusion(net, method_for=lambda n: SIMD,
                                      vmem_budget=1024)) == []
    # the XLA analogue has no VMEM ceiling: vmem_check=False keeps the
    # full chain regardless of budget
    assert fusion_summary(plan_fusion(
        net, method_for=lambda n: SIMD, vmem_check=False)) == full


def test_planner_blocks_final_stage_before_dropping_conv():
    """A budget too small for the full chain but large enough for its
    oc-blocked-final-stage variant keeps the WHOLE chain, with
    ``oc_block_final`` recorded on the group — the new admission rung
    fires before any trailing conv is popped.  The rung blocks at the
    128-lane MXU tile, so the final stage is wider than that."""
    net = NetworkDef("t", (64, 16, 64), 4, (
        _conv("c1", 64), _conv("c2", 64), _conv("c3", 512),
        LayerSpec("pool", "p", kernel=(2, 2), stride=(2, 2)),
    ))
    convs = [l for l in net.layers if l.kind == "conv"]
    pool = net.layers[-1]
    need3 = chain_working_set(convs, pool, SIMD, 64, 16, 64)
    need3_blocked = chain_working_set(convs, pool, SIMD, 64, 16, 64,
                                      oc_block_final=128)
    assert need3_blocked < need3
    plan = plan_fusion(net, method_for=lambda n: SIMD,
                       vmem_budget=(need3_blocked + need3) // 2)
    assert fusion_summary(plan) == [("c1", "c2", "c3", "p")]
    (g,) = [it for it in plan if isinstance(it, FusedLayerSpec)]
    assert g.oc_block_final == 128


def test_chain_cell_bytes_shrinks_with_oc_block_final():
    """Blocking the final stage must shrink the modelled cell: the final
    weight block and the final accumulator/output tiles drop from
    full-width oc to the block."""
    chain = ((3, 3, 1, 1, 1, 1), (3, 3, 1, 1, 1, 1), (3, 3, 1, 1, 1, 1))
    ocs = (384, 384, 256)
    for pool in ((3, 3, 2, 2), None):
        for im2col in (True, False):
            full = K.chain_cell_bytes(2, 13, 13, 256, chain, ocs, pool,
                                      im2col=im2col)
            blocked = K.chain_cell_bytes(2, 13, 13, 256, chain, ocs, pool,
                                         im2col=im2col, oc_block_final=8)
            assert blocked < full
            # monotone in the block width, capped at full width
            sizes = [K.chain_cell_bytes(2, 13, 13, 256, chain, ocs, pool,
                                        im2col=im2col, oc_block_final=b)
                     for b in (8, 32, 128, 256)]
            assert sizes == sorted(sizes)
            assert sizes[-1] == full


# ---------------------------------------------------------------------------
# chain Pallas kernel vs the per-layer reference (interpret mode)
# ---------------------------------------------------------------------------


def _chain_case(n, c, h, w_, ocs, ks, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, c, h, w_),
                          jnp.float32)
    ws, bs = [], []
    ci = c
    for i, (oc, k) in enumerate(zip(ocs, ks)):
        ws.append(jax.random.normal(jax.random.PRNGKey(seed + 10 + i),
                                    (oc, ci, k, k)) * 0.1)
        bs.append(jax.random.normal(jax.random.PRNGKey(seed + 20 + i),
                                    (oc,)))
        ci = oc
    return x, tuple(ws), tuple(bs)


def _ref_chain(x, ws, bs, strides, pads, relus):
    for w, b, s, p, r in zip(ws, bs, strides, pads, relus):
        x = conv2d_ref(x, w, b, s, p, relu=r)
    return x


@pytest.mark.parametrize("method", ["basic_simd", "advanced_simd_128"])
@pytest.mark.parametrize("depth", [2, 3])
@pytest.mark.parametrize("pool", [None, ("max", (3, 3), (2, 2)),
                                  ("avg", (2, 2), (2, 2))])
def test_chain_kernel_matches_per_layer(method, depth, pool):
    """methods × chain lengths 2–3 × with/without pool tail (the ISSUE's
    acceptance matrix), against the per-layer reference ladder."""
    ocs = (7, 6, 9)[:depth]
    ks = (3, 3, 5)[:depth]
    strides = (((1, 1),) * depth)
    pads = tuple((k // 2, k // 2) for k in ks)
    relus = (True,) * (depth - 1) + (False,)
    x, ws, bs = _chain_case(2, 5, 20, 18, ocs, ks)
    ref = _ref_chain(x, ws, bs, strides, pads, relus)
    kwargs = {}
    if pool is not None:
        kind, pk, ps = pool
        ref = pool2d_ref(ref, pk, ps, kind)
        kwargs = dict(pool_kernel=pk, pool_stride=ps, pool_kind=kind)
    out = conv2d_chain(x, ws, bs, strides, pads, relus, method=method,
                       interpret=True, **kwargs)
    assert out.shape == ref.shape
    assert jnp.max(jnp.abs(out - ref)) < 1e-4


@pytest.mark.parametrize("method", ["basic_simd", "advanced_simd_128"])
def test_chain_kernel_multi_tile_strided(method):
    """A tiny oh_block forces several bands per frame: the composed halo,
    the intermediate vertical-padding masking, and a strided middle stage
    must all band correctly."""
    x, ws, bs = _chain_case(1, 4, 33, 21, (6, 5), (3, 5), seed=3)
    strides = ((1, 1), (2, 2))
    pads = ((1, 1), (2, 2))
    relus = (True, True)
    ref = _ref_chain(x, ws, bs, strides, pads, relus)
    for ohb in (4, 1):
        out = conv2d_chain(x, ws, bs, strides, pads, relus, method=method,
                           interpret=True, oh_block=ohb)
        assert jnp.max(jnp.abs(out - ref)) < 1e-4


@pytest.mark.parametrize("method", ["basic_simd", "advanced_simd_128"])
@pytest.mark.parametrize("lrn_n", [4, 5])  # even n: asymmetric padding
def test_chain_lrn_tail(method, lrn_n):
    """conv→conv→pool→LRN in one cell, including `engine._lrn`'s even-n
    asymmetric window padding."""
    lrn_kw = dict(lrn_alpha=2e-2, lrn_beta=0.75, lrn_k=2.0)
    x, ws, bs = _chain_case(1, 4, 18, 16, (6, 7), (3, 3), seed=5)
    strides, pads, relus = ((1, 1),) * 2, ((1, 1),) * 2, (True, True)
    ref = pool2d_ref(_ref_chain(x, ws, bs, strides, pads, relus),
                     (3, 3), (2, 2), "max")
    ref = _lrn(ref, LayerSpec("lrn", "n", lrn_n=lrn_n, **lrn_kw))
    out = conv2d_chain(x, ws, bs, strides, pads, relus, method=method,
                       interpret=True, pool_kernel=(3, 3),
                       pool_stride=(2, 2), lrn_n=lrn_n, **lrn_kw)
    assert jnp.max(jnp.abs(out - ref)) < 1e-4


@pytest.mark.parametrize("obf", [4, 8])
@pytest.mark.parametrize("pool", [None, ("max", (3, 3), (2, 2))])
def test_chain_oc_block_final_matches_per_layer(obf, pool):
    """The oc-blocked final stage: the outer oc-tile grid axis recomputes
    the upstream stages per tile but must reproduce the full-width chain
    exactly (same fp32 accumulation order per output element)."""
    x, ws, bs = _chain_case(2, 5, 20, 18, (7, 6, 9), (3, 3, 5), seed=11)
    strides = ((1, 1),) * 3
    pads = ((1, 1), (1, 1), (2, 2))
    relus = (True, True, False)
    ref = _ref_chain(x, ws, bs, strides, pads, relus)
    kwargs = {}
    if pool is not None:
        kind, pk, ps = pool
        ref = pool2d_ref(ref, pk, ps, kind)
        kwargs = dict(pool_kernel=pk, pool_stride=ps, pool_kind=kind)
    for ohb in (None, 4):
        out = conv2d_chain(x, ws, bs, strides, pads, relus,
                           method="advanced_simd_128", interpret=True,
                           oh_block=ohb, oc_block_final=obf, **kwargs)
        assert out.shape == ref.shape
        assert jnp.max(jnp.abs(out - ref)) < 1e-4


def test_chain_oc_block_final_rejects_lrn():
    x, ws, bs = _chain_case(1, 3, 12, 12, (4, 4), (3, 3))
    strides, pads, relus = ((1, 1),) * 2, ((1, 1),) * 2, (True, True)
    with pytest.raises(ValueError, match="LRN"):
        conv2d_chain(x, ws, bs, strides, pads, relus,
                     method="advanced_simd_128", interpret=True,
                     pool_kernel=(2, 2), pool_stride=(2, 2), lrn_n=5,
                     oc_block_final=4)


def test_chain_rejects_non_simd_and_bare_lrn():
    x, ws, bs = _chain_case(1, 3, 8, 8, (4, 4), (3, 3))
    strides, pads, relus = ((1, 1),) * 2, ((1, 1),) * 2, (True, True)
    with pytest.raises(ValueError, match="SIMD"):
        conv2d_chain(x, ws, bs, strides, pads, relus,
                     method="basic_parallel", interpret=True)
    with pytest.raises(ValueError, match="pool"):
        conv2d_chain(x, ws, bs, strides, pads, relus,
                     method="basic_simd", interpret=True, lrn_n=5)
    with pytest.raises(ValueError, match="SIMD"):
        conv2d_chain_fused(x, ws, bs, Method.SEQ_REF, strides, pads, relus)


# ---------------------------------------------------------------------------
# the one-NHWC-pass XLA analogue
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", [Method.BASIC_SIMD, Method.ADVANCED_SIMD_4,
                                    Method.ADVANCED_SIMD_8])
@pytest.mark.parametrize("pool", [None, ("max", (3, 3), (2, 2))])
def test_chain_fused_xla_matches_per_layer(method, pool):
    x, ws, bs = _chain_case(2, 5, 20, 18, (7, 6, 9), (3, 3, 5), seed=7)
    strides = ((1, 1),) * 3
    pads = ((1, 1), (1, 1), (2, 2))
    relus = (True, True, True)
    ref = _ref_chain(x, ws, bs, strides, pads, relus)
    kwargs = {}
    if pool is not None:
        kind, pk, ps = pool
        ref = pool2d_ref(ref, pk, ps, kind)
        kwargs = dict(pool_kernel=pk, pool_stride=ps, pool_kind=kind)
    out = conv2d_chain_fused(x, ws, bs, method, strides, pads, relus,
                             use_pallas=False, **kwargs)
    assert out.shape == ref.shape
    assert jnp.max(jnp.abs(out - ref)) < 1e-4


# ---------------------------------------------------------------------------
# the shared VMEM working-set model
# ---------------------------------------------------------------------------


def test_fused_cell_bytes_monotone_in_phb_and_oc_block():
    """More pooled rows or a wider oc tile can only grow the modelled
    cell — the auto walks rely on it."""
    pool = (3, 3, 2, 2)
    args = dict(ow=54, wp=58, c=96, kh=5, kw=5, sy=1, pool=pool)
    for im2col in (True, False):
        sizes = [K.fused_cell_bytes(phb, oc_block=8, im2col=im2col, **args)
                 for phb in (1, 2, 4, 8, 16)]
        assert sizes == sorted(sizes) and len(set(sizes)) == len(sizes)
        sizes = [K.fused_cell_bytes(4, oc_block=ocb, im2col=im2col, **args)
                 for ocb in (4, 8, 32, 128)]
        assert sizes == sorted(sizes) and len(set(sizes)) == len(sizes)


def test_chain_cell_bytes_monotone_in_blk():
    chain = ((3, 3, 1, 1, 1, 1), (3, 3, 1, 1, 1, 1), (3, 3, 1, 1, 1, 1))
    ocs = (384, 384, 256)
    for pool in ((3, 3, 2, 2), None):
        for im2col in (True, False):
            sizes = [K.chain_cell_bytes(blk, 13, 13, 256, chain, ocs, pool,
                                        im2col=im2col)
                     for blk in (1, 2, 3, 4, 6)]
            assert sizes == sorted(sizes) and len(set(sizes)) == len(sizes)


@pytest.mark.parametrize("net_name", ["lenet5", "cifar10", "alexnet"])
@pytest.mark.parametrize("method", [Method.BASIC_SIMD, Method.ADVANCED_SIMD_4,
                                    Method.ADVANCED_SIMD_8])
def test_planner_kernel_agreement(net_name, method):
    """Every planner-approved group must resolve a band the kernel can
    actually stage: the executed block is ≥ 1 and its modelled cell fits
    the same budget the planner checked against."""
    net = NETWORKS[net_name]()
    eng = CNNEngine(net, method=method, use_pallas=True)
    plan = eng.plan(True)
    report = {g["group"]: g for g in eng.fusion_report()}
    c, h, w = net.input_shape
    for it in plan:
        if not isinstance(it, FusedLayerSpec):
            if it.kind == "conv":
                kh, kw = it.kernel
                h = (h + 2 * it.padding[0] - kh) // it.stride[0] + 1
                w = (w + 2 * it.padding[1] - kw) // it.stride[1] + 1
                c = it.out_channels
            elif it.kind == "pool":
                h = (h - it.kernel[0]) // it.stride[0] + 1
                w = (w - it.kernel[1]) // it.stride[1] + 1
            continue
        geo = report[it.name]
        assert geo["rows_per_cell"] >= 1 and geo["n_tiles"] >= 1
        im2col = method in (Method.ADVANCED_SIMD_4, Method.ADVANCED_SIMD_8)
        if len(it.convs) > 1:
            chain, ocs = layers_as_chain(it.convs)
            cp = -(-c // SUBLANES) * SUBLANES
            pool_t = (it.pool.kernel[0], it.pool.kernel[1],
                      it.pool.stride[0], it.pool.stride[1]) \
                if it.pool is not None else None
            assert K.chain_cell_bytes(
                geo["rows_per_cell"], h, w, cp, chain, ocs, pool_t,
                im2col=im2col) <= K.CHAIN_VMEM_BUDGET_BYTES
        else:
            # the planner's floor check implies the executed (equalized)
            # band also fits the soft budget — same model, larger-or-
            # equal band never smaller than floor ⇒ verify directly
            assert fused_working_set(
                it.convs[0], it.pool, method, c, w,
                lrn=it.lrn is not None) <= K.VMEM_BUDGET_BYTES
        for cv in it.convs:
            kh, kw = cv.kernel
            h = (h + 2 * cv.padding[0] - kh) // cv.stride[0] + 1
            w = (w + 2 * cv.padding[1] - kw) // cv.stride[1] + 1
        c = it.convs[-1].out_channels
        if it.pool is not None:
            h = (h - it.pool.kernel[0]) // it.pool.stride[0] + 1
            w = (w - it.pool.kernel[1]) // it.pool.stride[1] + 1


# ---------------------------------------------------------------------------
# whole-network: the alexnet chain end-to-end
# ---------------------------------------------------------------------------


def test_alexnet_chain_single_dispatch_interpret():
    """conv3→conv4→conv5+pool5 executes as ONE fused group on the Pallas
    path and the fused forward matches the sequential reference."""
    net = NETWORKS["alexnet"]()
    eng = CNNEngine(net, method=SIMD, use_pallas=True)
    groups = fusion_summary(eng.plan(True))
    assert ("conv3", "conv4", "conv5", "pool5") in groups
    ref_eng = CNNEngine(net, method=Method.SEQ_REF)
    params = ref_eng.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, *net.input_shape),
                          jnp.float32)
    ref = ref_eng.forward(params, x)
    out = eng.forward(params, x, fuse=True)
    assert jnp.max(jnp.abs(out - ref)) < 1e-4
