"""Mutation corpus for the kernel sanitizer (K1xx rules).

Each test seeds exactly one defect into the kernel SOURCE TEXT (via the
sanitizer's ``sources`` injection hook — the files on disk are never
touched), re-runs the abstract interpreter, and asserts the matching
K-rule fires.  A clean-pass test drives the full ``tools/sanitize.py``
sweep grid, and an independence test asserts the sanitizer derives its
band intervals without importing the resolver functions the verifier
trusts (the N-version-programming contract)."""
import ast
from pathlib import Path

import pytest

from repro.analysis import sanitizer
from repro.analysis.sanitizer import (
    sanitize_chain,
    sanitize_conv2d,
    sanitize_matmul,
    sanitize_pool2d,
)

KERNELS_ROOT = (Path(sanitizer.__file__).resolve().parent.parent
                / "kernels")


def _mutate(old: str, new: str, module: str = "conv2d", count: int = 0):
    """Seed one defect into a kernel source; returns the ``sources``
    mapping for the sanitize_* calls."""
    src = (KERNELS_ROOT / sanitizer.KERNEL_SOURCES[module]).read_text()
    assert old in src, f"mutation anchor not found: {old!r}"
    mutated = src.replace(old, new) if count == 0 else \
        src.replace(old, new, count)
    assert mutated != src
    return {module: mutated}


def _rules(findings):
    return {f.rule for f in findings}


def _sanitize_carry(sources=None):
    """A fused conv→pool dispatch whose geometry opens the carry gate
    (overlapping pool, 4 bands) with the knob forced on."""
    return sanitize_conv2d((2, 33, 21, 8), (3, 3, 8, 16), padding=(1, 1),
                           relu=True, im2col=True, oh_block=5,
                           pool_kernel=(3, 3), pool_stride=(2, 2),
                           pool_carry=True, sources=sources)


def _sanitize_halo(sources=None):
    """A fused conv→pool→LRN dispatch forced onto the two-pass
    channel-halo cell: oc_block 4 against 16 output channels gives 4 oc
    tiles, each reading lrn_n - 1 = 4 halo weight columns."""
    return sanitize_conv2d((2, 20, 18, 8), (5, 5, 8, 16), padding=(2, 2),
                           relu=True, im2col=True, oc_block=4,
                           pool_kernel=(3, 3), pool_stride=(2, 2),
                           lrn=(5, 2e-2, 0.75, 2.0), lrn_oc_block=True,
                           sources=sources)


# -- clean kernels prove clean ----------------------------------------------


def test_clean_full_sweep_grid():
    """The bundled kernels prove clean across the exact netdef x method
    x fuse x backend grid CI gates on — zero findings, including the
    K105 cross-check against the verifier's derivation."""
    import sys

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent
                           / "tools"))
    try:
        import sanitize as sanitize_cli
    finally:
        sys.path.pop(0)
    findings, combos, dispatches = sanitize_cli.sweep()
    # every bundled net x 3 methods x 2 fuse x 2 backends, plus the
    # forced second-generation cell configs (carry / channel-halo LRN /
    # oc-blocked chain final stage)
    assert combos == (12 * len(sanitize_cli.NETWORKS)
                      + len(sanitize_cli.EXTRA_CONFIGS))
    assert dispatches > 100
    assert findings == []


def test_clean_single_dispatches():
    for f, geom in (
        sanitize_conv2d((2, 28, 28, 8), (5, 5, 8, 16), padding=(2, 2),
                        relu=True, im2col=True, oh_block=5),
        sanitize_conv2d((2, 28, 28, 8), (5, 5, 8, 16), padding=(2, 2),
                        im2col=False, oh_block=5),
        sanitize_pool2d((2, 24, 24, 16), kernel=(3, 3), stride=(2, 2),
                        oh_block=4),
        sanitize_matmul((7, 130), (130, 33)),
        sanitize_chain((2, 28, 28, 8), [(3, 3, 8, 16), (3, 3, 16, 16)],
                       strides=[(1, 1), (1, 1)],
                       paddings=[(1, 1), (1, 1)], relus=[True, True],
                       pool_kernel=(2, 2), pool_stride=(2, 2),
                       oh_block=4),
        # second-generation cells: sliding-window pool carry, two-pass
        # channel-halo LRN, oc-blocked chain final stage
        _sanitize_carry(),
        _sanitize_halo(),
        sanitize_chain((2, 28, 28, 8), [(3, 3, 8, 16), (3, 3, 16, 16)],
                       strides=[(1, 1), (1, 1)],
                       paddings=[(1, 1), (1, 1)], relus=[True, True],
                       pool_kernel=(2, 2), pool_stride=(2, 2),
                       oh_block=4, oc_block_final=8),
        # residual joins: several bands, and oc tiles of 128 over 160
        # padded channels (advanced) or the full width (basic)
        _sanitize_residual(),
        sanitize_conv2d((2, 11, 9, 16), (1, 1, 16, 160), relu=True,
                        im2col=False, oh_block=4, residual=True),
        # a strided conv over two lane tiles of channels; basic_simd
        # with its oc grid (full-width weights bust the budget)
        sanitize_conv2d((2, 9, 10, 256), (3, 3, 256, 24), stride=(2, 2),
                        padding=(1, 1), im2col=True, oh_block=2),
        sanitize_conv2d((2, 14, 14, 1024), (1, 1, 1024, 2048),
                        stride=(2, 2), im2col=False),
        sanitize_pool2d((2, 114, 114, 64), kernel=(3, 3), stride=(2, 2)),
    ):
        assert f == []


def _sanitize_residual(sources=None):
    return sanitize_conv2d((2, 11, 9, 16), (1, 1, 16, 160), relu=True,
                           im2col=True, oh_block=4, residual=True,
                           sources=sources)


# -- K101: out-of-bounds loads ----------------------------------------------


def test_k101_index_map_offset():
    """+1 on the halo-band element offset walks the last band off the
    padded frame."""
    sources = _mutate("lambda i, t, o: (i, t * row_step, 0, 0)",
                      "lambda i, t, o: (i, t * row_step + 1, 0, 0)")
    f, _ = sanitize_conv2d((2, 28, 28, 8), (5, 5, 8, 16), im2col=True,
                           sources=sources)
    assert "K101" in _rules(f)


def test_k101_residual_block_a_band_late():
    """The shortcut's index map shifted by one band reads past the
    shortcut for the last band: a spec-level K101, and nothing else."""
    sources = _mutate("""in_specs.append(pl.BlockSpec((None, out_rows, out_cols, ocb),
                                     lambda i, t, o: (i, t, 0, o)))""",
                      """in_specs.append(pl.BlockSpec((None, out_rows, out_cols, ocb),
                                     lambda i, t, o: (i, t + 1, 0, o)))""")
    f, _ = _sanitize_residual(sources=sources)
    assert _rules(f) == {"K101"}
    assert any("in_spec 3" in x.detail for x in f)


def test_k101_body_load():
    """Starting the basic_simd kernel's strided patch load one row late
    reads past the input band's last row."""
    sources = _mutate("patch = x_ref[pl.ds(i, ohh, stride=sy)",
                      "patch = x_ref[pl.ds(i + 1, ohh, stride=sy)")
    f, _ = sanitize_conv2d((2, 28, 28, 8), (5, 5, 8, 16), im2col=False,
                           sources=sources)
    assert "K101" in _rules(f)


# -- K102: output coverage --------------------------------------------------


def test_k102_grid_undercount():
    """Dropping one band tile leaves output rows never stored."""
    sources = _mutate("grid=(n, n_tiles, oc // ocb),",
                      "grid=(n, n_tiles - 1, oc // ocb),")  # basic_simd
    f, _ = sanitize_conv2d((2, 28, 28, 8), (5, 5, 8, 16), padding=(2, 2),
                           im2col=False, oh_block=5, sources=sources)
    assert "K102" in _rules(f)


# -- K103: precision flow ---------------------------------------------------


def test_k103_f64_accumulate():
    sources = _mutate("patches.astype(ACC_DTYPE)",
                      "patches.astype(jnp.float64)")
    f, _ = sanitize_conv2d((2, 28, 28, 8), (5, 5, 8, 16), im2col=True,
                           sources=sources)
    assert "K103" in _rules(f)


def test_k103_double_downcast():
    sources = _mutate(
        "o_ref[...] = acc.reshape(ohh, oww, ocb).astype(o_ref.dtype)",
        "o_ref[...] = acc.astype(o_ref.dtype)"
        ".reshape(ohh, oww, ocb).astype(o_ref.dtype)")
    f, _ = sanitize_conv2d((2, 28, 28, 8), (5, 5, 8, 16), im2col=True,
                           sources=sources)
    assert "K103" in _rules(f)


# -- K104: chain intermediate-padding masks ---------------------------------

_CHAIN_MASK = "band = jnp.where((rows >= 0) & (rows < oh_valid), out, 0.0)"


def test_k104_missing_mask():
    """Padded 2-stage chain: stage 0's halo rows reach above the frame
    (b0 < 0), so dropping the row mask lets stage 1 consume garbage."""
    sources = _mutate(_CHAIN_MASK, "band = out")
    f, _ = sanitize_chain((2, 28, 28, 8), [(3, 3, 8, 8), (3, 3, 8, 8)],
                          strides=[(1, 1), (1, 1)],
                          paddings=[(1, 1), (1, 1)],
                          relus=[True, True], oh_block=4,
                          sources=sources)
    assert "K104" in _rules(f)


def test_k104_mask_not_required_when_no_garbage():
    """Same mutation on an unpadded single-tile chain: no halo row can
    hold garbage, so the missing mask is provably harmless."""
    sources = _mutate(_CHAIN_MASK, "band = out")
    f, _ = sanitize_chain((2, 16, 16, 8), [(3, 3, 8, 8), (3, 3, 8, 8)],
                          strides=[(1, 1), (1, 1)],
                          paddings=[(0, 0), (0, 0)],
                          relus=[True, True], sources=sources)
    assert "K104" not in _rules(f)


# -- K105: cross-derivation disagreement ------------------------------------


def test_k105_geometry_disagreement():
    """Tampering one field of the sanitizer's geometry dict must surface
    as a K105 against the verifier's resolver-backed derivation."""
    import sys

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent
                           / "tools"))
    try:
        import sanitize as sanitize_cli
    finally:
        sys.path.pop(0)
    from repro.core.methods import Method
    from repro.core.netdefs import NETWORKS
    from repro.core.plan import compile_plan

    plan = compile_plan(NETWORKS["lenet5"](), method=Method.BASIC_SIMD,
                        fuse=False, use_pallas=True, verify=False)
    step = next(s for s in plan.steps if s.kind == "conv")
    _, geom = sanitize_cli.sanitize_step(plan, step, "step")
    assert sanitize_cli._cross_check(geom, plan, step, "step") == []
    geom = dict(geom, band=geom["band"] + 1)
    bad = sanitize_cli._cross_check(geom, plan, step, "step")
    assert [f.rule for f in bad] == ["K105"]


# -- K106: VMEM scratch carry discipline ------------------------------------


def test_k106_stale_carry_rows():
    """Storing the HEAD of the fresh band instead of its tail leaves the
    next band step consuming rows that are not the boundary rows — the
    carry-discipline proof must fire exactly K106."""
    sources = _mutate(
        "jax.lax.slice_in_dim(fresh, r_rows - k_rows, r_rows, axis=0)",
        "jax.lax.slice_in_dim(fresh, 0, k_rows, axis=0)")
    f, _ = _sanitize_carry(sources=sources)
    assert _rules(f) == {"K106"}


def test_k106_carry_axis_not_arbitrary():
    """The carried (band) grid axis must be 'arbitrary': a parallel axis
    gives the compiler licence to reorder band steps and the scratch
    hand-off breaks."""
    sources = _mutate(
        'dimension_semantics=("parallel", "parallel", "arbitrary")',
        'dimension_semantics=("parallel", "parallel", "parallel")')
    f, _ = _sanitize_carry(sources=sources)
    assert "K106" in _rules(f)


def test_k106_needs_a_carry_dispatch():
    """The classic (no-scratch) fused cell must never draw K106."""
    f, _ = sanitize_conv2d((2, 33, 21, 8), (3, 3, 8, 16), padding=(1, 1),
                           relu=True, im2col=True, oh_block=5,
                           pool_kernel=(3, 3), pool_stride=(2, 2),
                           pool_carry=False)
    assert f == []


# -- K101 on the channel-halo cell: oc-tile under-fetch ----------------------


def test_k101_halo_weight_underfetch():
    """Dropping the host-side halo widening of the weight matrix leaves
    the unblocked weight spec reading ``lrn_n - 1`` columns past the
    operand for the last oc tile — a spec-level K101 under-fetch."""
    sources = _mutate("wmat = jnp.pad(wmat, ((0, 0), (halo_lo, halo_hi)))",
                      "wmat = jnp.pad(wmat, ((0, 0), (0, 0)))")
    f, _ = _sanitize_halo(sources=sources)
    assert "K101" in _rules(f)


# -- Phase-A re-derivations track the trusted resolvers ----------------------


@pytest.mark.parametrize("pool_carry", [None, True, False])
@pytest.mark.parametrize("pool,phb,n_tiles", [
    ((3, 3, 2, 2), 5, 4), ((3, 3, 2, 2), 1, 2), ((2, 2, 2, 2), 4, 3),
    ((3, 3, 1, 1), 2, 5), ((5, 5, 2, 2), 1, 3), ((3, 3, 2, 2), 5, 1),
])
def test_phase_a_pool_carry_matches_resolver(pool_carry, pool, phb,
                                             n_tiles):
    """The sanitizer's from-scratch carry gate must agree with the
    trusted kernel resolver over the whole config space (the K105
    N-version contract, checked directly)."""
    from repro.kernels.conv2d import kernels as K

    for im2col in (True, False):
        for lrn in (None, (5, 2e-2, 0.75, 2.0)):
            assert sanitizer._a_resolve_pool_carry(
                pool_carry, im2col, lrn, pool, phb, n_tiles) \
                == K.resolve_pool_carry(pool_carry, im2col, lrn, pool,
                                        phb, n_tiles)


@pytest.mark.parametrize("oc,c,kh", [(2048, 1024, 1), (512, 512, 3),
                                     (256, 64, 1), (96, 3, 11), (384, 768, 3)])
def test_phase_a_basic_ocb_matches_resolver(oc, c, kh):
    from repro.kernels.conv2d import kernels as K

    for ow, wp in ((7, 16), (56, 58)):
        args = (oc, ow, wp, c, kh, kh, 1)
        assert sanitizer._a_basic_ocb(*args) == K.resolve_basic_ocb(*args)


@pytest.mark.parametrize("lrn_oc_block", [None, True, False])
@pytest.mark.parametrize("oc,oc_block", [
    (96, 8), (96, 128), (16, 4), (8, 8), (2048, 8), (7, 4),
])
def test_phase_a_lrn_ocb_matches_resolver(lrn_oc_block, oc, oc_block):
    from repro.kernels.conv2d import kernels as K

    pool = (3, 3, 2, 2)
    for lrn in (None, (5, 2e-2, 0.75, 2.0), (4, 2e-2, 0.75, 2.0)):
        for ow, wp, c in ((54, 58, 8), (13, 17, 2048)):
            args = (oc, oc_block, lrn, lrn_oc_block, ow, wp, c, 5, 5, 1,
                    pool)
            assert sanitizer._a_resolve_lrn_ocb(*args) \
                == K.resolve_lrn_ocb(*args)


# -- K100: unproven dispatches fail loudly ----------------------------------


def test_k100_unsupported_construct():
    sources = _mutate("patches = jnp.concatenate(cols, axis=-1)",
                      "patches = jnp.stack(cols)")
    f, _ = sanitize_conv2d((2, 28, 28, 8), (5, 5, 8, 16), im2col=True,
                           sources=sources)
    assert _rules(f) == {"K100"}


def test_k100_entry_raise():
    f, geom = sanitize_conv2d((2, 24, 24, 8), (3, 3, 8, 32),
                              padding=(1, 1),
                              lrn=(5, 2.0, 1e-4, 0.75), im2col=True)
    assert _rules(f) == {"K100"}  # LRN without pool: the entry's raise


# -- independence: no trusted-resolver imports ------------------------------


def test_sanitizer_import_independence():
    """The sanitizer must derive every band interval itself: its module
    may import ONLY the stdlib and the findings taxonomy — never the
    kernel modules, fusion planner, or verifier it cross-checks."""
    tree = ast.parse(Path(sanitizer.__file__).read_text())
    imported = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            imported.append(node.module or "")
    assert "repro.analysis.findings" in imported
    for mod in imported:
        assert not mod.startswith(("repro.kernels", "repro.core")), mod
        assert "verifier" not in mod and "fusion" not in mod, mod
    # and the trusted resolvers specifically must not be reachable
    banned = ("group_band_params", "band_intervals", "resolve_oh_block",
              "step_band_params")
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            for a in node.names:
                assert a.name not in banned, a.name


def test_mutations_are_rule_precise():
    """A seeded K101 must not drag in unrelated K102/K103 noise (the
    interpreter clamps and continues after a violation)."""
    sources = _mutate("lambda i, t, o: (i, t * row_step, 0, 0)",
                      "lambda i, t, o: (i, t * row_step + 1, 0, 0)")
    f, _ = sanitize_conv2d((2, 28, 28, 8), (5, 5, 8, 16), im2col=True,
                           sources=sources)
    assert _rules(f) == {"K101"}


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-q"]))
