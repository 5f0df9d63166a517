"""``reference.py`` against the engine's Pallas path (interpret mode on
the CPU) at small sizes, and against a forward written out by hand for
a small branching graph."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmarks.chip import reference, spec
from benchmarks.chip.test_chipbench_harness import LENET5
from benchmarks.chip.test_chipbench_work import tiny_graph
from repro.core.engine import CNNEngine
from repro.core.netdefs import NETWORKS


@pytest.mark.parametrize("name", ["lenet5", "cifar10"])
def test_reference_matches_the_pallas_engine(name):
    config = LENET5 if name == "lenet5" else spec.config(name)
    seed = 2**31 + 17
    frames = np.random.default_rng(0).standard_normal(
        (2, *config["input"])).astype(np.float32)
    engine = CNNEngine(NETWORKS[name](), use_pallas=True)
    params = engine.init(reference.weights_key(seed))
    out = np.asarray(engine.forward_batched(params, frames))
    ref = reference.probabilities(config, seed, frames, block=2)
    assert out.shape == ref.shape == (2, config["num_classes"])
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=0)


def test_reference_weights_follow_the_stated_scheme():
    # the same draws as CNNEngine.init from the same key, made without it
    seed = 5
    engine = CNNEngine(NETWORKS["cifar10"]())
    want = engine.init(reference.weights_key(seed))
    got = reference.make_params(spec.config("cifar10"), seed)
    assert sorted(got) == sorted(want)
    for name in got:
        for k in ("w", "b"):
            np.testing.assert_array_equal(got[name][k], want[name][k])


@pytest.mark.parametrize("name", ["alexnet", "lenet5"])
def test_reference_weights_are_the_engines_draws(name):
    seed = 2**31 + 29
    want = CNNEngine(NETWORKS[name]()).init(reference.weights_key(seed))
    got = reference.make_params(
        LENET5 if name == "lenet5" else spec.config(name), seed)
    assert sorted(got) == sorted(want)
    for layer in got:
        for k in ("w", "b"):
            np.testing.assert_array_equal(got[layer][k], want[layer][k])


def test_gain_scales_one_layers_draw_and_nothing_else():
    seed = 2**32 + 7
    with_gain = reference.make_params(tiny_graph(), seed)
    cfg = tiny_graph()
    for layer in cfg["layers"]:
        layer.pop("gain", None)
    plain = reference.make_params(cfg, seed)
    assert sorted(with_gain) == sorted(plain)
    for name in plain:
        w = plain[name]["w"]
        np.testing.assert_array_equal(
            with_gain[name]["w"], 0.25 * w if name == "b1_conv3" else w)
        np.testing.assert_array_equal(with_gain[name]["b"], plain[name]["b"])


def _by_hand(p, x):
    """The tiny graph's forward, written out without ``reference.py``'s
    helpers: the grouped conv as one conv per group, the pools by
    explicit padding and slicing, the add and the fc as plain arithmetic."""
    hi = lax.Precision.HIGHEST

    def conv(x, name, stride, pad, w=None, b=None):
        w = p[name]["w"] if w is None else w
        b = p[name]["b"] if b is None else b
        y = lax.conv_general_dilated(x, w, (stride, stride),
                                     [(pad, pad), (pad, pad)],
                                     dimension_numbers=("NCHW", "OIHW",
                                                        "NCHW"),
                                     precision=hi)
        return y + b[None, :, None, None]

    relu = lambda v: jnp.maximum(v, 0.0)  # noqa: E731
    stem = relu(conv(x, "stem", 1, 1))                      # [N, 8, 16, 16]
    padded = jnp.pad(stem, ((0, 0), (0, 0), (1, 1), (1, 1)),
                     constant_values=-jnp.inf)              # [N, 8, 18, 18]
    pool0 = jnp.max(jnp.stack([padded[:, :, i:i + 15:2, j:j + 15:2]
                               for i in range(3) for j in range(3)]), 0)
    h = relu(conv(pool0, "b1_conv1", 1, 0))                 # [N, 4, 8, 8]
    w2, b2 = p["b1_conv2"]["w"], p["b1_conv2"]["b"]         # [4, 2, 3, 3]
    h = relu(jnp.concatenate(
        [conv(h[:, 2 * g:2 * g + 2], None, 2, 1, w2[2 * g:2 * g + 2],
              b2[2 * g:2 * g + 2]) for g in range(2)], axis=1))
    h = conv(h, "b1_conv3", 1, 0)                           # [N, 16, 4, 4]
    shortcut = conv(pool0, "b1_proj", 2, 0)                 # [N, 16, 4, 4]
    h = relu(h + shortcut)
    feat = jnp.mean(h, axis=(2, 3))                         # [N, 16]
    logits = jnp.matmul(feat, p["fc"]["w"], precision=hi) + p["fc"]["b"]
    return np.asarray(jax.nn.softmax(logits, axis=-1))


def test_graph_reference_matches_a_forward_by_hand():
    seed = 2**31 + 41
    frames = np.random.default_rng(3).standard_normal(
        (3, 3, 16, 16)).astype(np.float32)
    ref = reference.probabilities(tiny_graph(), seed, frames, block=2)
    want = _by_hand(reference.make_params(tiny_graph(), seed), frames)
    assert ref.shape == (3, 10)
    np.testing.assert_allclose(ref, want, atol=1e-6, rtol=0)


def test_a_padded_average_pool_counts_the_zeros():
    layer = {"kind": "pool", "name": "p", "pool": "avg", "kernel": [3, 3],
             "stride": [1, 1], "pad": [1, 1], "relu": False}
    out = np.asarray(reference._pool(jnp.ones((1, 1, 4, 4)), layer))
    assert out.shape == (1, 1, 4, 4)
    assert out[0, 0, 0, 0] == pytest.approx(4 / 9)   # a corner: 4 of 9
    assert out[0, 0, 0, 1] == pytest.approx(6 / 9)   # an edge: 6 of 9
    assert out[0, 0, 1, 1] == pytest.approx(1.0)


def test_seeds_beyond_32_bits_stay_distinct():
    keys = {tuple(np.asarray(reference.weights_key(s)).tolist())
            for s in (5, 5 + 2**31, 5 + 2**32, 2**33 + 5)}
    assert len(keys) == 4
