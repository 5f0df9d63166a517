"""``reference.py`` against the engine's Pallas path (interpret mode on
the CPU) at small sizes."""
import numpy as np
import pytest

from benchmarks.chip import reference, spec
from benchmarks.chip.test_chipbench_harness import LENET5
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


def test_seeds_beyond_32_bits_stay_distinct():
    keys = {tuple(np.asarray(reference.weights_key(s)).tolist())
            for s in (5, 5 + 2**31, 5 + 2**32, 2**33 + 5)}
    assert len(keys) == 4
