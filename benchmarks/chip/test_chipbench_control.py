"""The control comes out not correct: the reference at
``Precision.HIGH`` (three bfloat16 passes) in the program's place fails
every cell's limits.  It needs a TPU, where ``HIGH`` is lower than
``HIGHEST``; XLA's CPU backend computes float32 at both, so on a CPU
the test skips.  On the chip:

    python3 -m pytest -q benchmarks/chip/test_chipbench_control.py
"""
import jax
import pytest

from benchmarks.chip import check, control, spec
from benchmarks.chip.run import ROOT

CELLS = [c["name"] for c in spec.load_benchmark(ROOT)["workloads"]]


@pytest.fixture(scope="module")
def tpu():
    if jax.devices()[0].platform != "tpu":
        pytest.skip("the control reads lower than the program only on a TPU")


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct(tpu, cell):
    entry = spec.workload(spec.load_benchmark(ROOT), cell)
    config = spec.config(entry["config"])
    # a pool small enough for a test run, on three seeds
    traffic = dict(spec.traffic(entry["traffic"]), pool_frames=64)
    for seed in (2**31 + 101, 2**31 + 102, 2**31 + 103):
        readings = control.control_readings(config, traffic, seed)
        correct, rows = check.decide(readings, spec.limits(cell))
        assert not correct, rows
