"""The check's control at a size a test run holds: the float32 reference
computed with float8 matmul inputs, put in the program's place, must fail
the cells' limits, where the reference against itself reads 0."""
import numpy as np
import pytest

from bench import check, weights
from bench import run as R
from bench.reference import Reference
from bench.tests import tiny
from repro.models.registry import get_model


@pytest.fixture(scope="module")
def model():
    return get_model(R.program_config(tiny.spec()))


@pytest.mark.parametrize("seed", [1, 2, 2**32 + 3])
def test_control_fails_the_limits(model, seed):
    spec = tiny.spec()
    pub = weights.published(weights.build(model, spec, seed), spec)
    ref = Reference(spec, pub)
    ctl = Reference(spec, pub, precision="fp8")
    rng = np.random.default_rng(seed)
    sample = [(rng.integers(0, 512, 24).astype(np.int32),
               list(rng.integers(0, 512, 40))) for _ in range(2)]
    assert check.control_gap(ref, ref, sample)[0] == 0.0
    reading, n = check.control_gap(ref, ctl, sample)
    assert n == 80
    for cell in ("qwen2-1.5b-lcd4.chat", "starcoder2-15b-lcd2.batch"):
        assert reading > check.limits(cell)["max_gap"]["limit"]
