"""The benchmark's own tests: ``python -m pytest benchmarks/tests -q``
from the root of the repo, on the CPU."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest


@pytest.fixture
def follows_the_stated_rate():
    """``hold(config, train, primed, initial)``: the plain reference's
    three steps with ``base_lr`` absent, stated as HandyRL's own and
    stated ten times smaller; absent IS HandyRL's own, to the last bit,
    and the tenth moves the parameters a tenth as far."""
    import numpy as np

    from benchmarks.harness import check
    from benchmarks.reference.training import BASE_LR

    def hold(config, train, primed, initial, capacity=64):
        train = {k: v for k, v in train.items() if k != "base_lr"}
        change = {}
        for name, stated in (("absent", {}), ("same", {"base_lr": BASE_LR}),
                             ("tenth", {"base_lr": BASE_LR / 10})):
            _, _, final, _ = check.reference_follow(
                config, dict(train, **stated), primed, capacity, initial)
            change[name] = check.leaf_norms(final, minus=initial)
        assert (change["absent"] == change["same"]).all()
        moved = change["absent"] > 0
        assert moved.sum() > len(moved) // 2
        assert np.median(change["tenth"][moved] / change["absent"][moved]) \
            == pytest.approx(0.1, rel=0.1)

    return hold
