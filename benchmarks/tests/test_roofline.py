"""Operation and byte counts against values worked by hand from the
layer shapes."""

import jax
import jax.numpy as jnp
import pytest
import yaml

from benchmarks.harness import roofline
from benchmarks.harness.cells import BENCH_DIR


def _shapes(tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
                        tree, is_leaf=lambda x: isinstance(x, tuple))


GEESE = {f"TorusConv_{i}": {"Conv_0": {"kernel": (3, 3, 17 if i == 0 else 32, 32)},
                            "GroupNorm_0": {"scale": (32,), "bias": (32,)}}
         for i in range(13)}
GEESE.update(Dense_0={"kernel": (32, 4)}, Dense_1={"kernel": (64, 1)})

GEISTER = {
    "Conv_0": {"kernel": (3, 3, 25, 32)},
    "GroupNorm_0": {"scale": (32,), "bias": (32,)},
    "DRC_0": {f"ConvLSTMCell_{i}": {"Conv_0": {"kernel": (3, 3, 64, 128),
                                               "bias": (128,)}}
              for i in range(3)},
    "Conv_1": {"kernel": (3, 3, 32, 8)},
    "GroupNorm_1": {"scale": (8,), "bias": (8,)},
    "Conv_2": {"kernel": (1, 1, 8, 4)},
    "Dense_0": {"kernel": (1, 70), "bias": (70,)},
    "ValueHead_0": {"Conv_0": {"kernel": (1, 1, 32, 2), "bias": (2,)},
                    "Dense_0": {"kernel": (72, 1)}},
    "ValueHead_1": {"Conv_0": {"kernel": (1, 1, 32, 2), "bias": (2,)},
                    "Dense_0": {"kernel": (72, 1)}},
}


def _config(name):
    with open(f"{BENCH_DIR}/configs/{name}.yaml") as f:
        return yaml.safe_load(f)


def test_geese32_counts():
    cfg = _config("geese32")
    fwd, elements = roofline.forward_counts(
        _shapes(GEESE), cfg["roofline"]["board_cells"])
    # stem 2*77*9*17*32 + 12 blocks of 2*77*9*32*32 + heads 2*32*4 + 2*64
    assert fwd == 753984 + 12 * 1419264 + 256 + 128 == 17785536
    assert elements == 13 * 77 * 32 + 4 + 1
    cost = roofline.step_cost(_shapes(GEESE), cfg["train_args"],
                              cfg["roofline"], ring_row_bytes=5400)
    # 256 windows x 8 steps x 1 seat, forward + backward = 3 forwards
    assert cost["flops"] == 2048 * 3 * 17785536
    n_params = 9 * 17 * 32 + 12 * 9 * 32 * 32 + 13 * 64 + 128 + 64
    assert cost["bytes"] == (256 * 8 * 5400 + 32 * n_params
                             + 2048 * elements * 2 * 2)
    share, bound = roofline.roofline(cost, "TPU v5 lite", 4.63e-3)
    assert bound == "compute"
    assert share == pytest.approx(100 * (cost["flops"] / 197e12) / 4.63e-3)
    assert 11.5 < share < 12.5      # the ledger's PR 23 read 11.98


def test_geister_drc_counts_the_cells_three_times_a_step():
    cfg = _config("geister_drc")
    geo = cfg["roofline"]
    fwd, _ = roofline.forward_counts(_shapes(GEISTER), 36,
                                     geo["kernel_repeats"])
    cell = 2 * 36 * 9 * 64 * 128
    heads = (2 * 36 * 9 * 32 * 8 + 2 * 36 * 8 * 4 + 2 * 70
             + 2 * (2 * 36 * 32 * 2 + 2 * 72))
    assert fwd == 2 * 36 * 9 * 25 * 32 + 9 * cell + heads
    once, _ = roofline.forward_counts(_shapes(GEISTER), 36)
    assert fwd - once == 6 * cell    # what bench.py's count leaves out
    cost = roofline.step_cost(_shapes(GEISTER), cfg["train_args"], geo, 1000)
    # 128 windows x 2 seats; 8 trained steps at 3 forwards, 4 burn-in at 1
    assert cost["flops"] == 256 * fwd * (3 * 8 + 4)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9")
