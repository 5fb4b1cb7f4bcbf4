"""``train_args.base_lr``: a configuration states the rate it trains at
and the harness hands it to the program (``harness/rate.py``); what the
plain references make of the key is held beside each training side
(``follows_the_stated_rate`` in ``conftest.py``)."""

import jax.numpy as jnp
import pytest

from benchmarks.harness import rate
from benchmarks.harness.cells import Cell, load_manifest

STATED = ("trinity.fed", "joyai.fed")
UNSTATED = ("geese.fed", "geister.fed")


class _Trainer:
    """What ``rate.state`` touches of the program's ``Trainer``."""

    def __init__(self, frames):
        from handyrl_tpu.ops.update import DEFAULT_LR, make_optimizer

        self.default_lr = DEFAULT_LR
        self.data_cnt_ema = frames
        self.optimizer = make_optimizer(DEFAULT_LR * frames)
        self.opt_state = self.optimizer.init({"w": jnp.zeros(3)})

    def rate(self):
        return float(self.opt_state.hyperparams["learning_rate"])


@pytest.mark.parametrize("name", STATED + UNSTATED)
def test_the_learner_is_handed_arguments_it_accepts(name):
    """The program refuses a train-args key it does not know: the key
    stays with the harness, and nothing else of the three sections
    changes."""
    from handyrl_tpu.config import Config

    args = Cell(load_manifest(), name).program_args()
    handed = rate.program_args(args)
    Config.from_dict(handed)
    assert (rate.KEY in args["train_args"]) == (name in STATED)
    kept = dict(args["train_args"])
    kept.pop(rate.KEY, None)
    assert handed["train_args"] == kept
    assert handed["env_args"] == args["env_args"]
    assert (handed is args) == (name in UNSTATED)   # no copy, no change


@pytest.mark.parametrize("name,update_limit", zip(STATED, (0.035, 0.052)))
def test_the_configuration_states_the_rate_its_users_train_at(
        name, update_limit):
    """1.0e-6 under Adam at the window's first step, as HandyRL's rate
    per trained frame of a batch, said to be an assumption; the limit
    on the parameters' change is the one the file had before."""
    config = Cell(load_manifest(), name).config
    train = config["train_args"]
    assert train["base_lr"] * train["batch_size"] * train["forward_steps"] \
        == pytest.approx(1.0e-6, rel=1e-9)
    assert "1.0e-6" in config["assumed"]["base_lr"]
    assert config["check_limits"]["update_gap"] == update_limit


@pytest.mark.parametrize("name", STATED)
def test_a_stated_rate_is_the_trainers_at_its_first_step(name):
    train = Cell(load_manifest(), name).program_args()["train_args"]
    frames = train["batch_size"] * train["forward_steps"]
    trainer = _Trainer(frames)
    rate.state(trainer, train)
    assert trainer.default_lr == train["base_lr"]
    assert trainer.rate() == pytest.approx(1.0e-6, rel=1e-6)
    assert trainer.opt_state.hyperparams["learning_rate"].dtype == jnp.float32
    # the anneal of an epoch boundary, as Trainer._finish_epoch states it
    assert trainer.default_lr * trainer.data_cnt_ema / (1 + 200 * 1e-5) \
        == pytest.approx(1.0e-6 / 1.002)


@pytest.mark.parametrize("name", UNSTATED)
def test_a_configuration_that_states_none_trains_as_it_did(name):
    from handyrl_tpu.ops.update import DEFAULT_LR

    train = Cell(load_manifest(), name).program_args()["train_args"]
    frames = train["batch_size"] * train["forward_steps"]
    trainer = _Trainer(frames)
    before = trainer.opt_state
    rate.state(trainer, train)
    assert trainer.opt_state is before and trainer.default_lr == DEFAULT_LR
    assert trainer.rate() == pytest.approx(3e-8 * frames, rel=1e-6)
