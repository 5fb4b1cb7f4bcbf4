"""The learning rate a configuration states.

``train_args.base_lr`` is HandyRL's rate per trained frame of a batch:
the program trains at ``base_lr x batch_size x forward_steps`` (annealed
by ``1 / (1 + steps x 1e-5)`` at each epoch boundary), and so does the
plain reference's ``follow``, which reads the same key from the same
section.  Absent, both keep HandyRL's 3e-8.

The program has no config key for it yet (``Trainer.default_lr`` is set
from a constant and ``Config.from_dict`` refuses a key it does not
know), so until it has one the harness withholds the key from the
``Learner``'s arguments and states the rate through the trainer's own
attribute and its optimiser's injected rate, as the trainer itself
does at every epoch boundary.  Nothing is compiled for it: the rate is
a leaf of the optimiser state the fused step is handed.
"""

KEY = "base_lr"


def program_args(args):
    """``args`` as the ``Learner`` takes them: without the key."""
    if KEY not in args["train_args"]:
        return args
    return dict(args, train_args={
        k: v for k, v in args["train_args"].items() if k != KEY})


def state(trainer, train):
    """Before the first step: the trainer trains at the stated rate."""
    if KEY not in train:
        return
    from handyrl_tpu.ops.update import set_learning_rate

    trainer.default_lr = float(train[KEY])
    trainer.opt_state = set_learning_rate(
        trainer.opt_state, trainer.default_lr * trainer.data_cnt_ema)
