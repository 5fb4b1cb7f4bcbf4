"""The training side of ``olmo_hybrid_net``: ``trinity_training``'s as
it is -- windows that are whole token sequences from position 0,
columns without a legal-action mask, the RL loss of one sequence of one
seat with TD(lambda) on the value, the gradient summed a sequence at a
time (a batch here is ONE), Adam's moments made again from the
gradients that wait on the host, the rate read from
``train_args.base_lr``.  A dense net adds no term of its own, so
nothing is written anew: ``follow`` asks its net for
``sequence(params, tokens, lowp)``, which ``olmo_hybrid_net`` has.  It
imports nothing of the program.

The memory plan is ``trinity_training``'s at 766 M parameters and one
sequence of 4,096 positions a step: float32 parameters and one
gradient on the device (3.1 GB each), one sequence's logits (4,096 x
12,544 float32, 0.2 GB a copy), a block of 64 positions' states of the
recurrence a layer made again coming back (71 MB).
"""

from .training import draw  # noqa: F401  (the harness reaches it here)
from .trinity_training import (  # noqa: F401
    episode_columns, follow, gather, loss)
