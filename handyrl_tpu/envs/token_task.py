"""A seeded synthetic token task: one seat writes a sequence.

An episode is a prompt drawn from the episode's seed followed by the
agent's tokens, its length fixed by the same seed.  A step's observation
is the token at that position and its action the next token, any of the
vocabulary (``legal_actions`` is None: all legal, none listed).  While
the prompt is read the seat observes and does not act (turn mask 0: no
policy loss on context); from the prompt's last token on it acts, and
its token is the next position's observation.  The outcome is
verifiable: the answer should repeat the prompt, cyclically, and scores
``2 * (share of answer tokens that do) - 1`` in [-1, 1].  There is no
network here and no data set: a stand-in, with the shape of the real
thing, for post-training a language-model policy on tasks whose reward
can be checked.

``env_args``: ``net`` names the policy's size (``models/sequence_net.py::
PRESETS``) and with it the episode lengths below (``LENGTHS``): the
sparse-expert net ``trinity_mini_ep8`` plays episodes of up to 4,096
positions (about 2,200 on average), the latent-attention net
``joyai_flash_ep16`` of up to 8,192 (about 4,400), the delta-rule
hybrid ``olmo_hybrid_tp2`` the sparse-expert net's lengths; ``tiny``,
``tiny_latent`` and ``tiny_hybrid`` are the three at test size, up to 32.
"""

import math
import random

import numpy as np

from ..environment import BaseEnvironment
from ..models.sequence_net import PRESETS, sequence_net
from ..staging import fit_runs_to_window

# prompt length uniform in [lo, hi]; answer length lognormal (median,
# sigma), at least ``least``; steps = prompt - 1 + answer, capped at
# the net's ``sequence_length``
LENGTHS = {
    "trinity_mini_ep8": {"prompt": (128, 1024), "median": 1536,
                         "sigma": 0.7, "least": 64},
    "joyai_flash_ep16": {"prompt": (256, 2048), "median": 3072,
                         "sigma": 0.7, "least": 128},
    "olmo_hybrid_tp2": {"prompt": (128, 1024), "median": 1536,
                        "sigma": 0.7, "least": 64},
    "tiny": {"prompt": (3, 8), "median": 12, "sigma": 0.7, "least": 4},
    "tiny_latent": {"prompt": (3, 8), "median": 12, "sigma": 0.7,
                    "least": 4},
    "tiny_hybrid": {"prompt": (3, 8), "median": 12, "sigma": 0.7,
                    "least": 4},
}


class Environment(BaseEnvironment):
    def __init__(self, args=None):
        super().__init__(args)
        self.preset = (args or {}).get("net", "trinity_mini_ep8")
        self.sizes = PRESETS[self.preset]
        self.lengths = LENGTHS[self.preset]
        # an episode of this task IS the learner's training window: the
        # ring's append programs are bucketed by it (a tool that primes
        # a ring's groups and builds no ring reads the bucket there)
        fit_runs_to_window(self.sizes.sequence_length)
        self.reset()

    def reset(self, args=None):
        # the episode's seed comes from ``random``, which every actor
        # process seeds; prompt and length come from it alone
        rng = np.random.default_rng(random.getrandbits(63))
        spec = self.lengths
        prompt = int(rng.integers(spec["prompt"][0], spec["prompt"][1] + 1))
        answer = max(spec["least"], int(round(
            rng.lognormal(math.log(spec["median"]), spec["sigma"]))))
        self.prompt = rng.integers(
            0, self.sizes.vocab, prompt).astype(np.int32)
        self.steps = min(self.sizes.sequence_length, prompt - 1 + answer)
        self.tokens = list(self.prompt)
        self.t = 0

    # -- transitions -------------------------------------------------
    def step(self, actions):
        action = actions.get(0)
        if action is not None:
            self.tokens.append(int(action))
        self.t += 1

    def turns(self):
        # reading the prompt is no turn: the seat acts from the
        # prompt's last token on
        return [0] if self.t >= len(self.prompt) - 1 else []

    def observers(self):
        return [0]

    def terminal(self):
        return self.t >= self.steps

    def outcome(self):
        answer = np.asarray(self.tokens[len(self.prompt):], np.int32)
        if not len(answer):
            return {0: 0.0}
        wanted = self.prompt[np.arange(len(answer)) % len(self.prompt)]
        return {0: 2.0 * float((answer == wanted).mean()) - 1.0}

    def legal_actions(self, player=None):
        return None     # every token of the vocabulary, not listed

    def players(self):
        return [0]

    # -- neural-net interface ----------------------------------------
    def observation(self, player=None):
        return np.asarray(self.tokens[self.t], np.int32)

    def net(self):
        return sequence_net(self.preset)

    def __str__(self):
        return (f"prompt {len(self.prompt)} tokens, step {self.t} of "
                f"{self.steps}")
