"""Model wrapper: one uniform interface over Flax policy-value nets.

Role parity with the reference ``ModelWrapper``/``RandomModel``
(/root/reference/handyrl/model.py:33-74): train-side batched forward,
actor-side numpy->numpy single-state ``inference`` with batch-dim
handling, ``init_hidden`` plumbing for recurrent nets, and a
``RandomModel`` whose all-zero outputs yield a uniform policy over
legal actions.

TPU-native differences: parameters are an explicit pytree (not module
state), ``inference`` is a cached ``jax.jit`` of ``module.apply``
(compiled per obs-structure, re-used across weight updates), and
pickling a ``TPUModel`` ships ``(module, numpy params)`` so CPU actor
processes can rebuild and jit locally.
"""

import pickle
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


def _to_numpy(tree):
    return jax.tree.map(lambda a: np.asarray(a), tree)


def snapshot_params(params) -> bytes:
    """Serialize a params pytree (device -> host, pickled numpy)."""
    return pickle.dumps(_to_numpy(params))


def load_params(blob: bytes):
    return pickle.loads(blob)


class TPUModel:
    """A Flax module bound to a params pytree.

    ``inference`` is the actor-side hot path: numpy obs in, numpy
    outputs out, batch dim added/stripped automatically.
    """

    def __init__(self, module, params=None):
        self.module = module
        self.params = params
        self._jitted = None

    # -- initialization ---------------------------------------------
    def init_params(self, example_obs, seed: int = 0):
        obs_b = jax.tree.map(lambda a: jnp.asarray(a)[None], example_obs)
        hidden_b = self.init_hidden([1])
        variables = self.module.init(jax.random.PRNGKey(seed), obs_b, hidden_b)
        self.params = variables["params"]
        return self.params

    def init_hidden(self, batch_shape=None):
        """Zero hidden state with leading ``batch_shape`` dims, or None
        for feed-forward nets.  ``None``/``[]`` means "no batch dim"
        (single-state actor inference)."""
        if hasattr(self.module, "init_hidden"):
            return self.module.init_hidden(tuple(batch_shape or ()))
        return None

    @property
    def is_recurrent(self) -> bool:
        return hasattr(self.module, "init_hidden")

    @property
    def is_sequence(self) -> bool:
        """The training window IS this net's sequence (one causal pass
        over time, no state carried from step to step): a module that
        declares ``sequence_length``, the positions its actor-side
        cache holds."""
        return bool(getattr(self.module, "sequence_length", 0))

    # -- forward ----------------------------------------------------
    def apply(self, params, obs, hidden=None):
        return self.module.apply({"params": params}, obs, hidden)

    def inference(self, obs, hidden=None) -> Dict[str, Any]:
        """Single-state forward: numpy in, numpy out (no batch dim)."""
        if self._jitted is None:
            self._jitted = jax.jit(self.apply)
        obs_b = jax.tree.map(lambda a: np.asarray(a)[None], obs)
        hidden_b = (
            jax.tree.map(lambda a: np.asarray(a)[None], hidden)
            if hidden is not None
            else None
        )
        out = self._jitted(self.params, obs_b, hidden_b)
        return jax.tree.map(lambda a: np.asarray(a)[0], out)

    def inference_batch(self, obs, hidden=None) -> Dict[str, Any]:
        """Batched actor forward: numpy ``(N, ...)`` leaves in and out.

        The RolloutPool's hot path — one dispatch covers every seat of
        every lockstep episode.  Shares the jit cache with
        ``inference`` (a second trace for the batched shape)."""
        if self._jitted is None:
            self._jitted = jax.jit(self.apply)
        out = self._jitted(self.params, obs, hidden)
        return jax.tree.map(np.asarray, out)

    # -- serialization (learner -> actor shipping) -------------------
    def __getstate__(self):
        return {"module": self.module, "params": _to_numpy(self.params)}

    def __setstate__(self, state):
        self.module = state["module"]
        self.params = state["params"]
        self._jitted = None


class RandomModel:
    """Uniform-policy stand-in: zero logits over every head.

    Built from a real model's output structure on a sample observation,
    mirroring /root/reference/handyrl/model.py:65-74.
    """

    def __init__(self, model: TPUModel, example_obs):
        outputs = model.inference(example_obs, model.init_hidden())
        self._outputs = {
            k: np.zeros_like(v)
            for k, v in outputs.items()
            if k != "hidden"
        }

    def init_hidden(self, batch_shape=None):
        return None

    def inference(self, obs=None, hidden=None):
        return dict(self._outputs)

    def inference_batch(self, obs, hidden=None):
        """Zero logits for every row of the batch (uniform policy)."""
        n = jax.tree.leaves(obs)[0].shape[0]
        return {
            k: np.broadcast_to(v, (n,) + v.shape)
            for k, v in self._outputs.items()
        }
