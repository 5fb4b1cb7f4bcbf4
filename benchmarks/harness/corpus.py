"""The fed corpus: real episodes of the configuration's environment,
played ONCE per checkout by the program's own ``RolloutPool`` and kept
in ``benchmarks/.cache/``.

Who plays is the configuration's to state, under ``corpus``:

  policy: net      (the default, also when absent) the configuration's
                   net at random weights, on the host's CPU backend (the
                   chip belongs to the learner, and a batch-1 forward
                   per seat per step gains nothing from it)
  policy: random   the program's own uniform stand-in (``RandomModel``:
                   zero logits, so ``selected_prob`` is 1 / legal
                   actions), which is what the program's actors play
                   until the first model lands (``worker.py::_fetch``).
                   No weight is made and no forward runs: a net the
                   host cannot play in a checkout's time still gets the
                   environment's real episodes in the wire format

Blocks are raw pickle, the format the shm trajectory plane delivers
(pipeline mode is on by default), so ring ingest pays what it pays for
a local fleet's episodes.
"""

import os
import pickle
import random
import zlib

import numpy as np

from .cells import BENCH_DIR

CACHE_DIR = os.path.join(BENCH_DIR, ".cache")
POLICIES = ("net", "random")


class _OutputShapes:
    """What ``RandomModel`` asks of a model, its output structure on one
    observation, answered by an abstract evaluation of the net: no
    weight is made, nothing is computed."""

    def __init__(self, model):
        self.module = model.module
        self.init_hidden = model.init_hidden

    def inference(self, obs, hidden=None):
        import jax

        batched = jax.tree.map(lambda a: np.asarray(a)[None], (obs, hidden))
        out = jax.eval_shape(
            lambda *args: self.module.init_with_output(*args)[0],
            jax.random.PRNGKey(0), *batched)
        return jax.tree.map(lambda s: np.zeros(s.shape[1:], s.dtype), out)


def _play(config, count, seed):
    """``count`` episodes from the program's production actor engine
    (``RolloutPool``: ``lockstep_episodes`` games advanced together, one
    batched forward a step), every seat on the same model: the net at
    random weights, or the uniform stand-in (the module's docstring)."""
    from handyrl_tpu.environment import make_env, prepare_env
    from handyrl_tpu.generation import RolloutPool
    from handyrl_tpu.models.wrapper import RandomModel, TPUModel

    env_args = config["env_args"]
    train = config["train_args"]
    prepare_env(env_args)
    random.seed(seed)
    np.random.seed(seed % 2**32)
    envs = [make_env(env_args) for _ in range(train["lockstep_episodes"])]
    env = envs[0]
    env.reset()
    players = env.players()
    model = TPUModel(env.net())
    if policy_of(config) == "random":
        model = RandomModel(_OutputShapes(model),
                            env.observation(players[0]))
    else:
        model.init_params(env.observation(players[0]), seed=seed)
    pool = RolloutPool(envs, {
        "observation": train["observation"], "gamma": train["gamma"],
        "compress_steps": train["compress_steps"],
        "episode_compress": False})
    models = {p: model for p in players}
    job = {"role": "g", "player": list(players),
           "model_id": {p: 0 for p in players}}
    episodes = []
    assigned = 0
    while len(episodes) < count:
        finished = []
        while pool.has_free_slot() and assigned < count + pool.K:
            finished += pool.assign(dict(job), models)
            assigned += 1
        finished += pool.step()
        episodes += [ep for verb, ep in finished
                     if verb == "episode" and ep is not None]
    return episodes[:count]


def stretch(episode, steps, compress_steps):
    """A synthetic episode of exactly ``steps`` moments: the given
    episode's moments repeated.  It promises the ring its final T_max
    at the first ingest, whatever the corpus happened to play."""
    from handyrl_tpu.batch import load_block
    from handyrl_tpu.generation import pack_episode

    moments = [m for blob in episode["moment"] for m in load_block(blob)]
    tiled = [moments[i % len(moments)] for i in range(steps)]
    return pack_episode(tiled, episode["outcome"], episode["args"],
                        compress_steps, compress=False)


def policy_of(config):
    policy = config["corpus"].get("policy", "net")
    if policy not in POLICIES:
        raise ValueError(f"corpus policy {policy!r}; a configuration "
                         f"states one of {POLICIES}")
    return policy


def cache_path(config_name, config):
    """The corpus's file in the checkout's cache.  Its name carries the
    policy only when that is not ``net``, so a corpus played before the
    key existed is still found."""
    spec = config["corpus"]
    policy = policy_of(config)
    return os.path.join(
        CACHE_DIR, "corpus",
        f"{spec.get('name', config_name)}-{spec['seed']}-"
        f"{spec['episodes']}{'' if policy == 'net' else '-' + policy}"
        f".pkl.z")


def load_corpus(config_name, config):
    """The configuration's episodes, from the checkout's cache or played
    now; ``episodes[0]`` is the horizon-length one."""
    spec = config["corpus"]
    horizon = int(config["horizon_steps"])
    path = cache_path(config_name, config)
    if os.path.exists(path):
        # zlib at rest: the observation planes are sparse, so the file
        # is ~20x smaller than the episodes and loads at memory speed,
        # whatever the disk does
        with open(path, "rb") as f:
            return pickle.loads(zlib.decompress(f.read()))
    import jax

    with jax.default_device(jax.devices("cpu")[0]):
        episodes = _play(config, int(spec["episodes"]), int(spec["seed"]))
    longest = max(episodes, key=lambda e: e["steps"])
    if longest["steps"] > horizon:
        raise RuntimeError(
            f"corpus episode of {longest['steps']} steps exceeds the "
            f"configuration's horizon_steps {horizon}")
    episodes.insert(0, stretch(
        longest, horizon, config["train_args"]["compress_steps"]))
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "wb") as f:
        f.write(zlib.compress(pickle.dumps(
            episodes, protocol=pickle.HIGHEST_PROTOCOL), 1))
    os.replace(tmp, path)
    return episodes
