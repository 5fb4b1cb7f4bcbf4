"""The output check: the plain reference against itself, against the
configuration's stated precision and against the control."""

import os
import subprocess
import sys
import tracemalloc
import types

import jax
import numpy as np
import pytest
import yaml

from benchmarks.harness import check, corpus, weights
from benchmarks.harness.cells import BENCH_DIR, ROOT

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def geese_inputs():
    """A ``geese32`` batch at test size: what a follow is made from."""
    with open(f"{BENCH_DIR}/configs/geese32.yaml") as f:
        config = yaml.safe_load(f)
    train = dict(config["train_args"], batch_size=32, seed=5)
    train["lockstep_episodes"] = 4
    config = dict(config, train_args=train)
    episodes = corpus._play(config, 24, 11)

    from handyrl_tpu.environment import make_env
    from handyrl_tpu.models.wrapper import TPUModel

    env = make_env(config["env_args"])
    env.reset()
    model = TPUModel(env.net())
    shapes = weights.param_shapes(
        model.module, env.observation(env.players()[0]), None)
    initial = jax.device_get(weights.make_params(shapes, 5))
    return config, train, episodes, initial


@pytest.fixture(scope="module")
def geese(geese_inputs):
    config, train, episodes, initial = geese_inputs
    follow = {lowp: check.reference_follow(
        config, train, episodes, 64, initial, lowp=lowp)
        for lowp in (None, "bf16", "fp8")}
    return initial, follow, config["check_limits"]


def test_the_control_comes_out_not_correct(geese):
    """The reference computed in fp8, the step below the stated
    bfloat16, put in the program's place: ``correct`` must be false,
    and it is the gradient's number that fails."""
    initial, follow, limits = geese
    numbers = check.training_numbers(
        check.as_captured(follow["fp8"]), follow[None], initial)
    correct, lines = check.verdict(numbers, limits)
    assert not correct, lines
    assert numbers["grad_gap"] > limits["grad_gap"]
    assert numbers["grad_diff"] > limits["grad_diff"]


def test_the_default_training_side_follows_a_stated_rate(
        geese_inputs, follows_the_stated_rate):
    """``train_args.base_lr`` absent is HandyRL's 3e-8 a frame, as
    before the key existed; stated, the change scales with it."""
    follows_the_stated_rate(*geese_inputs)


def test_the_stated_precision_and_the_reference_itself_pass(geese):
    initial, follow, limits = geese
    for lowp in (None, "bf16"):
        numbers = check.training_numbers(
            check.as_captured(follow[lowp]), follow[None], initial)
        assert check.verdict(numbers, limits)[0], (lowp, numbers)
    same = check.training_numbers(
        check.as_captured(follow[None]), follow[None], initial)
    assert max(same.values()) < 1e-6
    assert set(same) <= set(limits)


def test_a_step_that_changes_nothing_fails_the_update_number(geese):
    initial, follow, limits = geese
    stuck = dict(check.as_captured(follow[None]),
                 params_after_third=initial)
    numbers = check.training_numbers(stuck, follow[None], initial)
    assert numbers["update_gap"] == pytest.approx(1.0)
    assert not check.verdict(numbers, limits)[0]


def test_a_part_of_the_batch_left_out_fails_the_loss_number(geese):
    initial, follow, limits = geese
    captured = check.as_captured(follow[None])
    captured["losses"] = [0.75 * x for x in captured["losses"]]
    numbers = check.training_numbers(captured, follow[None], initial)
    losses, _, _, scales = follow[None]
    assert numbers["loss_gap"] == pytest.approx(
        max(0.25 * abs(x) / s for x, s in zip(losses, scales)))
    assert all(s >= abs(x) for x, s in zip(losses, scales))
    assert not check.verdict(numbers, limits)[0]


def _rehearse(*argv, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse.py"), *argv],
        capture_output=True, text=True, timeout=1500, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})))


def _result(proc):
    import json

    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_a_broken_timed_path_comes_out_not_correct():
    """The rest of a run, driven without the look for a chip, with the
    fused step returning its state unchanged underneath the harness."""
    result = _result(_rehearse("geese.fed", "noop_step"))
    assert result["correct"] is False
    assert result["device"]["platform"] == "cpu"


# -- the gaps, leaf by leaf -------------------------------------------
def _training_numbers_before(captured, reference, initial):
    """``check.training_numbers`` and what it called, as they were
    while they built whole trees in float64: the reference the leaf by
    leaf walk is held to, to the last bit."""
    def leaf_norms(tree):
        return np.asarray([float(np.sqrt(np.sum(np.square(
            np.asarray(x, np.float64))))) for x in jax.tree.leaves(tree)])

    def tree_sub(a, b):
        return jax.tree.map(lambda x, y: np.asarray(x, np.float64)
                            - np.asarray(y, np.float64), a, b)

    def worst_leaf_gap(program, reference):
        p, r = leaf_norms(program), leaf_norms(reference)
        return float(np.max(np.abs(p - r) / np.maximum(r, np.median(r))))

    def worst_leaf_difference(program, reference):
        r = leaf_norms(reference)
        return float(np.max(leaf_norms(tree_sub(program, reference))
                            / np.maximum(r, np.median(r))))

    ref_losses, ref_first, ref_final, ref_scales = reference
    prog_first = jax.tree.map(lambda m: np.asarray(m) / (1 - check.ADAM_B1),
                              captured["mu_after_first"])
    return {
        "loss_gap": max(abs(p - r) / scale for p, r, scale in
                        zip(captured["losses"], ref_losses, ref_scales)),
        "grad_gap": worst_leaf_gap(prog_first, ref_first),
        "grad_diff": worst_leaf_difference(prog_first, ref_first),
        "update_gap": worst_leaf_gap(
            tree_sub(captured["params_after_third"], initial),
            tree_sub(ref_final, initial)),
    }


def _f32(rng, shape, scale):
    return (scale * rng.standard_normal(shape)).astype(np.float32)


def _three_leaf_case(seed, sizes=((300, 70), (70,), (9, 9, 16, 32))):
    rng = np.random.default_rng(seed)

    def tree(scale):
        return {"a": {"kernel": _f32(rng, sizes[0], scale),
                      "bias": _f32(rng, sizes[1], scale)},
                "b": {"kernel": _f32(rng, sizes[2], scale)}}

    initial, grad = tree(1.0), tree(1e-2)
    noisy = lambda t, eps: jax.tree.map(          # noqa: E731
        lambda x: x + _f32(rng, x.shape, eps), t)
    final = noisy(initial, 1e-4)
    captured = {"losses": [3.1, 2.9, 2.8],
                "mu_after_first": jax.tree.map(
                    lambda g: g * np.float32(0.1), noisy(grad, 1e-4)),
                "params_after_third": noisy(final, 1e-6)}
    reference = ([3.1001, 2.9002, 2.7999], grad, final, [5.0, 5.0, 5.0])
    return captured, reference, initial


@pytest.mark.parametrize("seed", (1, 2, 3))
def test_the_gaps_leaf_by_leaf_equal_the_whole_tree_ones_to_the_last_bit(
        seed):
    case = _three_leaf_case(seed)
    now = check.training_numbers(*case)
    before = _training_numbers_before(*case)
    assert list(now) == list(before)
    for name in now:
        assert now[name] == before[name] and now[name] > 0, name


def test_the_gaps_hold_one_leaf_in_float64_at_a_time():
    """Not a tracer on ``numpy.asarray(..., float64)``: the walk makes
    its one float64 leaf through ``numpy.subtract`` / ``square``, which
    such a tracer would never see.  ``tracemalloc`` sees every buffer
    numpy allocates: over the four gaps the host never holds more than
    the largest leaf in float64 (and a float32 leaf of the scaled
    gradient) beyond what it was handed; the whole-tree walk held four
    float64 trees at once."""
    big = 1 << 20
    case = _three_leaf_case(4, sizes=((big,), (big,), (big,)))
    largest = big * 8

    def peak(numbers):
        tracemalloc.start()
        try:
            base = tracemalloc.get_traced_memory()[0]
            numbers(*case)
            return tracemalloc.get_traced_memory()[1] - base
        finally:
            tracemalloc.stop()

    assert peak(check.training_numbers) < 1.6 * largest
    assert peak(_training_numbers_before) > 6 * largest


# -- the reference's training side, brought by name -------------------
TRAINING_SIDE = '''
"""A training side as a later configuration brings it: columns with no
dense mask (every action is legal), a gather without that key, and a
follow that accumulates its gradient over two blocks of rows."""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import training
from benchmarks.reference.training import draw  # shared as it is

CALLS = []


def episode_columns(episode):
    CALLS.append("episode_columns")
    columns = training.episode_columns(episode)
    del columns["illegal"]
    return columns


def gather(columns, slots, starts, seat, forward_steps, burn_in, one_seat):
    CALLS.append("gather")
    items = columns.items() if isinstance(columns, dict) \\
        else enumerate(columns)
    dense = {k: dict(c, illegal=np.zeros(c["act"].shape, bool))
             for k, c in items}
    batch = training.gather(dense, slots, starts, seat, forward_steps,
                            burn_in, one_seat)
    del batch["action_mask"]
    return batch


def _loss(net, params, batch, cfg, lowp):
    legal = jnp.zeros(batch["action"].shape[:3] + (1,), jnp.float32)
    return training.loss(net, params, dict(batch, action_mask=legal),
                         cfg, lowp)


def follow(net, params, batches, cfg, lowp=None, blocks=2):
    CALLS.append("follow")
    lr = training.BASE_LR * cfg["batch_size"] * cfg["forward_steps"]
    grad = jax.jit(jax.value_and_grad(
        lambda p, b: _loss(net, p, b, cfg, lowp), has_aux=True))
    step = jax.jit(training.adam_step)
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    count, losses, scales, first = 0, [], [], None
    for batch in batches:
        assert "action_mask" not in batch
        total, parts, grads = 0.0, {}, None
        rows = np.arange(len(batch["action"]))
        for block in np.array_split(rows, blocks):
            (t, p), g = grad(params, jax.tree.map(lambda a: a[block], batch))
            total += float(t)
            parts = {k: parts.get(k, 0.0) + float(v) for k, v in p.items()}
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        params, mu, nu, count, seen = step(params, grads, mu, nu, count, lr)
        losses.append(total)
        scales.append(abs(parts["p"]) + parts.get("v", 0.0)
                      + parts.get("r", 0.0)
                      + cfg["entropy_regularization"] * parts["ent"])
        if first is None:
            first = jax.device_get(seen)
    return losses, first, jax.device_get(params), scales
'''


@pytest.fixture
def blocked_side(tmp_path, geese_inputs):
    """A configuration brought as FILES ONLY (a configuration file, a
    net file, a training-side file; the manifest's entries added in
    memory): ``(config, train, module)`` with the new files' directory
    on the reference package's search path."""
    import benchmarks.reference as package
    from benchmarks.harness.cells import Cell, load_manifest

    config, train, _episodes, _initial = geese_inputs
    (tmp_path / "blocked_side.py").write_text(TRAINING_SIDE)
    (tmp_path / "blocked_net.py").write_text(
        "from benchmarks.reference.geese_net import RECURRENT, forward"
        "  # noqa: F401\n")
    (tmp_path / "blocked.yaml").write_text(yaml.safe_dump(dict(
        config, reference="blocked_net", reference_training="blocked_side")))
    manifest = load_manifest()
    manifest["configs"].append(dict(
        manifest["configs"][0], name="blocked",
        file=str(tmp_path / "blocked.yaml")))
    manifest["workloads"].append(dict(
        manifest["workloads"][0], name="blocked.fed", config="blocked"))
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if manifest["workloads"][0]["name"] in metric.get("workloads", []):
            metric["workloads"].append("blocked.fed")
    cell = Cell(manifest, "blocked.fed")
    package.__path__.append(str(tmp_path))
    try:
        module = check.reference_setup(cell.config, train)[0]
        assert module.__file__ == str(tmp_path / "blocked_side.py")
        yield cell.config, cell.program_args()["train_args"], module
    finally:
        package.__path__.remove(str(tmp_path))
        for name in ("blocked_side", "blocked_net"):
            sys.modules.pop("benchmarks.reference." + name, None)


def test_a_training_side_brought_by_name_follows_in_blocks(
        blocked_side, geese_inputs, geese):
    """``reference_follow`` through the new files alone: no dense mask
    in the columns, no ``action_mask`` in the batch, the gradient summed
    over two blocks of rows; and what it returns agrees with the
    default side's whole-batch follow to float32 rounding (every term
    of the loss is a sum over rows, and every action of Geese is legal
    to a goose that acts)."""
    config, train, module = blocked_side
    _config, _train, episodes, initial = geese_inputs
    _initial, follow, _limits = geese
    assert train["batch_size"] == 32
    blocked = check.reference_follow(config, train, episodes, 64, initial)
    assert module.CALLS == (["episode_columns"] * len(episodes)
                            + ["gather"] * 3 + ["follow"])
    losses, first, final, scales = follow[None]
    np.testing.assert_allclose(blocked[0], losses, rtol=1e-5)
    np.testing.assert_allclose(blocked[3], scales, rtol=1e-5)
    for ours, theirs in ((blocked[1], first), (blocked[2], final)):
        assert jax.tree.structure(ours) == jax.tree.structure(theirs)
        for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
            np.testing.assert_allclose(
                a, b, rtol=1e-4, atol=1e-5 * np.abs(b).max())
    numbers = check.training_numbers(
        check.as_captured(blocked), follow[None], initial)
    assert max(numbers.values()) < 1e-4, numbers
    fp8 = check.reference_follow(config, train, episodes, 64, initial,
                                 lowp="fp8")
    assert not check.verdict(check.training_numbers(
        check.as_captured(fp8), blocked, initial), _limits)[0]


def test_the_ring_rows_are_compared_through_the_training_side_by_name(
        blocked_side, geese_inputs):
    """``run._check_ring_rows`` with the program's side stood in by the
    default gather (dense mask and all): every key the new side's gather
    returns is compared, the ``action_mask`` it lacks is not."""
    from benchmarks import run
    from benchmarks.reference import training

    config, train, module = blocked_side
    _config, _train, episodes, _initial = geese_inputs
    spoil = {}

    def sample_fn(_buffers, slots, starts, seats):
        slots = np.asarray(slots).tolist()
        columns = {s: training.episode_columns(episodes[s])
                   for s in set(slots)}      # slot k holds episode k
        batch = training.gather(
            columns, slots, np.asarray(starts), np.asarray(seats),
            train["forward_steps"], train["burn_in_steps"], True)
        for key, rows in spoil.items():
            batch[key][rows] += 1
        return batch

    count = 6
    offers = [(1.0 + k, None) for k in range(count)]
    probes = types.SimpleNamespace(
        pairing=types.SimpleNamespace(
            shed=[], landed=[(due, due + 0.1) for due, _ in offers]),
        appends=[(0, [1] * count)])
    feeder = types.SimpleNamespace(
        offers=offers, order=list(range(len(episodes))))
    replay = types.SimpleNamespace(_sample_fn=sample_fn, buffers=None)

    def mismatch():
        del module.CALLS[:]
        bad = run._check_ring_rows(
            config, train, 7, replay, probes, episodes, feeder,
            {"capacity": 16}, 0.0, 100.0, sample=8)
        assert module.CALLS[-1] == "gather"
        assert set(module.CALLS[:-1]) == {"episode_columns"}
        return bad

    assert mismatch() == 0.0
    spoil["action_mask"] = slice(None)
    assert mismatch() == 0.0
    spoil["action"] = [2, 5]
    assert mismatch() == 2.0


def test_the_harness_names_no_training_side_of_its_own():
    """``check.reference_follow``, ``run._check_ring_rows`` and
    ``control.py`` reach the four functions only through what
    ``reference_setup`` returns."""
    import inspect

    from benchmarks import control, run

    for module in (check, run, control):
        source = inspect.getsource(module)
        assert "import training" not in source, module.__name__
        assert "reference.training" not in source, module.__name__
