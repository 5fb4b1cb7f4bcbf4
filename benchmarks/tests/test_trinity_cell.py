"""``trinity.fed``'s own files in rehearsal, at the net's tiny preset.

``test_cells.py`` rehearses every cell with ``rehearse.TINY``, which can
shrink train args, corpus and traffic and NOT a net: at 706 M parameters
and 2 x 4,096 positions a step its two cases of ``trinity.fed`` cannot
finish on a CPU (a configuration stating its own rehearsal preset is a
``benchmark`` PR's repair: ``PERF.md`` section 7).  Here the cell's files
are driven through ``run.main(rehearsal=)`` all the same -- configuration,
traffic mix, both halves of the plain reference, the cost module, the
readers -- with the manifest's entry pointing at a copy of the
configuration whose sizes are the tiny preset's (``TINY_SIZES``), in
memory and in ``tmp_path``; no file of the benchmark changes.

    python benchmarks/tests/test_trinity_cell.py <tiny config> [every_pick]

is the rehearsal's own process (a run ends in ``os._exit``).
"""

import json
import os
import subprocess
import sys

import pytest
import yaml

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL, CONFIG = "trinity.fed", "trinity_mini_ep8"
# models/sequence_net.py::PRESETS["tiny"], as the configuration's file
# and the plain reference state a size
TINY_LAYERS = ["sliding_attention", "full_attention", "sliding_attention"]
TINY_SIZES = {
    "env_args": {"env": "TokenTask", "net": "tiny"},
    "horizon_steps": 32,
    "roofline": {"layer_types": TINY_LAYERS, "sliding_window": 8,
                 "experts_per_token": 2, "experts": 8},
    "trunk_layers": ["layer_0", "layer_1", "layer_2"],
}
TINY_GEOMETRY = {"layer_types": tuple(TINY_LAYERS), "num_dense_layers": 1,
                 "sliding_window": 8, "num_experts_per_tok": 2,
                 "query_block": 16}
REHEARSAL = {
    "traffic": {"warm_steps": 3, "warm_offers": 5, "rate_eps": 4},
    "train_args": {"forward_steps": 32, "compress_steps": 4,
                   "batch_size": 4, "minimum_episodes": 16,
                   "update_episodes": 20, "updates_per_epoch": 3,
                   "device_replay_mb": 64, "compute_dtype": "float32"},
    "corpus": {"episodes": 24, "name": "trinity_tiny"},
}


def _tiny_manifest(config_path):
    """The manifest with ``trinity_mini_ep8``'s entry pointing at the
    tiny copy of its file."""
    from benchmarks.harness import cells

    manifest = cells.load_manifest()
    for entry in manifest["configs"]:
        if entry["name"] == CONFIG:
            entry["file"] = str(config_path)
    return manifest


def _tiny_reference():
    from benchmarks.reference import trinity_net

    trinity_net.GEOMETRY.update(TINY_GEOMETRY)


def every_pick_is_computed():
    """A program that takes no notice of WHICH experts it holds: every
    selected expert's part is computed, by the held expert of the same
    number modulo the held count (the uncut layer's work on the share's
    weights)."""
    from handyrl_tpu.models import sequence_net

    held_only = sequence_net.held_experts

    def every(m, selected, *rest):
        sizes = rest[2]
        return held_only(
            m, sizes.first_expert + selected % sizes.experts_held, *rest)

    sequence_net.held_experts = every


def rehearse(config_path, *flags):
    from benchmarks import run
    from benchmarks.harness import cells

    manifest = _tiny_manifest(config_path)
    cells.load_manifest = lambda root=cells.ROOT: manifest
    _tiny_reference()
    if "every_pick" in flags:
        every_pick_is_computed()
    return run.main(["--workload", CELL, "--seed", str(2**31 + 33),
                     "--seconds", "4", "--trace", "0"], rehearsal=REHEARSAL)


# -- the tests ---------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_config(tmp_path_factory):
    from benchmarks.harness.cells import Cell, load_manifest

    config = dict(Cell(load_manifest(), CELL).config, **TINY_SIZES)
    path = tmp_path_factory.mktemp("trinity") / "trinity_tiny.yaml"
    path.write_text(yaml.safe_dump(config))
    return path


@pytest.fixture()
def tiny_cell(tiny_config, monkeypatch):
    from benchmarks.harness.cells import Cell
    from benchmarks.reference import trinity_net

    monkeypatch.setattr(trinity_net, "GEOMETRY", dict(trinity_net.GEOMETRY))
    _tiny_reference()
    cell = Cell(_tiny_manifest(tiny_config), CELL)
    cell.config["train_args"].update(REHEARSAL["train_args"])
    cell.config["corpus"].update(REHEARSAL["corpus"])
    return cell


def _rehearse(tiny_config, *flags):
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), str(tiny_config), *flags],
        capture_output=True, text=True, timeout=1500, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-1]), lines


@pytest.fixture(scope="module")
def stated_run(tiny_config):
    """One rehearsal of the cell's files as they are: the rate stated."""
    return _rehearse(tiny_config)


def test_the_cells_files_end_correct_in_rehearsal(stated_run):
    result, lines = stated_run
    assert result["correct"] is True, [l for l in lines if "check " in l]
    assert result["failed"] == 0 < result["attempted"]
    assert {"setup_s", "learner_frames_per_s"} <= set(result["metrics"])
    assert all(v["value"] > 0 for v in result["metrics"].values())
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           CONFIG + ".yaml")) as f:
        limits = yaml.safe_load(f)["check_limits"]
    assert {k: v["limit"] for k, v in result["check"].items()} == limits
    phases = [l.split()[1] for l in lines if l.startswith("setup_phase ")]
    assert phases == ["import", "backend", "corpus", "build", "prime",
                      "compile", "warm"]
    # float32 on one backend: the program IS the reference to rounding
    assert max(v["value"] for v in result["check"].values()) < 1e-3


@pytest.fixture(scope="module")
def moved_at(tiny_config, tmp_path_factory):
    """``moved_at(base_lr)``: a rehearsal of a copy of the tiny
    configuration stating that rate (None: stating none), which has to
    end correct; the median leaf's change on both sides.  One rehearsal
    a rate."""
    import functools

    @functools.cache
    def run(base_lr):
        config = yaml.safe_load(tiny_config.read_text())
        config["train_args"].pop("base_lr")
        if base_lr is not None:
            config["train_args"]["base_lr"] = base_lr
        path = tmp_path_factory.mktemp("rate") / "trinity_tiny.yaml"
        path.write_text(yaml.safe_dump(config))
        result, lines = _rehearse(path)
        assert result["correct"] is True, [l for l in lines if "check " in l]
        assert result["check"]["update_gap"]["value"] < 1e-2
        moved = result["median_leaf_change"]
        return moved["program"], moved["reference"]

    return run


@pytest.mark.parametrize("factor", [None, 0.1])
def test_a_stated_rate_reaches_the_program_and_the_reference(
        moved_at, factor):
    """A configuration that states no rate (None) trains on BOTH sides
    at HandyRL's 3e-8 a frame, as every configuration did before the
    key existed; one that states a tenth of it moves both sides a tenth
    as far; in each the two sides stay as close as the cell's limit
    asks.  Were the key read on one side only, ``update_gap`` would
    read 9 (or 0.9)."""
    from benchmarks.reference.training import BASE_LR

    whole = moved_at(BASE_LR)
    other = moved_at(None if factor is None else BASE_LR * factor)
    for moved, unit in zip(other, whole):
        assert moved / unit == pytest.approx(factor or 1.0, rel=0.1)
    if factor is None:
        assert other == whole


def test_a_step_that_computes_every_pick_comes_out_not_correct(tiny_config):
    """The share is part of the result: a step in which every selected
    expert's part is computed, and not only the held ones', is another
    net to the reference, which holds the same experts."""
    result, lines = _rehearse(tiny_config, "every_pick")
    assert result["correct"] is False, [l for l in lines if "check " in l]
    assert result["check"]["ring_mismatch"]["value"] == 0


def test_the_cost_module_and_both_reference_halves_are_found_by_name(
        tiny_cell):
    import jax

    from benchmarks.harness import check, roofline, weights
    from handyrl_tpu.environment import make_env
    from handyrl_tpu.models.wrapper import TPUModel

    train = tiny_cell.program_args()["train_args"]
    training, net, one_seat = check.reference_setup(tiny_cell.config, train)
    assert training.__name__ == "benchmarks.reference.trinity_training"
    assert net.__name__ == "benchmarks.reference.trinity_net"
    assert one_seat and net.RECURRENT is False
    cost_of = roofline.cost_function(tiny_cell.config)
    assert cost_of.__module__ == "benchmarks.cost.trinity"
    env = make_env(tiny_cell.config["env_args"])
    model = TPUModel(env.net())
    shapes = weights.param_shapes(model.module, env.observation(0),
                                  model.init_hidden([1]))
    cost = cost_of(shapes, train, tiny_cell.config["roofline"], 32)
    assert set(cost["parts"]) == {"attention", "mlp", "moe", "head"}
    assert cost["flops"] == pytest.approx(
        sum(p["flops"] for p in cost["parts"].values()))
    assert cost["bytes"] > sum(p["bytes"] for p in cost["parts"].values())
    assert all(p["flops"] > 0 < p["bytes"] for p in cost["parts"].values())
    # every leaf is some part's: nothing is counted under no name
    n_params = sum(leaf.size for leaf in jax.tree.leaves(shapes))
    assert cost["bytes"] >= 32.0 * n_params


def test_the_fp8_control_fails(tiny_cell):
    """The reference computed one precision below the stated one, put in
    the program's place, passes some limit of the cell's by."""
    from benchmarks import control
    from benchmarks.harness import check

    numbers = control.control_numbers(tiny_cell, 2**31 + 5, "fp8", capacity=64)
    correct, lines = check.verdict(numbers, tiny_cell.config["check_limits"])
    assert not correct, lines


def test_half_of_the_batch_left_out_fails_the_loss_number(tiny_cell):
    """What ``loss_gap`` is held against since the stated rate took the
    fp8 control's reading of it under three times the sound runs'."""
    from benchmarks import control
    from benchmarks.harness import check

    numbers = control.half_batch_numbers(tiny_cell, 2**31 + 5, capacity=64)
    limits = tiny_cell.config["check_limits"]
    assert numbers["loss_gap"] > 10 * limits["loss_gap"]
    assert not check.verdict(numbers, limits)[0]


def test_the_reference_follows_the_stated_rate(
        tiny_cell, follows_the_stated_rate):
    from benchmarks import control

    follows_the_stated_rate(*control.inputs(tiny_cell, 2**31 + 5))


def test_every_reader_the_cell_lists_has_its_file(tiny_cell):
    from benchmarks import run

    names = {m["name"] for m in tiny_cell.per_layer}
    assert {"step_attention_ms", "step_moe_ms", "step_head_ms",
            "attention_roofline", "moe_roofline", "moe_load_imbalance",
            "seq_fill_share"} <= names
    for name in names:
        assert callable(run._reader(name))


if __name__ == "__main__":
    os._exit(rehearse(*sys.argv[1:]))
