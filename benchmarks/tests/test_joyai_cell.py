"""``joyai.fed``'s own files in rehearsal, at the net's tiny preset.

As ``test_trinity_cell.py`` does for ``trinity.fed``, and for its
reason (``rehearse.TINY`` cannot shrink a net, and 680 M parameters
over 8,192 positions a step do not finish on a CPU): the cell's files
-- configuration, traffic mix, both halves of the plain reference, the
cost module, the readers -- are driven through ``run.main(rehearsal=)``
with the manifest's entry pointing at a copy of the configuration whose
sizes are the tiny preset's (``TINY_SIZES``), in memory and in
``tmp_path``; no file of the benchmark changes.

    python benchmarks/tests/test_joyai_cell.py <tiny config> [no_term]

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

CELL, CONFIG = "joyai.fed", "joyai_flash_ep16"
# models/sequence_net.py::PRESETS["tiny_latent"], as the configuration's
# file and the plain reference state a size
TINY_SIZES = {
    "env_args": {"env": "TokenTask", "net": "tiny_latent"},
    "horizon_steps": 32,
    "roofline": {"num_hidden_layers": 3, "experts_per_token": 2,
                 "experts": 8},
    "trunk_layers": ["layer_0", "layer_1", "layer_2", "mtp"],
}
TINY_GEOMETRY = {"num_hidden_layers": 3, "first_k_dense_replace": 1,
                 "num_attention_heads": 4, "num_experts_per_tok": 2,
                 "query_block": 16}
REHEARSAL = {
    "traffic": {"warm_steps": 3, "warm_offers": 5, "rate_eps": 4},
    "train_args": {"forward_steps": 32, "compress_steps": 4,
                   "batch_size": 1, "minimum_episodes": 16,
                   "update_episodes": 20, "updates_per_epoch": 3,
                   "device_replay_mb": 64, "compute_dtype": "float32"},
    "corpus": {"episodes": 24, "name": "joyai_tiny"},
}


def _tiny_manifest(config_path):
    """The manifest with ``joyai_flash_ep16``'s entry pointing at the
    tiny copy of its file."""
    from benchmarks.harness import cells

    manifest = cells.load_manifest()
    for entry in manifest["configs"]:
        if entry["name"] == CONFIG:
            entry["file"] = str(config_path)
    return manifest


def _tiny_reference():
    from benchmarks.reference import joyai_net

    joyai_net.GEOMETRY.update(TINY_GEOMETRY)


def the_modules_term_is_left_out():
    """A program whose loss lacks the next-next-token term: the RL loss
    alone, as the other sequence net trains."""
    from handyrl_tpu.ops import losses

    losses.NEXTN_WEIGHT = 0.0


def rehearse(config_path, *flags):
    from benchmarks import run
    from benchmarks.harness import cells

    manifest = _tiny_manifest(config_path)
    cells.load_manifest = lambda root=cells.ROOT: manifest
    _tiny_reference()
    if "no_term" in flags:
        the_modules_term_is_left_out()
    return run.main(["--workload", CELL, "--seed", str(2**31 + 38),
                     "--seconds", "4", "--trace", "0"], rehearsal=REHEARSAL)


# -- the tests ---------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_config(tmp_path_factory):
    from benchmarks.harness.cells import Cell, load_manifest

    config = dict(Cell(load_manifest(), CELL).config, **TINY_SIZES)
    path = tmp_path_factory.mktemp("joyai") / "joyai_tiny.yaml"
    path.write_text(yaml.safe_dump(config))
    return path


@pytest.fixture()
def tiny_cell(tiny_config, monkeypatch):
    from benchmarks.harness.cells import Cell
    from benchmarks.reference import joyai_net

    monkeypatch.setattr(joyai_net, "GEOMETRY", dict(joyai_net.GEOMETRY))
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


def test_the_cells_files_end_correct_in_rehearsal(tiny_config):
    """A batch of ONE window of the whole horizon, as the cell runs."""
    result, lines = _rehearse(tiny_config)
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


def test_a_step_without_the_modules_term_comes_out_not_correct(tiny_config):
    """The term is part of the result: the module's own leaves take
    their whole gradient from it."""
    result, lines = _rehearse(tiny_config, "no_term")
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
    assert training.__name__ == "benchmarks.reference.joyai_training"
    assert net.__name__ == "benchmarks.reference.joyai_net"
    assert one_seat and net.RECURRENT is False
    cost_of = roofline.cost_function(tiny_cell.config)
    assert cost_of.__module__ == "benchmarks.cost.joyai"
    env = make_env(tiny_cell.config["env_args"])
    model = TPUModel(env.net())
    shapes = weights.param_shapes(model.module, env.observation(0),
                                  model.init_hidden([1]))
    cost = cost_of(shapes, train, tiny_cell.config["roofline"], 32)
    assert set(cost["parts"]) == {"attention", "mlp", "moe", "head", "mtp"}
    assert cost["flops"] == pytest.approx(
        sum(p["flops"] for p in cost["parts"].values()))
    assert cost["bytes"] > sum(p["bytes"] for p in cost["parts"].values())
    assert all(p["flops"] > 0 < p["bytes"] for p in cost["parts"].values())
    # every leaf is some part's: nothing is counted under no name
    n_params = sum(leaf.size for leaf in jax.tree.leaves(shapes))
    assert cost["bytes"] >= 32.0 * n_params


def test_the_published_count_is_the_issues(tiny_cell):
    """At the published widths, from shapes alone: 680.4 M parameters,
    26.35 M an attention, and the step's operations by part."""
    import jax
    import numpy as np

    from benchmarks.harness import roofline
    from benchmarks.harness.cells import Cell, load_manifest
    from handyrl_tpu.environment import make_env
    from handyrl_tpu.models.wrapper import TPUModel

    config = Cell(load_manifest(), CELL).config
    model = TPUModel(make_env(config["env_args"]).net())
    shapes = jax.eval_shape(lambda: model.module.init(
        jax.random.PRNGKey(0), np.zeros((1,), np.int32),
        model.init_hidden([1]))["params"])
    size = lambda tree: sum(  # noqa: E731
        leaf.size for leaf in jax.tree.leaves(tree))
    assert size(shapes) == 680_441_856
    assert size(shapes["layer_1"]["attn"]) == 26_347_520
    assert size(shapes["mtp"]) - size(shapes["mtp"]["layer"]) == 8_394_752
    cost = roofline.cost_function(config)(
        shapes, config["train_args"], config["roofline"], 32)
    positions, keys = 8192, (8192 + 1) / 2
    per_layer = 2 * 26_345_472 + 2 * 32 * (192 + 128) * keys
    assert cost["parts"]["attention"]["flops"] == pytest.approx(
        3 * positions * 6 * per_layer)
    assert cost["parts"]["mtp"]["flops"] == pytest.approx(
        3 * positions * 2 * (4096 * 2048 + 2048 * 16160))
    # every published number stands in the file under its own key
    assert (config["q_lora_rank"], config["kv_lora_rank"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["moe_intermediate_size"],
            config["intermediate_size"], config["hidden_size"]) == (
                1536, 512, 128, 64, 128, 768, 7168, 2048)
    assert set(config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size", "epochs"}


def test_the_fp8_control_fails(tiny_cell):
    """The reference computed one precision below the stated one, put in
    the program's place, passes some limit of the cell's by."""
    from benchmarks import control
    from benchmarks.harness import check

    numbers = control.control_numbers(tiny_cell, 2**31 + 5, "fp8", capacity=64)
    correct, lines = check.verdict(numbers, tiny_cell.config["check_limits"])
    assert not correct, lines


def test_the_reference_follows_the_stated_rate(
        tiny_cell, follows_the_stated_rate):
    from benchmarks import control

    follows_the_stated_rate(*control.inputs(tiny_cell, 2**31 + 5))


def test_every_reader_the_cell_lists_has_its_file(tiny_cell):
    from benchmarks import run

    names = {m["name"] for m in tiny_cell.per_layer}
    assert {"step_latent_attention_ms", "latent_attention_roofline",
            "step_mtp_ms", "mtp_target_share", "step_moe_ms", "step_head_ms",
            "moe_roofline", "moe_load_imbalance", "seq_fill_share",
            "fused_step_roofline"} <= names
    assert not {"step_attention_ms", "attention_roofline"} & names
    for name in names:
        assert callable(run._reader(name))


if __name__ == "__main__":
    os._exit(rehearse(*sys.argv[1:]))
