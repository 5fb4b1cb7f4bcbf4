"""``olmo.fed``'s own files in rehearsal, at the net's tiny preset.

As ``test_joyai_cell.py`` does for ``joyai.fed``, and for its reason
(``rehearse.TINY`` cannot shrink a net, and 766 M parameters over 4,096
positions a step do not finish on a CPU): the cell's files --
configuration, traffic mix, both halves of the plain reference, the
cost module, the readers -- are driven through ``run.main(rehearsal=)``
with the manifest's entry pointing at a copy of the configuration whose
sizes are the tiny preset's (``TINY_SIZES``), in memory and in
``tmp_path``; no file of the benchmark changes.

    python benchmarks/tests/test_olmo_cell.py <tiny config> [no_decay]

is the rehearsal's own process (a run ends in ``os._exit``).
"""

import fcntl
import json
import os
import subprocess
import sys
import tempfile

import pytest
import yaml

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL, CONFIG = "olmo.fed", "olmo_hybrid_tp2"
# models/sequence_net.py::PRESETS["tiny_hybrid"], as the configuration's
# file and the plain reference state a size
TINY_LAYERS = ["linear_attention", "full_attention", "linear_attention"]
TINY_SIZES = {
    "env_args": {"env": "TokenTask", "net": "tiny_hybrid"},
    "horizon_steps": 32,
    "roofline": {"layer_types": TINY_LAYERS, "heads_held": 2},
    "trunk_layers": ["layer_0", "layer_1", "layer_2"],
}
TINY_GEOMETRY = {"layer_types": tuple(TINY_LAYERS), "attention_head_dim": 16,
                 "query_block": 16, "scan_block": 8}
REHEARSAL = {
    "traffic": {"warm_steps": 3, "warm_offers": 5, "rate_eps": 4},
    "train_args": {"forward_steps": 32, "compress_steps": 4,
                   "batch_size": 1, "minimum_episodes": 16,
                   "update_episodes": 20, "updates_per_epoch": 3,
                   "device_replay_mb": 64, "compute_dtype": "float32"},
    "corpus": {"episodes": 24, "name": "olmo_tiny"},
}


def _tiny_manifest(config_path):
    """The manifest with ``olmo_hybrid_tp2``'s entry pointing at the
    tiny copy of its file."""
    from benchmarks.harness import cells

    manifest = cells.load_manifest()
    for entry in manifest["configs"]:
        if entry["name"] == CONFIG:
            entry["file"] = str(config_path)
    return manifest


def _tiny_reference():
    from benchmarks.reference import olmo_hybrid_net

    olmo_hybrid_net.GEOMETRY.update(TINY_GEOMETRY)


def the_recurrence_drops_its_decay():
    """A program whose delta layers never forget (``alpha = 1``: the
    plain delta rule in the gated one's place)."""
    import jax.numpy as jnp

    from handyrl_tpu.models import sequence_net

    gated = sequence_net.delta_scan

    def ungated(q, k, v, g, beta, chunk):
        return gated(q, k, v, jnp.zeros_like(g), beta, chunk)

    sequence_net.delta_scan = ungated


def rehearse(config_path, *flags):
    from benchmarks import run
    from benchmarks.harness import cells

    manifest = _tiny_manifest(config_path)
    cells.load_manifest = lambda root=cells.ROOT: manifest
    _tiny_reference()
    if "no_decay" in flags:
        the_recurrence_drops_its_decay()
    return run.main(["--workload", CELL, "--seed", str(2**31 + 47),
                     "--seconds", "4", "--trace", "0"], rehearsal=REHEARSAL)


# -- the tests ---------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_config(tmp_path_factory):
    from benchmarks.harness.cells import Cell, load_manifest

    config = dict(Cell(load_manifest(), CELL).config, **TINY_SIZES)
    path = tmp_path_factory.mktemp("olmo") / "olmo_tiny.yaml"
    path.write_text(yaml.safe_dump(config))
    return path


@pytest.fixture()
def tiny_cell(tiny_config, monkeypatch):
    from benchmarks.harness.cells import Cell
    from benchmarks.reference import olmo_hybrid_net

    monkeypatch.setattr(olmo_hybrid_net, "GEOMETRY",
                        dict(olmo_hybrid_net.GEOMETRY))
    _tiny_reference()
    cell = Cell(_tiny_manifest(tiny_config), CELL)
    cell.config["train_args"].update(REHEARSAL["train_args"])
    cell.config["corpus"].update(REHEARSAL["corpus"])
    return cell


def _rehearse(tiny_config, *flags):
    # a run clears and works in its CELL's run directory (run.py): two
    # rehearsals of this cell, handed to two test workers, take turns
    with open(os.path.join(tempfile.gettempdir(),
                           "olmo_cell_rehearsal.lock"), "w") as turn:
        fcntl.flock(turn, fcntl.LOCK_EX)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), str(tiny_config),
             *flags], capture_output=True, text=True, timeout=1500, cwd=ROOT,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-1]), lines


def _published_shapes(**sizes):
    import jax
    import numpy as np

    from handyrl_tpu.models import sequence_net

    net = sequence_net.SequencePolicyNet(
        sequence_net.PRESETS[CONFIG]._replace(**sizes))
    return jax.eval_shape(lambda: net.init(
        jax.random.PRNGKey(0), np.zeros((1,), np.int32),
        net.init_hidden((1,)))["params"])


def _size(tree):
    import jax

    return sum(leaf.size for leaf in jax.tree.leaves(tree))


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
    # (a step of 8e-9 moves a two-element leaf by a few of its last
    # bits, and ``update_gap`` is of that change)
    assert result["check"].pop("update_gap")["value"] < 5e-3
    assert max(v["value"] for v in result["check"].values()) < 1e-4


def test_a_recurrence_that_drops_its_decay_comes_out_not_correct(
        tiny_config):
    """The gate is part of the result: a state that never forgets is
    another model's."""
    result, lines = _rehearse(tiny_config, "no_decay")
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
    assert training.__name__ == "benchmarks.reference.olmo_hybrid_training"
    assert net.__name__ == "benchmarks.reference.olmo_hybrid_net"
    assert one_seat and net.RECURRENT is False
    cost_of = roofline.cost_function(tiny_cell.config)
    assert cost_of.__module__ == "benchmarks.cost.olmo_hybrid"
    env = make_env(tiny_cell.config["env_args"])
    model = TPUModel(env.net())
    shapes = weights.param_shapes(model.module, env.observation(0),
                                  model.init_hidden([1]))
    cost = cost_of(shapes, train, tiny_cell.config["roofline"], 32)
    assert set(cost["parts"]) == {
        "delta", "delta_scan", "attention", "mlp", "head"}
    assert cost["flops"] == pytest.approx(
        sum(p["flops"] for p in cost["parts"].values()))
    assert cost["bytes"] > sum(p["bytes"] for p in cost["parts"].values())
    assert all(p["flops"] > 0 < p["bytes"] for p in cost["parts"].values())
    # every leaf is some part's: nothing is counted under no name
    n_params = sum(leaf.size for leaf in jax.tree.leaves(shapes))
    assert cost["bytes"] >= 32.0 * n_params


def test_the_published_count_is_the_issues():
    """At the published widths, from shapes alone: 766.2 M parameters
    at the cut, 928.8 M at every head, 208 M a layer uncut; and the
    file's widths under their published keys."""
    from benchmarks.harness.cells import Cell, load_manifest

    cut, uncut = _published_shapes(), _published_shapes(heads_held=0)
    assert _size(cut) == 766_245_786
    assert _size(cut["layer_0"]["delta"]) == 44_375_262
    assert _size(cut["layer_3"]["attn"]) == 29_495_040
    assert _size(cut["layer_0"]["mlp"]) == 126_812_160
    assert _size(cut["embedding"]) + _size(cut["head"]) == 2 * 12544 * 3840
    assert _size(uncut) == 928_866_036
    layers = sum(_size(uncut[f"layer_{i}"]) for i in range(4))
    assert layers / 4 == pytest.approx(208.1e6, rel=1e-3)
    assert _size(uncut["layer_0"]["delta"]) == 88_750_332
    config = Cell(load_manifest(), CELL).config
    assert (config["hidden_size"], config["intermediate_size"],
            config["linear_key_head_dim"], config["linear_value_head_dim"],
            config["linear_conv_kernel_dim"], config["head_dim"],
            config["rms_norm_eps"], config["linear_allow_neg_eigval"]) == (
                3840, 11008, 96, 192, 4, 128, 1e-6, True)
    held = ("num_attention_heads", "num_key_value_heads",
            "linear_num_key_heads", "linear_num_value_heads")
    assert [config[k] for k in held] == [15] * 4
    assert [config["published"][k] for k in held] == [30] * 4
    assert (config["published"]["num_hidden_layers"],
            config["published"]["vocab_size"]) == (32, 100352)
    assert set(config["reduced"]) == set(held) | {
        "num_hidden_layers", "vocab_size", "epochs", "qk_norm_statistic"}
    assert config["rope_parameters"] == {"rope_theta": None}


def test_the_recurrences_count_is_the_algorithms_and_knows_no_chunk(
        tiny_cell):
    """``delta_scan`` is counted as the rule needs it (a position and
    head three products of ``dk x dv``; operands once each way, no
    state through HBM): a chunk-wise pass's own arithmetic is not in
    it, so the configuration states no chunk and the count reads
    none."""
    from benchmarks.harness import roofline
    from benchmarks.harness.cells import Cell, load_manifest

    config = Cell(load_manifest(), CELL).config
    assert set(config["roofline"]) == {"layer_types", "heads_held"}
    cost_of = roofline.cost_function(config)
    shapes = _published_shapes()
    costs = [cost_of(shapes, config["train_args"],
                     dict(config["roofline"], **more), 32)
             for more in ({}, {"chunk": 16})]
    assert costs[0] == costs[1]
    scan = costs[0]["parts"]["delta_scan"]
    assert scan["flops"] == 3 * 4096 * 3 * (3 * 2 * 15 * 96 * 192)
    assert scan["bytes"] == 3 * 2 * 4096 * 15 * (2 * 96 + 2 * 192 + 2) * 2
    delta = costs[0]["parts"]["delta"]
    projections = 3840 * (2 * 1440 + 3 * 2880 + 2 * 15)
    assert delta["flops"] == 3 * 4096 * 3 * 2 * (projections + 4 * 5760)
    # the step's total: 17.9 TFLOP a step of 4,096 positions
    assert costs[0]["flops"] == pytest.approx(17.9e12, rel=0.01)


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
    assert {"step_delta_ms", "step_delta_scan_ms", "delta_scan_roofline",
            "delta_roofline", "step_mlp_ms", "delta_retention",
            "step_attention_ms", "attention_roofline", "step_head_ms",
            "seq_fill_share", "fused_step_roofline"} <= names
    assert not {"step_moe_ms", "moe_roofline", "moe_load_imbalance",
                "moe_held_pick_share", "step_latent_attention_ms",
                "step_mtp_ms", "boundary_drain_ms"} & names
    for name in names:
        assert callable(run._reader(name))


if __name__ == "__main__":
    os._exit(rehearse(*sys.argv[1:]))
