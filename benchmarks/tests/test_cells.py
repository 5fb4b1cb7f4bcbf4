"""Every cell's command, end to end at a tiny size on the CPU, behind
the tests' own rehearsal entry; the command line itself refuses to run
without the cell's TPU chips; a later PR's configuration brings its
plain reference as a file of its own."""

import json
import os
import re
import subprocess
import sys

import pytest
import yaml

from benchmarks.harness import check
from benchmarks.harness.cells import BENCH_DIR, ROOT, Cell, load_manifest

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = [w["name"] for w in load_manifest()["workloads"]]


def _env(**extra):
    return dict(os.environ, JAX_PLATFORMS="cpu", **extra)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end_in_rehearsal(cell):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse.py"), cell],
        capture_output=True, text=True, timeout=1500, cwd=ROOT, env=_env())
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "median_leaf_change", "check"]
    moved = result["median_leaf_change"]    # both sides trained at one rate
    assert 0.9 < moved["program"] / moved["reference"] < 1.1
    limits = Cell(load_manifest(), cell).config["check_limits"]
    assert {k: v["limit"] for k, v in result["check"].items()} == limits
    assert proc.stderr.strip().splitlines()[-len(result["check"]):] == [
        l for l in lines if l.startswith("check ") and " limit " in l]
    assert result["correct"] is True, [l for l in lines if "check " in l]
    assert "setup_s" in result["metrics"] and len(result["metrics"]) >= 2
    assert all(v["value"] > 0 for v in result["metrics"].values())
    phases = [l.split()[1] for l in lines if l.startswith("setup_phase ")]
    assert phases == ["import", "backend", "corpus", "build", "prime",
                      "compile", "warm"]
    (rss,) = [l for l in lines if l.startswith("host peak rss: ")]
    peak, before = map(int, re.fullmatch(
        r"host peak rss: (\d+) bytes \((\d+) before the reference\)",
        rss).groups())
    assert peak >= before > 100e6


@pytest.mark.parametrize("cell", CELLS[:1])
def test_the_reference_follows_with_the_device_released(cell):
    """A traced rehearsal: after the window the ring's rows are checked
    and the step's phases captured (once) while the program's state is
    alive, then the device is released, and only then the reference
    follows; the readers find the captured phases in the cache."""
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse.py"), cell, "traced"],
        capture_output=True, text=True, timeout=1500, cwd=ROOT, env=_env())
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    marks = [l for l in lines if l.startswith((
        "order ", "device bytes in use before the reference: ", "check "))]
    assert marks[:3] == [
        "order ring_rows", "order step_profile", "order release"]
    in_use, deleted = re.fullmatch(
        r"device bytes in use before the reference: (\d+) "
        r"\((\d+) arrays deleted\)", marks[3]).groups()
    assert int(in_use) == 0 < int(deleted)    # the CPU reports no bytes
    assert marks[4].startswith("check losses ")
    assert [m for m in marks if m.startswith("order ")] == marks[:3]
    result = json.loads(lines[-1])
    assert result["correct"] is True
    assert {"step_device_ms", "device_idle_share"} <= set(result["metrics"])
    assert list(result)[-2:] == ["breakdown", "check"]


@pytest.mark.parametrize("cell", CELLS[:1])
def test_command_refuses_to_run_without_a_tpu(cell):
    manifest = load_manifest()
    proc = subprocess.run(
        manifest["command"] + ["--workload", cell, "--seed", "1",
                               "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=ROOT, env=_env())
    assert proc.returncode != 0
    assert "needs" in proc.stderr and "TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_a_new_configuration_brings_its_reference_as_a_file(tmp_path):
    """What a later PR does for a net the benchmark has not seen: a
    configuration file naming a reference module, that module as a new
    file of ``benchmarks/reference/``, and entries in BENCHMARK.json.
    No file that is there changes (the new files live in ``tmp_path``
    here, put on the package's search path)."""
    import benchmarks.reference as package

    (tmp_path / "toy_net.py").write_text(
        "RECURRENT = False\n\n\n"
        "def forward(params, obs, hidden=None, lowp=None):\n"
        "    return {'policy': obs.sum(), 'value': params}\n")
    with open(os.path.join(BENCH_DIR, "configs", "geese32.yaml")) as f:
        config = yaml.safe_load(f)
    config["reference"] = "toy_net"
    (tmp_path / "toy.yaml").write_text(yaml.safe_dump(config))
    manifest = load_manifest()
    manifest["configs"].append(dict(
        manifest["configs"][0], name="toy", file=str(tmp_path / "toy.yaml")))
    manifest["workloads"].append(dict(
        manifest["workloads"][0], name="toy.fed", config="toy"))
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if manifest["workloads"][0]["name"] in metric.get("workloads", []):
            metric["workloads"].append("toy.fed")
    cell = Cell(manifest, "toy.fed")
    assert cell.config["reference"] == "toy_net"
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    package.__path__.append(str(tmp_path))
    try:
        _training, net, _one_seat = check.reference_setup(
            cell.config, cell.program_args()["train_args"])
    finally:
        package.__path__.remove(str(tmp_path))
        sys.modules.pop("benchmarks.reference.toy_net", None)
    assert net.__file__ == str(tmp_path / "toy_net.py")
    assert net.forward(3.0, __import__("numpy").ones(4))["value"] == 3.0
