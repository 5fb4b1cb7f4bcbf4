import json
import os
import re

from benchmarks.harness.cells import BENCH_DIR, ROOT, Cell, load_manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_names_units_and_files():
    m = load_manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in m["configs"]]
             + [w["name"] for w in m["workloads"]]
             + [w["traffic"] for w in m["workloads"]]
             + [x["name"] for x in m["end_to_end"] + m["per_layer"]])
    assert all(NAME.match(n) for n in names), names
    metrics = m["end_to_end"] + m["per_layer"]
    assert all(UNIT.match(x["unit"]) for x in metrics)
    assert len({x["name"] for x in metrics}) == len(metrics)
    assert all(x["better"] in ("lower", "higher") for x in metrics)
    for c in m["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"])), c
    for x in m["per_layer"]:
        assert x["moves"] in {e["name"] for e in m["end_to_end"]}
        assert os.path.exists(os.path.join(
            BENCH_DIR, "layer_metrics", x["name"] + ".py")), x["name"]
    four = [w for w in m["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(m["workloads"]) // 4)
    assert len(json.dumps(m)) < 64 * 1024


def test_every_cell_reports_setup_another_metric_and_a_layer():
    m = load_manifest()
    for w in m["workloads"]:
        cell = Cell(m, w["name"])
        e2e = {x["name"] for x in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert cell.per_layer, w["name"]
        for x in cell.per_layer:     # a layer metric's cells report its target
            assert x["moves"] in e2e, (w["name"], x["name"])
        args = cell.program_args()
        assert args["train_args"]["seed"] == cell.config["train_args"]["seed"]
