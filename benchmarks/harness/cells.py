"""BENCHMARK.json -> one cell: its configuration, its traffic mix and
the metrics it reports.  Nothing here reads a cell's name for meaning:
a later PR adds a cell, a configuration, a traffic mix or a per-layer
metric as new files and entries."""

import json
import os

import yaml

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_manifest(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with its files read."""

    def __init__(self, manifest, name, root=ROOT):
        by_name = {w["name"]: w for w in manifest["workloads"]}
        if name not in by_name:
            raise SystemExit(
                f"unknown workload {name!r}; BENCHMARK.json has "
                f"{sorted(by_name)}")
        self.entry = by_name[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg = {c["name"]: c for c in manifest["configs"]}[
            self.entry["config"]]
        self.config_name = cfg["name"]
        with open(os.path.join(root, cfg["file"])) as f:
            self.config = yaml.safe_load(f)
        self.traffic_name = self.entry["traffic"]
        with open(os.path.join(
                BENCH_DIR, "traffic", self.traffic_name + ".json")) as f:
            self.traffic = json.load(f)

        def mine(metric):
            return name in metric.get("workloads", [name])

        self.end_to_end = [m for m in manifest["end_to_end"] if mine(m)]
        self.per_layer = [m for m in manifest["per_layer"] if mine(m)]

    def program_args(self):
        """The configuration's three sections as its file has them: the
        dict ``main.py`` would read from config.yaml, but for
        ``train_args.base_lr`` where a configuration states it, which
        the program has no key for (``harness/rate.py`` keeps it from
        the ``Learner``; the plain reference reads it).  A traffic mix
        changes none of them: what differs from the source is in the
        configuration's ``changed``."""
        args = {key: json.loads(json.dumps(self.config[key]))
                for key in ("env_args", "train_args", "worker_args")}
        train = args["train_args"]
        # The program's OWN seed stays the configuration's: it closes
        # its draw key over the fused step as a constant, so a new seed
        # is a new program and a cold compile (18-25 s on the v5e) in
        # every run.  ``--seed`` makes what the benchmark makes: the
        # weights, the ring's priming order, the order of offers and the
        # check's samples.
        train["metrics_path"] = "metrics.jsonl"
        return args
