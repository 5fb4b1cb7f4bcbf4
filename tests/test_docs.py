"""Docs <-> code consistency.  docs/parameters.md must document every
config key and must not document keys that do not exist, so the page
cannot drift from handyrl_tpu/config.py; and README.md, docs/ and the
CI workflow may name only files, modules and recorded runs that the
checkout holds, so a deletion cannot leave a reader pointed at
nothing."""

import dataclasses
import importlib.util
import os
import re

import pytest

from handyrl_tpu.anakin.config import AnakinConfig
from handyrl_tpu.config import TrainConfig, WorkerConfig
from handyrl_tpu.pipeline.config import PipelineConfig
from handyrl_tpu.resilience.chaos import ChaosConfig
from handyrl_tpu.serving.config import RouterConfig, ServingConfig
from handyrl_tpu.telemetry.costmodel import PerfConfig

DOCS = os.path.join(os.path.dirname(__file__), "..", "docs",
                    "parameters.md")


def _documented_keys():
    with open(DOCS) as f:
        text = f.read()
    # keys are documented as "* `name`, type = ..." or "* `name`" bullets
    return set(re.findall(r"^\s*\* `([a-z_]+)`", text, re.MULTILINE))


def _config_keys():
    keys = set()
    for field in dataclasses.fields(TrainConfig):
        if field.name == "env":
            continue  # internal merged-env slot, not a YAML key
        keys.add("lambda" if field.name == "lambda_" else field.name)
    for field in dataclasses.fields(WorkerConfig):
        keys.add(field.name)
    for field in dataclasses.fields(ChaosConfig):
        keys.add(field.name)  # the documented chaos.* sub-keys
    for field in dataclasses.fields(PipelineConfig):
        keys.add(field.name)  # the documented pipeline.* sub-keys
    for field in dataclasses.fields(AnakinConfig):
        keys.add(field.name)  # the documented anakin.* sub-keys
    for field in dataclasses.fields(ServingConfig):
        keys.add(field.name)  # the documented serving.* sub-keys
    for field in dataclasses.fields(RouterConfig):
        keys.add(field.name)  # the documented router.* sub-keys
    # PerfConfig is a plain class, not a dataclass: its KEYS tuple is
    # the validated perf.* key set
    keys.update(PerfConfig.KEYS)
    keys.update({"env", "opponent"})  # env_args.env + eval.opponent
    return keys


def test_every_config_key_is_documented():
    missing = _config_keys() - _documented_keys()
    assert not missing, f"undocumented config keys: {sorted(missing)}"


def test_no_phantom_keys_documented():
    phantom = _documented_keys() - _config_keys()
    assert not phantom, (
        f"docs/parameters.md documents non-existent keys: "
        f"{sorted(phantom)}")


DOC_PAGES = ("api.md", "custom_environment.md",
             "large_scale_training.md", "observability.md",
             "parameters.md", "serving.md", "static_analysis.md")


def test_docs_exist():
    for name in DOC_PAGES:
        path = os.path.join(os.path.dirname(DOCS), name)
        assert os.path.exists(path), f"missing doc {name}"


# -- the documents name only what is there ------------------------------
#
# PERF.md, ROADMAP.md and CHANGES.md are records and name what has
# gone; README.md and docs/ describe the system as it stands.

ROOT = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
# what building, testing and running leave behind (.gitignore)
_LEFT_BEHIND = {".git", ".probe", ".jax_cache", ".pytest_cache",
                ".hypothesis", "__pycache__", "chiprun_out"}
_ROOTED = ("scripts/", "tests/", "benchmarks/", "handyrl_tpu/", "runs/",
           "docs/")
_COMMAND = re.compile(
    r"\bpython3?\s+(?:-m\s+([\w.]+)|([\w./-]+\.py)\b)")


def _read(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return f.read()


@pytest.fixture(scope="module")
def paths():
    """Every file and directory of the working tree, root-relative."""
    paths = set()
    for base, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in _LEFT_BEHIND]
        rel = os.path.relpath(base, ROOT)
        for name in dirs + files:
            paths.add(os.path.normpath(os.path.join(rel, name)))
    return paths


def _resolves(name, paths):
    """``name`` is a path of the checkout, or the tail of one (docs
    write ``telemetry/spans.py`` and ``learner.py`` for files of the
    package)."""
    name = os.path.normpath(name)
    return name in paths or any(p.endswith(os.sep + name) for p in paths)


def _stale_commands(text, paths):
    stale = []
    for module, script in _COMMAND.findall(text):
        if module and importlib.util.find_spec(module) is None:
            stale.append(f"python -m {module}")
        if script and not _resolves(script, paths):
            stale.append(f"python {script}")
    return stale


def _stale_code_spans(text, paths):
    stale = []
    for span in re.findall(r"`([^`\n]+)`", text):
        words = span.split()
        if not words:
            continue
        # `tests/test_x.py::test_y`, `learner.py:677-685`
        name = re.sub(r":[:\d].*$", "", words[0]).rstrip(",.;")
        if not (name.endswith(".py") or name.startswith(_ROOTED)):
            continue
        if re.search(r"[<>*{}$]", name):
            continue    # a placeholder or a pattern, not a name
        if not _resolves(name, paths):
            stale.append(span)
    return stale


@pytest.mark.parametrize(
    "document", ["README.md"] + [f"docs/{n}" for n in DOC_PAGES])
def test_document_names_only_files_that_exist(document, paths):
    text = _read(document)
    stale = _stale_commands(text, paths) + _stale_code_spans(text, paths)
    assert not stale, f"{document} names what is not there: {stale}"


def test_ci_runs_only_files_that_exist(paths):
    stale = _stale_commands(_read(".github", "workflows", "ci.yaml"),
                            paths)
    assert not stale, f"ci.yaml runs what is not there: {stale}"


def test_readme_layout_lists_every_package():
    layout = _read("README.md").split("## Layout", 1)[1]
    layout = layout.split("```")[1]
    package = os.path.join(ROOT, "handyrl_tpu")
    expected = {"tests", "scripts", "benchmarks"} | {
        d for d in os.listdir(package)
        if os.path.exists(os.path.join(package, d, "__init__.py"))}
    missing = sorted(d for d in expected
                     if not re.search(rf"^\s*{d}/\s", layout, re.M))
    assert not missing, f"README.md Layout omits: {missing}"


def test_every_recorded_run_is_one_the_readme_names():
    """A record nobody points at is how 201 files of CPU timings
    stayed for twenty PRs: what lies under runs/ is a capability run
    with its config, and the README says which result it holds."""
    paragraph = _read("README.md").split("Recorded training runs", 1)[1]
    paragraph = paragraph.split("\n\n", 1)[0]
    scratch = set(re.findall(r"^runs/([^*/\s]+)/$", _read(".gitignore"),
                             re.M))
    runs = os.path.join(ROOT, "runs")
    for name in sorted(set(os.listdir(runs)) - scratch):
        path = os.path.join(runs, name)
        assert os.path.isdir(path), f"a file directly under runs/: {name}"
        assert os.path.exists(os.path.join(path, "config.yaml")), (
            f"runs/{name} holds no config.yaml: not a recorded run")
        assert re.search(rf"`runs/{name}`", paragraph), (
            f"README.md's 'Recorded training runs' does not name "
            f"`runs/{name}`")


def test_static_analysis_doc_covers_every_rule():
    """docs/static_analysis.md documents each lint rule by id — ALL
    SIX registries (the suppression comments reference these names,
    so the page is the rule registries' public contract).  Mechanical,
    like the parameters check above: a new rule set cannot land
    undocumented."""
    from handyrl_tpu.analysis.commrules import COMM_RULES
    from handyrl_tpu.analysis.leakrules import LEAK_RULES
    from handyrl_tpu.analysis.numrules import NUM_RULES
    from handyrl_tpu.analysis.racerules import RACE_RULES
    from handyrl_tpu.analysis.rules import RULES
    from handyrl_tpu.analysis.shardrules import SHARD_RULES

    path = os.path.join(os.path.dirname(DOCS), "static_analysis.md")
    with open(path) as f:
        text = f.read()
    missing = [r
               for r in (list(RULES) + list(SHARD_RULES)
                         + list(COMM_RULES) + list(RACE_RULES)
                         + list(NUM_RULES) + list(LEAK_RULES))
               if f"`{r}`" not in text]
    assert not missing, f"rules undocumented in static_analysis.md: {missing}"


def test_list_rules_covers_every_registry():
    """`handyrl-jaxlint --list-rules` prints every registered rule of
    every family with its one-line doc, without needing the family
    flags — the CLI's discoverability contract."""
    import contextlib
    import io

    from handyrl_tpu.analysis.commrules import COMM_RULES
    from handyrl_tpu.analysis.jaxlint import main
    from handyrl_tpu.analysis.leakrules import LEAK_RULES
    from handyrl_tpu.analysis.numrules import NUM_RULES
    from handyrl_tpu.analysis.racerules import RACE_RULES
    from handyrl_tpu.analysis.rules import RULES
    from handyrl_tpu.analysis.shardrules import SHARD_RULES

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert main(["--list-rules"]) == 0
    out = buf.getvalue()
    for registry in (RULES, SHARD_RULES, COMM_RULES, RACE_RULES,
                     NUM_RULES, LEAK_RULES):
        for rule_id, rule in registry.items():
            assert f"{rule_id}: {rule.summary}" in out, (
                f"--list-rules missing {rule_id} (or its summary)")
