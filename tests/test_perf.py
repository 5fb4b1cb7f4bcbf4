"""Perf attribution layer: cost model, self-time tree, report.

Covers the PR-20 contracts end to end without a training run:

  * PerfConfig validation + peak resolution (table vs overrides);
  * the RetraceGuard ``on_compile`` hook — fires once per NEW abstract
    signature, BEFORE the call, and hook failures never kill the step;
  * CostModel harvest against a real tiny jit on CPU (XLA's own
    cost_analysis numbers) and the epoch MFU/roofline reduction,
    including every verdict branch;
  * self_time_tree containment (nesting, threads, instants) and the
    untracked-residual identity over a metrics record's rounded values;
  * Attributor snapshots + the flight-recorder ``register_dump_extra``
    ride-along;
  * scripts/attribution_report.py over a synthetic run directory.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from handyrl_tpu import telemetry
from handyrl_tpu.analysis.guards import RetraceGuard
from handyrl_tpu.telemetry.attribution import (
    Attributor,
    self_time_tree,
    top_self,
    untracked_residual,
)
from handyrl_tpu.telemetry.costmodel import (
    DEVICE_PEAKS,
    CostModel,
    PerfConfig,
    resolve_peaks,
)

sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                "..", "scripts"))

import attribution_report  # noqa: E402


# -- PerfConfig / peaks -------------------------------------------------

def test_perf_config_defaults_and_validation():
    cfg = PerfConfig.from_config({})
    assert cfg.peak_tflops == 0.0
    assert cfg.peak_hbm_gbs == 0.0
    assert cfg.cost_analysis is True
    with pytest.raises(ValueError, match="unknown perf keys"):
        PerfConfig.from_config({"peak_tflop": 1.0})
    with pytest.raises(ValueError, match="peak_tflops"):
        PerfConfig.from_config({"peak_tflops": -1.0})
    with pytest.raises(ValueError, match="peak_hbm_gbs"):
        PerfConfig.from_config({"peak_hbm_gbs": -5})


def test_resolve_peaks_table_override_and_unknown():
    # the table row wins when no override is set
    assert resolve_peaks(None, kind="TPU v4") == DEVICE_PEAKS["TPU v4"]
    # config overrides win over the table
    cfg = PerfConfig(peak_tflops=123.0, peak_hbm_gbs=456.0)
    assert resolve_peaks(cfg, kind="TPU v4") == (123.0, 456.0)
    # a partial override keeps the table's other column
    cfg = PerfConfig(peak_tflops=123.0)
    assert resolve_peaks(cfg, kind="TPU v4") == \
        (123.0, DEVICE_PEAKS["TPU v4"][1])
    # unknown kind, no override: nothing to claim
    assert resolve_peaks(None, kind="CPU") == (None, None)


# -- guard hook + harvest ----------------------------------------------

def test_guard_on_compile_fires_once_per_new_signature():
    guard = RetraceGuard(name="t")
    seen = []
    guard.on_compile = lambda label, fn, args, kwargs: \
        seen.append((label, args[0].shape))
    wrapped = guard.wrap(jax.jit(lambda x: x * 2), label="prog")
    x8, x16 = jnp.ones(8), jnp.ones(16)
    wrapped(x8)
    wrapped(x8)       # same signature: no second fire
    wrapped(x16)      # new signature: fires again
    assert seen == [("prog", (8,)), ("prog", (16,))]
    assert guard.compiles == 2


def test_guard_on_compile_failure_never_kills_the_step(capsys):
    guard = RetraceGuard(name="t")

    def bad_hook(label, fn, args, kwargs):
        raise RuntimeError("boom")

    guard.on_compile = bad_hook
    wrapped = guard.wrap(jax.jit(lambda x: x + 1))
    out = wrapped(jnp.ones(4))
    assert out.shape == (4,)
    assert "on_compile hook failed" in capsys.readouterr().out


def test_costmodel_harvests_real_xla_numbers_on_cpu():
    cm = CostModel(PerfConfig(), kind="cpu-test")
    fn = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32), jnp.float32)
    cm.on_compile("step", fn, (x,), {})
    prog = cm.program("step")
    assert prog is not None and prog["harvests"] == 1
    # a 32x32 matmul is ~2*32^3 flops; XLA's number includes the sum
    assert prog["flops"] >= 2 * 32 ** 3
    assert prog["bytes"] > 0
    assert cm.harvest_failures == 0


def test_costmodel_async_harvest_lands_off_thread():
    """The inference service's hook: avals snapshot synchronously, the
    compile runs on the drain worker — the caller never blocks on XLA
    (the blocking variant stalled the batching thread long enough that
    workers degraded to local inference in the chaos drill)."""
    cm = CostModel(PerfConfig(), kind="cpu-test")
    fn = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32), jnp.float32)
    cm.on_compile_async("infer", fn, (x,), {})
    deadline = time.time() + 30.0
    while cm.program("infer") is None and time.time() < deadline:
        time.sleep(0.01)
    prog = cm.program("infer")
    assert prog is not None and prog["flops"] >= 2 * 32 ** 3
    assert cm.harvest_failures == 0
    # the worker exits once the queue drains (once-per-signature
    # harvests must not hold a thread for the process lifetime)
    deadline = time.time() + 10.0
    while cm._worker is not None and time.time() < deadline:
        time.sleep(0.01)
    assert cm._worker is None


def test_costmodel_async_harvest_first_signature_wins():
    """The serving path re-traces one program per batch bucket; only
    the first bucket harvests (a per-bucket re-compile would contend
    for the core at arbitrary serving moments, e.g. mid-respawn)."""
    cm = CostModel(PerfConfig(), kind="cpu-test")
    fn = jax.jit(lambda x: (x @ x).sum())
    cm.on_compile_async("infer", fn, (jnp.ones((16, 16)),), {})
    deadline = time.time() + 30.0
    while cm.program("infer") is None and time.time() < deadline:
        time.sleep(0.01)
    first = cm.program("infer")
    assert first is not None
    cm.on_compile_async("infer", fn, (jnp.ones((64, 64)),), {})
    deadline = time.time() + 5.0
    while cm._worker is not None and time.time() < deadline:
        time.sleep(0.01)
    assert cm.program("infer") == first     # second bucket skipped


def test_costmodel_async_harvest_failure_counts_never_raises():
    cm = CostModel(PerfConfig(), kind="cpu-test")
    cm.on_compile_async("infer", object(), (), {})   # no .lower at all
    deadline = time.time() + 10.0
    while cm.harvest_failures == 0 and time.time() < deadline:
        time.sleep(0.01)
    assert cm.harvest_failures == 1
    assert cm.program("infer") is None


def test_costmodel_harvest_failure_counts_never_raises(capsys):
    cm = CostModel(PerfConfig(), kind="cpu-test")
    cm.on_compile("step", object(), (), {})    # no .lower at all
    assert cm.program("step") is None
    assert cm.harvest_failures == 1
    # ... and says why, with the exception, once per program
    cm.on_compile("step", object(), (), {})
    assert cm.harvest_failures == 2
    err = capsys.readouterr().err
    assert err.count("cost harvest failed for 'step'") == 1
    assert "AttributeError" in err


def test_costmodel_harvest_off_by_config():
    cm = CostModel(PerfConfig(cost_analysis=False), kind="cpu-test")
    cm.on_compile("step", jax.jit(lambda x: x), (jnp.ones(4),), {})
    assert cm.program("step") is None
    assert cm.harvest_failures == 0


def test_costmodel_keeps_latest_signature_numbers():
    cm = CostModel(PerfConfig(), kind="cpu-test")
    fn = jax.jit(lambda x: (x @ x).sum())
    cm.on_compile("step", fn, (jnp.ones((16, 16)),), {})
    small = cm.program("step")["flops"]
    cm.on_compile("step", fn, (jnp.ones((64, 64)),), {})
    prog = cm.program("step")
    assert prog["flops"] > small        # re-laid geometry replaces
    assert prog["harvests"] == 2


# -- epoch reduction ---------------------------------------------------

def _programmed(flops, hbm_bytes, peak_tflops=0.0, peak_gbs=0.0):
    cm = CostModel(PerfConfig(peak_tflops=peak_tflops,
                              peak_hbm_gbs=peak_gbs), kind="cpu-test")
    with cm._lock:
        cm._programs["step"] = {
            "flops": flops, "bytes": hbm_bytes, "harvests": 1}
    return cm


def test_epoch_metrics_schema_is_stable_when_unknowable():
    cm = CostModel(PerfConfig(), kind="cpu-test")
    out = cm.epoch_metrics("step", 1.0, 10)
    assert out == {"mfu": None, "achieved_tflops": None,
                   "arithmetic_intensity": None,
                   "roofline_verdict": "unknown"}
    # harvested program but no peak row: achieved yes, mfu no
    cm = _programmed(2e12, 1e9)
    out = cm.epoch_metrics("step", 2.0, 10)
    assert out["achieved_tflops"] == pytest.approx(10.0)
    assert out["mfu"] is None
    assert out["roofline_verdict"] == "unknown"


def test_epoch_metrics_mfu_and_roofline_math():
    # ridge = 100 TFLOP/s / 1000 GB/s * 1e3 = 100 flops/byte
    cm = _programmed(2e12, 1e9, peak_tflops=100.0, peak_gbs=1000.0)
    out = cm.epoch_metrics("step", 2.0, 10)
    # achieved = 2e12 * 10 / 2.0 / 1e12 = 10 TFLOP/s -> mfu 0.1
    assert out["achieved_tflops"] == pytest.approx(10.0)
    assert out["mfu"] == pytest.approx(0.1)
    # intensity 2e12/1e9 = 2000 flops/byte >= ridge -> compute-bound
    assert out["arithmetic_intensity"] == pytest.approx(2000.0)
    assert out["roofline_verdict"] == "compute-bound"

    cm = _programmed(1e10, 1e9, peak_tflops=100.0, peak_gbs=1000.0)
    out = cm.epoch_metrics("step", 2.0, 10)
    # intensity 10 flops/byte < ridge 100 -> memory-bound
    assert out["roofline_verdict"] == "memory-bound"
    # zero device time / steps: rates unknowable, intensity still known
    out = cm.epoch_metrics("step", 0.0, 0)
    assert out["achieved_tflops"] is None and out["mfu"] is None
    assert out["arithmetic_intensity"] == pytest.approx(10.0)


def test_costmodel_stats_shape():
    cm = _programmed(1.0, 1.0, peak_tflops=9.0, peak_gbs=9.0)
    stats = cm.stats()
    assert stats["device_kind"] == "cpu-test"
    assert stats["peak_tflops"] == 9.0
    assert stats["programs"]["step"]["harvests"] == 1
    assert stats["cost_analysis"] is True
    assert stats["harvest_failures"] == 0


# -- self-time tree ----------------------------------------------------

def _span(name, ts, dur, role="learner", pid=1, tid=1):
    return {"name": name, "ts": ts, "dur": dur,
            "role": role, "pid": pid, "tid": tid}


def test_self_time_tree_subtracts_nested_children():
    tree = self_time_tree([
        _span("epoch", 0.0, 10.0),
        _span("update", 1.0, 4.0),
        _span("device", 2.0, 2.0),     # nested inside update
        _span("save", 6.0, 3.0),       # sibling of update
    ])
    assert tree["learner/epoch"]["self_sec"] == pytest.approx(3.0)
    assert tree["learner/update"]["self_sec"] == pytest.approx(2.0)
    assert tree["learner/device"]["self_sec"] == pytest.approx(2.0)
    assert tree["learner/save"]["self_sec"] == pytest.approx(3.0)
    # total time is never reduced by children
    assert tree["learner/epoch"]["total_sec"] == pytest.approx(10.0)


def test_self_time_tree_threads_never_nest_across():
    tree = self_time_tree([
        _span("a", 0.0, 10.0, tid=1),
        _span("b", 1.0, 5.0, tid=2),   # other thread: NOT a child
    ])
    assert tree["learner/a"]["self_sec"] == pytest.approx(10.0)
    assert tree["learner/b"]["self_sec"] == pytest.approx(5.0)


def test_self_time_tree_aggregates_counts_and_instants():
    tree = self_time_tree([
        _span("step", 0.0, 1.0),
        _span("step", 2.0, 1.0),
        _span("mark", 0.5, 0.0),       # instant event, zero time
        {"ts": 3.0, "dur": 1.0},       # nameless: skipped
    ])
    assert tree["learner/step"]["count"] == 2
    assert tree["learner/step"]["total_sec"] == pytest.approx(2.0)
    assert tree["learner/mark"] == {
        "count": 1, "total_sec": 0.0, "self_sec": 0.0}
    assert len(tree) == 2


def test_self_time_tree_keeps_the_ledgers_overlay_out_of_the_nesting():
    # device.starved begins inside one span of the trainer thread and
    # ends inside another: it is no span's parent and no span's child
    plain = [
        _span("trainer.ingest", 0.0, 4.0),
        _span("ingest.decompress", 0.5, 3.0),
        _span("trainer.update", 4.0, 2.0),
    ]
    tree = self_time_tree(plain + [_span("device.starved", 1.0, 4.5)])
    assert tree["learner/device.starved"] == {
        "count": 1, "total_sec": 4.5, "self_sec": 0.0}
    del tree["learner/device.starved"]
    assert tree == self_time_tree(plain)
    assert tree["learner/trainer.ingest"]["self_sec"] == pytest.approx(1.0)


def test_top_self_orders_by_self_time_then_name():
    tree = self_time_tree([
        _span("big", 0.0, 5.0),
        _span("tie_a", 6.0, 1.0),
        _span("tie_b", 8.0, 1.0),
    ])
    assert top_self(tree, 2) == [["learner/big", 5.0],
                                 ["learner/tie_a", 1.0]]


def test_untracked_residual_identity_over_rounded_values():
    record = {
        "epoch_wall_sec": 2.0,
        "profile_update_sec": 0.7,
        "profile_batch_wait_sec": 0.2,
        "profile_ingest_sec": 0.1,
        "batch_wait_sec": 99.0,        # not a profile_* key: ignored
        "profile_note": "x",           # non-numeric: ignored
    }
    residual = untracked_residual(record)
    assert residual == pytest.approx(1.0)
    # the emitted identity reconciles exactly, by construction
    tracked = sum(v for k, v in record.items()
                  if k.startswith("profile_") and k.endswith("_sec"))
    assert tracked + residual == pytest.approx(
        record["epoch_wall_sec"], abs=1e-9)
    # negative residual (thread-window skew) is representable
    assert untracked_residual(
        {"epoch_wall_sec": 1.0, "profile_update_sec": 1.2}) == \
        pytest.approx(-0.2)
    assert untracked_residual({}) == 0.0


# -- Attributor + dump extras ------------------------------------------

def _ticker(start=0.0, step=1.0):
    t = {"now": start}

    def clock():
        t["now"] += step
        return t["now"]

    return clock


def test_attributor_folds_only_this_epochs_spans():
    telemetry.configure(enabled=True, clock=_ticker())
    attributor = Attributor(top_n=3)
    with telemetry.trace_span("epoch0_work"):
        pass
    snap = attributor.note_epoch({"epoch": 0, "epoch_wall_sec": 5.0})
    assert snap["epoch"] == 0
    assert "learner/epoch0_work" not in snap or True  # role is pid-...
    assert snap["spans"] == 1 and len(snap["tree"]) == 1
    with telemetry.trace_span("epoch1_work"):
        pass
    snap = attributor.note_epoch({"epoch": 1, "epoch_wall_sec": 5.0})
    # the epoch-0 span is older than the mark: excluded from epoch 1
    assert [k.split("/")[1] for k, _ in snap["top_self"]] == \
        ["epoch1_work"]
    assert attributor.epochs == 2
    assert attributor.last is snap


def test_attributor_is_noop_when_telemetry_off():
    telemetry.configure(enabled=False)
    attributor = Attributor()
    assert attributor.note_epoch({"epoch": 0}) is None
    assert attributor.last is None and attributor.epochs == 0


def test_attribution_rides_flight_recorder_dumps(tmp_path):
    telemetry.configure(enabled=True, log_dir=str(tmp_path),
                        role="learner", primary=True)
    attributor = Attributor()
    telemetry.register_dump_extra(
        "attribution", lambda: attributor.last)
    with telemetry.trace_span("work"):
        pass
    attributor.note_epoch({"epoch": 3, "epoch_wall_sec": 1.0,
                           "untracked_residual_sec": 0.25})
    path = telemetry.dump("test")
    doc = json.loads(open(path).read())
    assert doc["attribution"]["epoch"] == 3
    assert doc["attribution"]["untracked_residual_sec"] == 0.25
    assert "learner/work" in doc["attribution"]["tree"]


def test_register_dump_extra_rejects_reserved_names():
    telemetry.configure(enabled=True)
    with pytest.raises(ValueError, match="reserved"):
        telemetry.register_dump_extra("spans", lambda: 1)


def test_failing_dump_extra_never_blocks_the_dump(tmp_path):
    telemetry.configure(enabled=True, log_dir=str(tmp_path),
                        role="learner", primary=True)

    def bad():
        raise RuntimeError("boom")

    telemetry.register_dump_extra("flaky", bad)
    path = telemetry.dump("test")
    doc = json.loads(open(path).read())
    assert doc["reason"] == "test" and "flaky" not in doc


# -- attribution report ------------------------------------------------

def _write_run(tmp_path, shift=0.0):
    run = tmp_path
    run.mkdir(exist_ok=True)
    header = {"meta": {"pid": 1, "role": "learner"}}
    spans = [
        _span("trainer.update", 1.0, 4.0 + shift),
        _span("trainer.batch_wait", 0.2, 0.5),
        _span("gather.recv", 0.5, 1.0, role="gather-0", pid=2),
    ]
    with open(run / "spans-1.jsonl", "w") as f:
        f.write(json.dumps(header) + "\n")
        for rec in spans:
            f.write(json.dumps(rec) + "\n")
    with open(run / "metrics.jsonl", "w") as f:
        for epoch in range(3):
            f.write(json.dumps({
                "epoch": epoch, "epoch_wall_sec": 10.0,
                "mfu": 0.1, "achieved_tflops": 25.0,
                "roofline_verdict": "memory-bound",
                "batch_wait_sec": 2.0,
                "untracked_residual_sec": 0.5}) + "\n")
    return str(run)


def test_attribution_report_builds_and_renders(tmp_path, capsys):
    run = _write_run(tmp_path / "run")
    out = tmp_path / "report.json"
    rc = attribution_report.main([run, "--json", str(out)])
    assert rc == 0
    text = capsys.readouterr().out
    assert "top self-time spans" in text
    assert "learner/trainer.update" in text
    doc = json.loads(out.read_text())
    assert doc["epochs"] == 3 and doc["spans"] == 3
    assert doc["medians"]["mfu"] == pytest.approx(0.1)
    assert doc["medians"]["batch_wait_share"] == pytest.approx(0.2)
    assert doc["tree"]["gather-0/gather.recv"]["self_sec"] == \
        pytest.approx(1.0)


def test_attribution_report_baseline_diff(tmp_path, capsys):
    run = _write_run(tmp_path / "run", shift=2.0)
    base = _write_run(tmp_path / "base", shift=0.0)
    rc = attribution_report.main([run, "--baseline", base])
    assert rc == 0
    text = capsys.readouterr().out
    assert "self-time delta vs baseline" in text
    # trainer.update grew by the injected 2s and tops the movers
    assert "+2.0000s" in text
