"""Multi-device sharding tests on the virtual 8-device CPU mesh."""

import os
import re

import numpy as np
import pytest

import jax

from handyrl_tpu.parallel import (
    MeshSpec,
    inference_shardings,
    make_mesh,
    make_sharded_update_step,
)
from handyrl_tpu.parallel.mesh import batch_sharding, param_sharding


def _need_devices(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} virtual devices")


def test_mesh_spec_from_config():
    spec = MeshSpec.from_config({"dp": 4, "tp": 2})
    assert spec.size == 8 and spec.shape() == (4, 1, 2)
    with pytest.raises(ValueError):
        MeshSpec.from_config({"bogus": 2})


def test_runtime_package_is_pmap_free():
    """ROADMAP item 2 closeout gate: ``jit`` + ``NamedSharding`` is
    the ONE mainline path.  The runtime package must carry no ``pmap``
    call and no fixed-device-count assumption — only ``analysis/`` may
    mention pmap, as a construct its rules lint.  A repo gate so the
    retired API cannot creep back in a refactor."""
    import handyrl_tpu

    root = os.path.dirname(os.path.abspath(handyrl_tpu.__file__))
    offenders = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        rel = os.path.relpath(dirpath, root)
        if rel == "analysis" or rel.startswith("analysis" + os.sep):
            continue  # the linter may NAME pmap; nothing may USE it
        for fname in filenames:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            with open(path) as f:
                text = f.read()
            if re.search(r"\bpmap\b", text):
                offenders.append((os.path.relpath(path, root), "pmap"))
            if re.search(r"device_count\(\)\s*==\s*\d", text):
                offenders.append((os.path.relpath(path, root),
                                  "fixed device-count equality"))
    assert not offenders, f"GSPMD regression: {offenders}"


def test_make_mesh_oversized_spec_error_names_the_config_key():
    _need_devices(2)
    with pytest.raises(ValueError, match=r"`mesh:` config"):
        make_mesh(MeshSpec(dp=4), devices=jax.devices()[:2])


def test_make_mesh_nondividing_spec_warns(capsys):
    """A mesh shape that does not tile the device count used to eat
    the remainder silently; now it says which devices idle and names
    the config key."""
    _need_devices(8)
    mesh = make_mesh(MeshSpec(dp=3), devices=jax.devices()[:8])
    assert mesh.shape["dp"] == 3
    out = capsys.readouterr().out
    assert "3 of 8 devices" in out and "`mesh:`" in out
    # a dividing subset is a sanctioned choice: no warning
    make_mesh(MeshSpec(dp=4), devices=jax.devices()[:8])
    assert "WARNING" not in capsys.readouterr().out


def test_inference_shardings_contract():
    """params per the tp/fsdp rules, obs/out batch rows on dp — and a
    single-device mesh collapses everything to replication (the
    bit-identical guarantee's structural half)."""
    _need_devices(8)
    P = jax.sharding.PartitionSpec
    mesh = make_mesh(MeshSpec(dp=4, tp=2), devices=jax.devices()[:8])
    params = {"wide": np.zeros((64, 256)), "bias": np.zeros((256,))}
    sh = inference_shardings(mesh, params)
    # jaxlint: disable=unknown-axis -- expected-value literal; tp is declared by parallel.mesh.AXES
    assert sh.params["wide"].spec == P(None, "tp")
    assert sh.params["bias"].spec == P()
    assert sh.obs.spec == P("dp")
    assert sh.out.spec == P("dp")
    fsdp = inference_shardings(mesh, params, fsdp=True)
    assert "dp" in tuple(fsdp.params["wide"].spec)
    one = make_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    sh1 = inference_shardings(one, params)
    assert all(s.is_fully_replicated
               for s in jax.tree.leaves(sh1.params))


def test_make_mesh_default_all_dp():
    _need_devices(8)
    mesh = make_mesh()
    assert mesh.shape["dp"] == len(jax.devices())
    assert mesh.shape["tp"] == 1


def test_param_sharding_tp_rule():
    _need_devices(8)
    mesh = make_mesh(MeshSpec(dp=4, tp=2), devices=jax.devices()[:8])
    params = {
        "dense": {"kernel": np.zeros((64, 256)), "bias": np.zeros((256,))},
        "conv": {"kernel": np.zeros((3, 3, 32, 128))},
        "head": {"kernel": np.zeros((32, 9))},
    }
    shardings = param_sharding(mesh, params)
    # wide kernels shard output features over tp.  (The expected-spec
    # literals name the tp axis make_mesh declares inside the package;
    # a tests-only lint scan cannot see that declaration.)
    # jaxlint: disable=unknown-axis -- expected-value literal; tp is declared by parallel.mesh.AXES
    assert shardings["dense"]["kernel"].spec == jax.sharding.PartitionSpec(None, "tp")
    conv_spec = shardings["conv"]["kernel"].spec
    # jaxlint: disable=unknown-axis -- expected-value literal; tp is declared by parallel.mesh.AXES
    assert conv_spec == jax.sharding.PartitionSpec(None, None, None, "tp")
    # biases and narrow heads replicate
    assert shardings["dense"]["bias"].spec == jax.sharding.PartitionSpec()
    assert shardings["head"]["kernel"].spec == jax.sharding.PartitionSpec()


def test_param_sharding_tp_boundaries():
    """The tp rule's edges: dim == min_tp_dim (128) is the smallest
    dim that shards; non-divisible dims and rank-1 params fall back to
    replication WITHOUT raising — an odd head size must degrade, not
    crash the learner at mesh build."""
    _need_devices(8)
    P = jax.sharding.PartitionSpec
    mesh = make_mesh(MeshSpec(dp=4, tp=2), devices=jax.devices()[:8])
    params = {
        "at_floor": np.zeros((64, 128)),     # == min_tp_dim: shards
        "below_floor": np.zeros((64, 126)),  # divisible but < 128
        "indivisible": np.zeros((64, 129)),  # 129 % 2 != 0
        "rank1": np.zeros((256,)),           # bias-like: replicates
        "scalar": np.zeros(()),              # rank-0: replicates
    }
    shardings = param_sharding(mesh, params)
    assert shardings["at_floor"].spec == P(None, "tp")
    assert shardings["below_floor"].spec == P()
    assert shardings["indivisible"].spec == P()
    assert shardings["rank1"].spec == P()
    assert shardings["scalar"].spec == P()
    # the shardings are actually placeable (no deferred errors)
    placed = jax.device_put(params, shardings)
    assert jax.tree.structure(placed) == jax.tree.structure(params)


def test_param_sharding_min_tp_dim_is_tunable():
    _need_devices(8)
    P = jax.sharding.PartitionSpec
    mesh = make_mesh(MeshSpec(dp=4, tp=2), devices=jax.devices()[:8])
    params = {"small": np.zeros((8, 32))}
    assert param_sharding(mesh, params)["small"].spec == P()
    lowered = param_sharding(mesh, params, min_tp_dim=32)
    assert lowered["small"].spec == P(None, "tp")


@pytest.mark.slow
def test_sharded_update_step_dp():
    """Full training step, batch sharded dp=4: compiles, runs, finite."""
    _need_devices(4)
    import sys, pathlib
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from __graft_entry__ import _build_model_and_batch

    from handyrl_tpu.ops.losses import LossConfig
    from handyrl_tpu.ops.update import make_optimizer

    mesh = make_mesh(MeshSpec(dp=4), devices=jax.devices()[:4])
    model, batch, cfg = _build_model_and_batch(batch_size=4)
    loss_cfg = LossConfig.from_config(cfg)
    optimizer = make_optimizer(1e-3)
    params, opt_state = model.params, None
    opt_state = optimizer.init(params)

    update = make_sharded_update_step(model, loss_cfg, optimizer, mesh, params)
    params2, opt_state, metrics = update(params, opt_state, batch)
    assert np.isfinite(float(metrics["total"]))
    # params changed and stayed replicated
    leaf = jax.tree.leaves(params2)[0]
    assert leaf.sharding.is_fully_replicated


@pytest.mark.slow
def test_sharded_update_step_dp_sp():
    """Sequence parallelism: batch sharded dp=2 AND time sharded sp=2.

    The update step contains a reverse time-scan (targets) and a time
    matmul stream (forward); sharding T over ``sp`` forces XLA to
    insert the cross-slice collectives — this must still compile, run,
    and agree numerically with the unsharded step."""
    _need_devices(4)
    import sys, pathlib
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from __graft_entry__ import _build_model_and_batch

    from handyrl_tpu.ops.losses import LossConfig
    from handyrl_tpu.ops.update import make_optimizer, make_update_step

    mesh = make_mesh(MeshSpec(dp=2, sp=2), devices=jax.devices()[:4])
    model, batch, cfg = _build_model_and_batch(batch_size=2)
    loss_cfg = LossConfig.from_config(cfg)

    optimizer = make_optimizer(1e-3)
    params_ref = jax.tree.map(jax.numpy.array, model.params)
    opt_ref = optimizer.init(params_ref)
    ref_step = make_update_step(model, loss_cfg, optimizer)
    params_ref, opt_ref, ref_metrics = ref_step(params_ref, opt_ref, batch)

    optimizer2 = make_optimizer(1e-3)
    params_sp = jax.tree.map(jax.numpy.array, model.params)
    opt_sp = optimizer2.init(params_sp)
    sp_step = make_sharded_update_step(
        model, loss_cfg, optimizer2, mesh, params_sp, shard_time=True)
    params_sp, opt_sp, sp_metrics = sp_step(params_sp, opt_sp, batch)

    # the sp-sharded step computes the same math
    assert float(sp_metrics["total"]) == pytest.approx(
        float(ref_metrics["total"]), rel=1e-4)
    ref_leaves = jax.tree.leaves(params_ref)
    sp_leaves = jax.tree.leaves(params_sp)
    for a, b in zip(ref_leaves, sp_leaves):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_sharded_update_step_bf16():
    """bf16 compute under a dp mesh: compiles, runs, finite metrics."""
    _need_devices(4)
    import sys, pathlib
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from __graft_entry__ import _build_model_and_batch

    from handyrl_tpu.ops.losses import LossConfig
    from handyrl_tpu.ops.update import make_optimizer

    mesh = make_mesh(MeshSpec(dp=4), devices=jax.devices()[:4])
    model, batch, cfg = _build_model_and_batch(batch_size=4)
    loss_cfg = LossConfig.from_config(cfg)
    optimizer = make_optimizer(1e-3)
    params = jax.tree.map(jax.numpy.array, model.params)
    opt_state = optimizer.init(params)

    update = make_sharded_update_step(
        model, loss_cfg, optimizer, mesh, params, compute_dtype="bfloat16")
    params, opt_state, metrics = update(params, opt_state, batch)
    assert np.isfinite(float(metrics["total"]))
    # master params stay float32 under bf16 compute
    assert all(l.dtype == np.float32 for l in jax.tree.leaves(params))


@pytest.mark.slow
def test_multichip_infer_dryrun_8():
    """The GSPMD inference dry run (scripts/multichip_infer_dryrun.py,
    the CI slow-job artifact): dp4xtp2+fsdp serves with tp-sharded
    leaves, dp legs bit-match the unsharded forward, snapshots never
    recompile, zero resharding copies."""
    _need_devices(8)
    import json
    import pathlib
    import subprocess
    import sys

    script = (pathlib.Path(__file__).resolve().parents[1]
              / "scripts" / "multichip_infer_dryrun.py")
    proc = subprocess.run([sys.executable, str(script)],
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    last = [line for line in proc.stdout.splitlines()
            if line.strip().startswith("{")][-1]
    rec = json.loads(last)
    assert rec["ok"] and rec["tp_sharded_leaves"] > 0
    assert rec["dp8_bitwise"] and rec["single_device_bitwise"]
    assert rec["infer_resharding_copies"] == 0


@pytest.mark.slow
def test_dryrun_multichip_8():
    _need_devices(8)
    import sys, pathlib
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from __graft_entry__ import dryrun_multichip

    dryrun_multichip(8)


def test_impact_target_params_shard_like_live_params():
    """``update_algorithm: impact`` threads the target net through the
    sharded step's trailing slot: target params must come back laid
    out EXACTLY like the live params (same pytree, same shardings),
    and the Adam moments must inherit the param layout structurally —
    under fsdp, where the layouts are actually non-trivial."""
    _need_devices(4)
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from __graft_entry__ import _build_model_and_batch

    from handyrl_tpu.ops.losses import LossConfig
    from handyrl_tpu.ops.update import make_optimizer

    mesh = make_mesh(MeshSpec(dp=4), devices=jax.devices()[:4])
    model, batch, cfg = _build_model_and_batch(
        batch_size=4, env_name="TicTacToe")
    cfg = dict(cfg, update_algorithm="impact",
               target_update_interval=16)
    loss_cfg = LossConfig.from_config(cfg)
    optimizer = make_optimizer(1e-3)
    params = jax.tree.map(jax.numpy.array, model.params)
    target = jax.tree.map(jax.numpy.array, model.params)
    opt_state = optimizer.init(params)

    step = make_sharded_update_step(
        model, loss_cfg, optimizer, mesh, params, fsdp=True)
    params, opt_state, metrics, target = step(
        params, opt_state, batch, target)
    assert np.isfinite(float(metrics["total"]))

    p_leaves = jax.tree.leaves(params)
    t_leaves = jax.tree.leaves(target)
    assert jax.tree.structure(params) == jax.tree.structure(target)
    for p, t in zip(p_leaves, t_leaves):
        assert p.sharding == t.sharding, (p.sharding, t.sharding)
    # fsdp engaged for real: some param AND its moment shard over dp,
    # and the target leaf at the same position carries the same spec
    def dp_sharded(tree):
        return [l for l in jax.tree.leaves(tree)
                if "dp" in tuple(l.sharding.spec)]
    assert dp_sharded(params), "fsdp never sharded a param"
    assert dp_sharded(target), "target missed the param layout"
    assert dp_sharded(opt_state), "Adam moments missed the layout"


def test_param_sharding_fsdp_rule():
    _need_devices(8)
    mesh = make_mesh(MeshSpec(dp=4, tp=2), devices=jax.devices()[:8])
    P = jax.sharding.PartitionSpec
    params = {
        "conv": {"kernel": np.zeros((3, 3, 64, 64)),   # big, no tp match
                 "bias": np.zeros((64,))},             # small: replicate
        "wide": {"kernel": np.zeros((64, 256))},       # tp takes last dim
    }
    shardings = param_sharding(mesh, params, fsdp=True)
    # fsdp shards the last free dim of large tensors over dp
    assert shardings["conv"]["kernel"].spec == P(None, None, None, "dp")
    # tp keeps the last dim; fsdp then takes the next free one
    assert shardings["wide"]["kernel"].spec == P("dp", "tp")
    # small tensors stay replicated (all-gather would cost more than it saves)
    assert shardings["conv"]["bias"].spec == P()
    assert MeshSpec.from_config({"dp": 4, "fsdp": True}).fsdp is True


@pytest.mark.slow
def test_fsdp_update_step_matches_replicated():
    """ZeRO sharding must not change the math: params + Adam moments
    shard over dp, and one update step agrees with the replicated run."""
    _need_devices(4)
    import sys, pathlib
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from __graft_entry__ import _build_model_and_batch

    from handyrl_tpu.ops.losses import LossConfig
    from handyrl_tpu.ops.update import make_optimizer, make_update_step

    mesh = make_mesh(MeshSpec(dp=4), devices=jax.devices()[:4])
    model, batch, cfg = _build_model_and_batch(batch_size=4)
    loss_cfg = LossConfig.from_config(cfg)

    optimizer = make_optimizer(1e-3)
    params_ref = jax.tree.map(jax.numpy.array, model.params)
    opt_ref = optimizer.init(params_ref)
    ref_step = make_update_step(model, loss_cfg, optimizer)
    params_ref, opt_ref, ref_metrics = ref_step(params_ref, opt_ref, batch)

    optimizer2 = make_optimizer(1e-3)
    params_z = jax.tree.map(jax.numpy.array, model.params)
    opt_z = optimizer2.init(params_z)
    z_step = make_sharded_update_step(
        model, loss_cfg, optimizer2, mesh, params_z, fsdp=True)
    params_z, opt_z, z_metrics = z_step(params_z, opt_z, batch)

    # at least one param leaf AND its Adam moment actually sharded
    def dp_sharded(tree):
        return [l for l in jax.tree.leaves(tree)
                if "dp" in tuple(l.sharding.spec)]
    assert dp_sharded(params_z), "no param sharded over dp"
    assert dp_sharded(opt_z), "no optimizer moment sharded over dp"

    assert float(z_metrics["total"]) == pytest.approx(
        float(ref_metrics["total"]), rel=1e-4)
    for a, b in zip(jax.tree.leaves(params_ref),
                    jax.tree.leaves(params_z)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_tp_actually_partitions_wide_net():
    """With a 128-filter GeeseNet, the tp rule must shard real conv
    kernels and the update step must run end to end on a dp x tp mesh
    (VERDICT r3: the bundled 32-filter nets never engaged tp)."""
    _need_devices(8)
    import sys, pathlib
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from __graft_entry__ import _build_model_and_batch

    from handyrl_tpu.models import TPUModel
    from handyrl_tpu.models.geese_net import GeeseNet
    from handyrl_tpu.ops.losses import LossConfig
    from handyrl_tpu.ops.update import make_optimizer

    mesh = make_mesh(MeshSpec(dp=4, tp=2), devices=jax.devices()[:8])
    _, batch, cfg = _build_model_and_batch(batch_size=4)
    wide = TPUModel(GeeseNet(filters=128, blocks=2))
    obs_leaf = jax.tree.leaves(batch["observation"])[0]
    wide.init_params(np.asarray(obs_leaf[0, 0, 0], np.float32), seed=0)

    shardings = param_sharding(mesh, wide.params)
    tp_kernels = [l for l in jax.tree.leaves(shardings)
                  if "tp" in tuple(l.spec)]
    assert tp_kernels, "128-filter net must engage the tp rule"

    loss_cfg = LossConfig.from_config(cfg)
    optimizer = make_optimizer(1e-3)
    params = wide.params
    opt_state = optimizer.init(params)
    update = make_sharded_update_step(
        wide, loss_cfg, optimizer, mesh, params)
    params, opt_state, metrics = update(params, opt_state, batch)
    assert np.isfinite(float(metrics["total"]))
    # a tp-sharded kernel went through the step still tp-sharded
    sharded_after = [l for l in jax.tree.leaves(params)
                     if "tp" in tuple(l.sharding.spec)]
    assert sharded_after, "tp sharding lost through the update step"


# -- OrderedLaunch: launches over a mesh ordered, compiles outside ----
class _FakeJit:
    """Stands for a jitted callable: records whether the launch lock was
    held at each lowering/compile and at each launch."""

    def __init__(self):
        self.compiled_unlocked = []
        self.launched_locked = []
        self.plain_calls = 0

    def __call__(self, *args):
        self.plain_calls += 1
        return "plain", args

    def lower(self, *args):
        from handyrl_tpu.parallel.mesh import _LAUNCH_LOCK

        fake, shapes = self, tuple(np.shape(a) for a in args)

        class _Lowered:
            def compile(self):
                fake.compiled_unlocked.append(not _LAUNCH_LOCK.locked())

                def executable(*call_args):
                    if tuple(np.shape(a) for a in call_args) != shapes:
                        raise TypeError("compiled for other shapes")
                    fake.launched_locked.append(_LAUNCH_LOCK.locked())
                    return shapes
                return executable
        return _Lowered()


def test_ordered_launch_without_a_mesh_is_the_callable_itself():
    from handyrl_tpu.parallel.mesh import OrderedLaunch

    fake = _FakeJit()
    step = OrderedLaunch(fake, None)
    assert step(np.zeros(3))[0] == "plain"
    assert fake.plain_calls == 1 and fake.compiled_unlocked == []
    assert step.lower is not None        # the jit's own attributes show


def test_ordered_launch_compiles_outside_the_lock_and_launches_inside():
    from handyrl_tpu.parallel.mesh import OrderedLaunch

    fake = _FakeJit()
    step = OrderedLaunch(fake, mesh=object())
    for _ in range(3):
        assert step(np.zeros(3)) == ((3,),)
    assert fake.compiled_unlocked == [True]          # once, lock free
    assert fake.launched_locked == [True] * 3
    # a ring growth: the executable refuses, a new one is compiled
    # outside the lock and replaces it
    assert step(np.zeros(5)) == ((5,),)
    assert step(np.zeros(5)) == ((5,),)
    assert fake.compiled_unlocked == [True, True]
    assert fake.plain_calls == 0


def test_ordered_launch_keeps_one_executable_per_key():
    from handyrl_tpu.parallel.mesh import OrderedLaunch

    fake = _FakeJit()
    step = OrderedLaunch(fake, mesh=object(),
                         key=lambda args: np.shape(args[0]))
    for rows in (8, 32, 8, 32, 8):      # the service's batch buckets
        assert step(np.zeros(rows)) == ((rows,),)
    assert len(fake.compiled_unlocked) == 2


def test_ordered_launch_on_a_real_mesh_donates_and_regrows():
    _need_devices(4)
    from jax.sharding import NamedSharding, PartitionSpec as P

    from handyrl_tpu.parallel.mesh import OrderedLaunch

    mesh = make_mesh(MeshSpec.from_config({"dp": 4}))
    rep = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("dp"))

    def fn(acc, x):
        return acc + x.sum(), x * 2

    step = OrderedLaunch(
        jax.jit(fn, in_shardings=(rep, rows), out_shardings=(rep, rows),
                donate_argnums=(0,)), mesh)
    acc = jax.device_put(np.float32(0), rep)
    for n in (8, 8, 16):                 # the third is a new shape
        acc, doubled = step(acc, np.ones(n, np.float32))
        assert doubled.sharding.is_equivalent_to(rows, 1)
    assert float(acc) == 32.0
    # an argument committed to another layout is refused, as jit does
    elsewhere = jax.device_put(np.ones(16, np.float32), rep)
    with pytest.raises(ValueError):
        step(acc, elsewhere)
