"""Multichip GSPMD inference dry run (the training-plane dry run's
pattern, ``__graft_entry__.dryrun_multichip``, on the INFERENCE plane).

Eight fake CPU devices host the dp4 x tp2 (+ fsdp) meshes and the
batched ``inference_batch`` dispatch runs as one GSPMD program through
the real :class:`pipeline.InferenceService` forward:

  * dp4 x tp2 + fsdp on a 128-filter GeeseNet: tp-sharded param
    leaves must actually EXIST (the bundled 32-filter nets never
    engage the tp rule — VERDICT r3), and the sharded output must
    match the unsharded forward within float32 epsilon (a partitioned
    contraction reassociates ONE reduction; the measured max diff
    rides the JSON artifact);
  * dp8 and dp8 + fsdp: bit-EXACT against the unsharded forward
    (np.array_equal — data-parallel row sharding and ZeRO-style
    weight sharding change no reduction order at equal row counts);
  * a single-device mesh: bit-identical to the mesh-less dispatch
    (the tentpole's compatibility floor);
  * hot-swap + multi-model routing: a second snapshot and a routed
    (resolver-served) snapshot both dispatch through the SAME
    compiled forward — exactly one inference compile per batch-bucket
    geometry, zero resharding copies (params are device_put onto the
    param shardings once per snapshot, never per request);
  * one request is driven through the real ``submit`` -> ``step`` ->
    ``deliver`` window (the serving tier's network plane), proving
    the SLO admission path never touches the mesh — admission is
    counter arithmetic; only the dispatch runs sharded.

Output discipline: progress lines to stdout, ONE pure-JSON line last
(CI does `tail -1 > multichip_infer_dryrun.json`, like the bench
variants).  Exit code 0 = every assertion held.
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402  (import after env setup on purpose)

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from handyrl_tpu.environment import make_env  # noqa: E402
from handyrl_tpu.models import TPUModel  # noqa: E402
from handyrl_tpu.models.geese_net import GeeseNet  # noqa: E402
from handyrl_tpu.parallel import MeshSpec, make_mesh  # noqa: E402
from handyrl_tpu.pipeline import (  # noqa: E402
    InferenceService,
    PipelineConfig,
)

# one reassociated reduction per tp-partitioned contraction: measured
# 3e-6..6e-6 on this CPU stack run-to-run (partitioner/thread-count
# dependent); the bound keeps float32-epsilon scale with headroom
TP_ATOL = 5e-5


class _Seat:
    """Network-plane seat duck (the frontend's _NetSeat shape):
    captures the delivered reply so the window can be driven
    synchronously."""

    def __init__(self, example):
        self.cid = 0
        self.example = example
        self.treedef = None
        self.drop_warned = False
        self.delivered = None

    def deliver(self, seq, epoch, outputs):
        self.delivered = (seq, epoch, outputs)
        return True


def _max_diff(out, ref):
    return max(
        float(np.max(np.abs(np.asarray(out[k]) - np.asarray(ref[k]))))
        for k in ref if ref[k] is not None)


def _bit_equal(out, ref):
    return all(
        np.array_equal(np.asarray(out[k]), np.asarray(ref[k]))
        for k in ref if ref[k] is not None)


def main():
    n_dev = len(jax.devices())
    assert n_dev >= 8, f"need 8 virtual devices, have {n_dev}"

    env = make_env({"env": "HungryGeese"})
    env.reset()
    model = TPUModel(GeeseNet(filters=128, blocks=2))
    obs0 = np.asarray(env.observation(env.players()[0]), np.float32)
    model.init_params(obs0, seed=0)
    rng = np.random.RandomState(7)
    obs = np.stack([obs0] * 16) \
        + rng.rand(16, *obs0.shape).astype(np.float32) * 0.2
    ref = model.inference_batch(obs, None)
    pcfg = PipelineConfig.from_config({"mode": "on", "batch_window": 0.0})

    out = {"metric": "multichip_infer_dryrun", "devices": n_dev}

    # -- leg 1: dp4 x tp2 + fsdp — the headline geometry --------------
    mesh = make_mesh(MeshSpec(dp=4, tp=2), devices=jax.devices()[:8])
    svc = InferenceService(model, pcfg, epoch=1, mesh=mesh, fsdp=True)
    got = svc._forward(model, obs)
    sh = svc._infer_sh
    tp_leaves = sum("tp" in tuple(s.spec)
                    for s in jax.tree.leaves(sh.params))
    fsdp_leaves = sum("dp" in tuple(s.spec)
                      for s in jax.tree.leaves(sh.params))
    assert tp_leaves > 0, "tp rule never sharded a param leaf"
    assert fsdp_leaves > 0, "fsdp rule never sharded a param leaf"
    diff = _max_diff(got, ref)
    assert diff <= TP_ATOL, (
        f"dp4xtp2 dispatch drifted {diff} > {TP_ATOL} from the "
        f"unsharded forward")
    placed = jax.tree.leaves(model._infer_placed[1])
    assert any(not l.sharding.is_fully_replicated for l in placed), \
        "no placed param leaf is actually distributed"
    out["tp_sharded_leaves"] = tp_leaves
    out["fsdp_sharded_leaves"] = fsdp_leaves
    out["dp4tp2_fsdp_max_diff"] = diff
    print(f"dp4xtp2+fsdp: {tp_leaves} tp-sharded / {fsdp_leaves} "
          f"fsdp-sharded leaves, max diff {diff:.2e} OK")

    # -- hot-swap + routing through the SAME compiled forward ---------
    compiles_before = svc.retrace_guard.compiles
    snap2 = TPUModel(model.module,
                     jax.tree.map(lambda a: np.asarray(a) * 1.0,
                                  model.params))
    svc.set_model(snap2, 2)
    svc._adopt_model()
    got2 = svc._forward(snap2, obs)
    assert _max_diff(got2, ref) <= TP_ATOL
    routed = TPUModel(model.module,
                      jax.tree.map(lambda a: np.asarray(a) * 0.5,
                                   model.params))
    svc.model_resolver = lambda epoch: routed
    rmodel, repoch = svc._routed(1)
    assert rmodel is routed and repoch == 1
    svc._forward(rmodel, obs)
    assert hasattr(routed, "_infer_placed"), \
        "routed snapshot was not placed onto the param shardings"
    assert svc.retrace_guard.compiles == compiles_before, (
        f"snapshot swap/routing recompiled: "
        f"{svc.retrace_guard.compiles} != {compiles_before} — one "
        f"compile per GEOMETRY, snapshots are arguments")
    assert svc.shard_guard.copies == 0, (
        f"{svc.shard_guard.copies} resharding copies — a snapshot "
        f"landed on the wrong layout")
    out["infer_compiles"] = svc.retrace_guard.compiles
    out["infer_resharding_copies"] = svc.shard_guard.copies
    print(f"hot-swap + routed snapshot: {compiles_before} compile(s) "
          f"per geometry, 0 resharding copies OK")

    # -- the real batching window (submit -> step -> deliver) ---------
    seat = _Seat(obs0)
    assert svc.submit(seat, 1, 16, [obs], epoch=None)
    assert svc.step(), "the window never dispatched"
    assert seat.delivered is not None, "no reply delivered"
    _seq, epoch, outputs = seat.delivered
    assert epoch == 2  # the adopted hot-swap snapshot answered
    assert outputs["policy"].shape[0] == 16
    out["window_dispatches"] = int(svc.batches)
    print("submit->step->deliver window dispatch OK (network plane "
          "rides the sharded forward; admission never touches the "
          "mesh)")
    svc.close()

    # -- leg 2: dp8 and dp8 + fsdp are bit-EXACT ----------------------
    for fsdp in (False, True):
        mesh = make_mesh(MeshSpec(dp=8), devices=jax.devices()[:8])
        svc = InferenceService(model, pcfg, epoch=1, mesh=mesh,
                               fsdp=fsdp)
        got = svc._forward(model, obs)
        assert _bit_equal(got, ref), (
            f"dp8{'+fsdp' if fsdp else ''} dispatch is not bitwise "
            f"identical to the unsharded forward "
            f"(max diff {_max_diff(got, ref)})")
        svc.close()
    out["dp8_bitwise"] = True
    out["dp8_fsdp_bitwise"] = True
    print("dp8 / dp8+fsdp: sharded inference bit-matches the "
          "unsharded forward OK")

    # -- leg 3: single-device mesh == today's behavior, bitwise -------
    one = make_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    svc = InferenceService(model, pcfg, epoch=1, mesh=one)
    got = svc._forward(model, obs)
    assert _bit_equal(got, ref), "single-device mesh is not bit-identical"
    svc.close()
    out["single_device_bitwise"] = True
    print("single-device mesh: bit-identical OK")

    out["ok"] = True
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
