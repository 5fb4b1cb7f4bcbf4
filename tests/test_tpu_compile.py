"""Ask the TPU's own compiler, with no chip attached.

The compiler for the v5e is installed wherever jax[tpu] is; it compiles
for a chip that is DESCRIBED (``get_topology_desc``), not attached.
Nothing runs, so these say nothing about results or times — they say
whether the main path's programs are accepted at the flagship shapes
(HungryGeese / GeeseNet 32f x 12, batch 256 x 8 steps, bf16 compute,
uint8 wire, the ring at the capacity the learner picks under the
default ``device_replay_mb``; the fused step at Geister's recurrent
geometry and at the three sequence nets' published widths too), whether they fit the chip's 16 GB, whether the ring's
own byte estimate matches what the compiler lays out — the estimate
sizes the ring, and tile padding is exactly what it exists to get
right (staging.py docstring) — and whether the step's gather reads the
ring in place.

Also here, on the CPU: a run whose trainer thread died must not end
green, and the persistent compile cache must stay in one place.
"""

import json
import os
import pathlib
import re
import subprocess
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp

import numpy as np  # noqa: E402
import pytest  # noqa: E402

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

BATCH = 256
HBM_BYTES = 16 * 2**30          # one v5e chip
RING_MB = 4096                  # config.py: device_replay_mb default
MAX_EPISODES = 20000            # runs/hungry_geese/config.yaml
# GB a layer under the attention scopes outside products and kernels
ATTENTION_GB = {"grouped_query": 1.65, "latent": 2.47}


@pytest.fixture(scope="module")
def v5e():
    """The four described devices of a v5e 2x2 host."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:   # no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e topology here: {exc!r}")
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without the chip: the next one
    # would warn and compile again, so the cache is off around these
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _planned(env_name, train, batch, steps_hint):
    """One configuration's pieces as the learner builds them, with the
    ring PLANNED at full capacity and nothing allocated."""
    import random

    import jax

    from handyrl_tpu.environment import make_env
    from handyrl_tpu.generation import Generator
    from handyrl_tpu.models import RandomModel, TPUModel
    from handyrl_tpu.ops.losses import LossConfig
    from handyrl_tpu.ops.update import DEFAULT_LR, make_optimizer
    from handyrl_tpu.staging import DeviceReplay, _decompress_episode

    cfg = {"gamma": 0.8, "burn_in_steps": 0, "compress_steps": 4,
           "entropy_regularization": 0.1,
           "entropy_regularization_decay": 0.1, "lambda": 0.7,
           "compute_dtype": "bfloat16", **train}
    random.seed(0)
    env = make_env({"env": env_name})
    env.reset()
    players = env.players()
    model = TPUModel(env.net())
    obs0 = env.observation(players[0])
    model.init_params(obs0, seed=0)
    rollout = RandomModel(model, obs0)
    job = {"player": players, "model_id": {p: 1 for p in players}}
    episode = None
    while episode is None:
        episode = Generator(env, cfg).generate(
            {p: rollout for p in players}, job)
    col = _decompress_episode(episode)
    replay = DeviceReplay(cfg, MAX_EPISODES, RING_MB << 20,
                          max_steps_hint=steps_hint)
    buffers = replay._plan_buffers(col)
    optimizer = make_optimizer(
        DEFAULT_LR * batch * cfg["forward_steps"])
    params = jax.eval_shape(lambda: model.params)
    return {
        "model": model, "col": col, "replay": replay,
        "buffers": buffers, "optimizer": optimizer, "params": params,
        "opt_state": jax.eval_shape(optimizer.init, params),
        "loss_cfg": LossConfig.from_config(cfg), "batch": batch,
        "estimate": replay.capacity * replay._per_slot_bytes(col),
    }


@pytest.fixture(scope="module")
def flagship():
    """``geese32``: HungryGeese / GeeseNet, simultaneous seats (the
    ring's ``seat`` mode), uint8 wire, batch 256 x 8.  Temporaries
    read 239 MB of a 4,294 MB estimate; with the masks stored as three
    one-byte channels they read 370 MB (three whole-ring copies)."""
    from handyrl_tpu.envs.kaggle.hungry_geese import EPISODE_STEPS

    return dict(_planned(
        "HungryGeese",
        {"turn_based_training": False, "observation": False,
         "forward_steps": 8, "transfer_dtype": "uint8",
         "policy_target": "UPGO", "value_target": "TD"},
        BATCH, EPISODE_STEPS), temp_share=0.07)


@pytest.fixture(scope="module")
def geister():
    """``geister_drc``: Geister / GeisterNet's DRC, turn-based with
    observation (the ring's ``all`` mode), burn-in 4, 214 actions (the
    masks pack into 14 words a row), bf16 wire, batch 128 x 12.  Its
    temporaries are the recurrent net's activations over the 8 trained
    steps: 593 MB (889 MB while the burn-in steps kept theirs too)."""
    return dict(_planned(
        "Geister",
        {"turn_based_training": True, "observation": True,
         "forward_steps": 8, "burn_in_steps": 4,
         "transfer_dtype": "bfloat16",
         "policy_target": "TD", "value_target": "TD"},
        128, 202), temp_share=0.25)


def _sequence_fixture(preset, steps, batch, slots):
    """A sequence preset's fused step, by shapes alone: nothing is
    played and no weight is made."""
    import jax

    from handyrl_tpu.environment import make_env
    from handyrl_tpu.models import TPUModel
    from handyrl_tpu.ops.losses import LossConfig
    from handyrl_tpu.ops.update import DEFAULT_LR, make_optimizer
    from handyrl_tpu.staging import DeviceReplay

    cfg = {"turn_based_training": False, "observation": True,
           "forward_steps": steps, "burn_in_steps": 0, "gamma": 1.0,
           "lambda": 0.95, "policy_target": "TD", "value_target": "TD",
           "entropy_regularization": 0.01,
           "entropy_regularization_decay": 0.1,
           "compute_dtype": "bfloat16"}
    env = make_env({"env": "TokenTask", "net": preset})
    model = TPUModel(env.net())
    params = jax.eval_shape(
        lambda: model.module.init(
            jax.random.PRNGKey(0), np.zeros((1,), np.int32),
            model.init_hidden([1]))["params"])
    replay = DeviceReplay(cfg, slots, 512 << 20)
    replay.t_max = steps
    col = {"players": [0], "obs": np.zeros((steps, 1), np.int32),
           "amask": np.zeros((steps, 1, 0), np.float32)}
    optimizer = make_optimizer(DEFAULT_LR * batch * steps)
    return {"model": model, "replay": replay, "params": params,
            "buffers": replay._plan_buffers(col), "optimizer": optimizer,
            "opt_state": jax.eval_shape(optimizer.init, params),
            "loss_cfg": LossConfig.from_config(cfg), "batch": batch}


@pytest.fixture(scope="module")
def sequence():
    """``trinity_mini_ep8``: the sparse-expert sequence net at published
    widths, one chip's share of eight; windows of 4,096 tokens, batch 2,
    every action legal (no mask in the ring's row, the token riding the
    packed channel), the ring at the configuration's 1,024 slots."""
    return _sequence_fixture("trinity_mini_ep8", 4096, 2, 1024)


@pytest.fixture(scope="module")
def latent_sequence():
    """``joyai_flash_ep16``: the latent-attention sequence net with its
    next-next-token module at published widths, one chip's share of
    sixteen; ONE window of 8,192 tokens a step, the ring at the
    configuration's 512 slots."""
    return _sequence_fixture("joyai_flash_ep16", 8192, 1, 512)


@pytest.fixture(scope="module")
def hybrid_sequence():
    """``olmo_hybrid_tp2``: the delta-rule hybrid at published widths,
    one chip's share of two; ONE window of 4,096 tokens a step, the
    ring at the configuration's 1,024 slots."""
    return _sequence_fixture("olmo_hybrid_tp2", 4096, 1, 1024)


def _on(tree, sharding):
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=sharding), tree)


def _footprint(mem):
    """Bytes one program holds on its device while it runs."""
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)


_DEFINED = re.compile(
    r"\s*(?:ROOT )?%?([\w.\-]+) = \w+\[(\d+)[,\]]\S* ([\w\-]+)\(")


def _lower_replay_step(v5e, f):
    """The device-replay fused step (draw + gather + update), lowered
    for the first described chip by shapes alone."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from handyrl_tpu.staging import epoch_sums, make_replay_update_step

    chip = SingleDeviceSharding(v5e[0])
    replay = f["replay"]
    step = make_replay_update_step(
        replay, f["model"], f["loss_cfg"], f["optimizer"],
        "bfloat16", batch_size=f["batch"])
    return step.lower(
        *_on((f["params"], f["opt_state"], f["buffers"],
              (jax.ShapeDtypeStruct((3,), jnp.int32), epoch_sums(replay))),
             chip))


def _compile_replay_step(v5e, f):
    """The device-replay fused step: its footprint and how it reads
    the ring."""
    replay = f["replay"]
    compiled = _lower_replay_step(v5e, f).compile()
    mem = compiled.memory_analysis()
    # the ring is all but ~6 MB (params + Adam moments) of the arguments
    assert abs(mem.argument_size_in_bytes - f["estimate"]) \
        <= 0.03 * f["estimate"], (mem.argument_size_in_bytes, f["estimate"])
    assert _footprint(mem) < HBM_BYTES
    # the gather reads the ring IN PLACE: temporaries stay a step's
    # worth.  (Stored at its logical width the observation buffer was
    # re-laid whole inside every step — temp ~= the ring itself; see
    # staging._stored_width.  Stored as one-byte elements the narrow
    # mask channels were: staging._pack_steps)
    assert mem.temp_size_in_bytes < f["temp_share"] * f["estimate"], (
        mem.temp_size_in_bytes, f["estimate"])
    # ... and no instruction of the step makes an array as long as the
    # ring, but for the compiler's one asynchronous prefetch across
    # calls (copy-start / copy-done) of a channel that fits its fast
    # memory whole
    rows = replay.capacity * replay.t_max + replay.run_round
    ring_long = {}
    for line in compiled.as_text().splitlines():
        m = _DEFINED.match(line)
        if m and int(m.group(2)) == rows and m.group(3) not in (
                "parameter", "copy-done"):
            ring_long[m.group(1)] = line.strip()[:160]
    assert not ring_long, ring_long


def _compile_ring_append(v5e, f):
    """One full ingest batch (8 episodes) scattered into the ring: the
    output IS the ring as the compiler lays it out — held against the
    ring's own estimate, which sized it."""
    import jax
    from jax.sharding import SingleDeviceSharding

    replay = f["replay"]
    replay._build_jits()
    append = replay._append_fn
    replay.buffers = f["buffers"]
    replay.ep_len = np.zeros(replay.capacity, np.int32)
    seen = {}
    # _append_run builds the host-side run; catch what it would upload
    replay._append_fn = lambda buffers, *run: (seen.update(run=run)
                                               or buffers)
    try:
        replay._append_run([f["col"]] * replay.max_run)
    finally:
        replay._append_fn = append
    chip = SingleDeviceSharding(v5e[0])
    run = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), seen["run"])
    compiled = append.lower(*_on((f["buffers"],) + run, chip)).compile()
    mem = compiled.memory_analysis()
    ring = mem.output_size_in_bytes
    assert abs(ring - f["estimate"]) <= 0.03 * f["estimate"], (
        ring, f["estimate"])
    assert ring <= (RING_MB << 20) * 1.03
    # donated: the scatter updates the ring in place
    assert mem.alias_size_in_bytes >= 0.999 * ring
    assert _footprint(mem) < HBM_BYTES


def _compile_service_forward(v5e, f):
    """The inference service's own jitted forward at its largest batch
    bucket (pipeline.max_batch rows)."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from handyrl_tpu.pipeline import InferenceService, PipelineConfig

    cfg = PipelineConfig()
    service = InferenceService(f["model"], cfg)
    try:
        forward = service._ensure_forward(f["model"])
        obs = f["col"]["obs"][0, 0]          # one seat's planes
        chip = SingleDeviceSharding(v5e[0])
        compiled = forward.lower(*_on(
            (f["params"], jax.ShapeDtypeStruct(
                (cfg.max_batch,) + obs.shape, obs.dtype)), chip)).compile()
    finally:
        service.close()
    assert _footprint(compiled.memory_analysis()) < HBM_BYTES


def _compile_dp4_step(v5e, f):
    """The dp=4 sharded update step over the four described chips:
    gradients all-reduce, and each chip is handed a quarter of the
    batch beside its replica of the state."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from handyrl_tpu.parallel import (
        MeshSpec, batch_sharding, make_mesh, make_sharded_update_step,
        replicated)

    mesh = make_mesh(MeshSpec(dp=4), devices=v5e)
    rows = jax.ShapeDtypeStruct((BATCH,), jnp.int32)
    batch = jax.eval_shape(
        f["replay"]._gather_batch, f["buffers"], rows, rows, rows)
    state = (f["params"], f["opt_state"])
    step = make_sharded_update_step(
        f["model"], f["loss_cfg"], f["optimizer"], mesh, f["params"],
        compute_dtype="bfloat16")
    compiled = step.lower(
        *_on(state, replicated(mesh)),
        _on(batch, batch_sharding(mesh))).compile()
    assert "all-reduce" in compiled.as_text()
    # each chip is handed a quarter of every batch leaf's rows ...
    batch_in = compiled.input_shardings[0][2]
    for leaf, sharding in zip(jax.tree.leaves(batch),
                              jax.tree.leaves(batch_in)):
        assert sharding.shard_shape(leaf.shape)[0] == BATCH // 4, leaf
    # ... so its arguments weigh well under the whole batch beside its
    # replica of the state (not a clean quarter: the compiler pads the
    # smaller shards' tiles)
    chip = SingleDeviceSharding(v5e[0])

    def arg_bytes(*trees):
        # (every leaf is read: jit drops an argument nothing uses)
        return jax.jit(lambda *t: jax.tree.map(lambda a: a.ravel()[0], t)
                       ).lower(*_on(trees, chip)).compile(
            ).memory_analysis().argument_size_in_bytes

    state_bytes = arg_bytes(*state)
    batch_bytes = arg_bytes(*state, batch) - state_bytes
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    assert per_chip - state_bytes <= 0.6 * batch_bytes, (
        per_chip, state_bytes, batch_bytes)
    assert _footprint(compiled.memory_analysis()) < HBM_BYTES


def _fused_kernels(text, kernel="splash_mqa"):
    """The step's fused attention kernels (or, by name, the passes that
    hand them their operands), counted by (net scope, phase, kernel):
    the reducer finds each under its layer's scope."""
    from handyrl_tpu.telemetry import devtrace

    kernels = {}
    for name, op_name in devtrace.op_names(text).items():
        if name.startswith(kernel) and op_name.endswith(
                devtrace.KERNEL_SUFFIX):
            key = (devtrace.net_scope_of(op_name), devtrace.phase_of(op_name),
                   name.split(".")[0])
            kernels[key] = kernels.get(key, 0) + 1
    return kernels


def _grouped_kernels(text):
    """The step's grouped products (the held experts' kernels), counted
    by (net scope, phase, kernel)."""
    from handyrl_tpu.telemetry import devtrace

    kernels = {}
    for name, op_name in devtrace.op_names(text).items():
        kernel = name.split(".")[0]
        if kernel in ("gmm", "tgmm") and op_name.endswith(
                devtrace.KERNEL_SUFFIX):
            key = (devtrace.net_scope_of(op_name),
                   devtrace.phase_of(op_name), kernel)
            kernels[key] = kernels.get(key, 0) + 1
    return kernels


_PRODUCT = re.compile(
    r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+\[[\d,]*\])\S* (?:convolution|dot)\(")


def _products(text):
    """The step's products (``convolution`` / ``dot``, inside a fusion
    or not), counted by ``(net scope, phase)`` (backward: the layer's
    rematerialisation and the way back) and the shape of the result."""
    from handyrl_tpu.telemetry import devtrace

    op_names = devtrace.op_names(text)
    products = {}
    for found in filter(None, map(_PRODUCT.match, text.splitlines())):
        op_name = op_names.get(found.group(1), "")
        shapes = products.setdefault(
            (devtrace.net_scope_of(op_name), devtrace.phase_of(op_name)), {})
        shapes[found.group(2)] = shapes.get(found.group(2), 0) + 1
    return products


def _held_stacks(text, positions, held, width):
    """Arrays of the dense held stack's hidden, ``(positions, held,
    expert width)`` in any dtype, that the step defines."""
    return re.findall(rf"= (\w+\[{positions},{held},{width}\])", text)


_SHAPE = re.compile(r"\b(pred|bf16|[suf](\d+))\[([\d,]*)\]")
_INSTRUCTION = re.compile(
    r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\((.*?)\)(?:, |$)")
_FREE = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")


def _arrays(shapes):
    """``(dtype, elements, bytes)`` of every array a shape text names."""
    found = []
    for dtype, bits, dims in _SHAPE.findall(shapes):
        elements = int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
        size = {"pred": 1, "bf16": 2}.get(dtype) or int(bits) // 8
        found.append((dtype, elements, elements * size))
    return found


def _outside_products_and_kernels(text, scopes):
    """The step's top-level instructions under the net scopes ``scopes``
    that are neither products nor kernels: ``{name: (operands' +
    results' bytes, result's shape text)}``, a count of what they move
    through HBM (not a time).  A product is a ``convolution`` / ``dot``
    or a fusion around one; a kernel a custom call; the closing half of
    an asynchronous pair counts nothing beside its opening half."""
    from handyrl_tpu.telemetry import devtrace

    products, body, inside = set(), [], None
    for line in text.splitlines():
        if line.startswith("ENTRY "):
            inside = "ENTRY"
        elif line.endswith("{") and not line.startswith(" "):
            inside = line.split()[0].lstrip("%")
        elif line.rstrip() == "}":
            inside = None
        elif inside == "ENTRY":
            body.append(line)
        elif inside and re.search(r" (convolution|dot)\(", line):
            products.add(inside)
    found = [m.groups() for m in map(_INSTRUCTION.match, body) if m]
    result = {name: shapes for name, shapes, _, _ in found}
    op_names = devtrace.op_names(text)
    outside = {}
    for (name, shapes, opcode, operands), line in zip(
            found, (l for l in body if _INSTRUCTION.match(l))):
        called = re.search(r"calls=%?([\w.\-]+)", line)
        if (devtrace.net_scope_of(op_names.get(name, "")) not in scopes
                or opcode in _FREE + ("custom-call", "convolution", "dot")
                or opcode.endswith("-done")
                or called and called.group(1) in products):
            continue
        moved = sum(size for _, _, size in _arrays(shapes)) + sum(
            size for operand in re.findall(r"%([\w.\-]+)", operands)
            for _, _, size in _arrays(result.get(operand, "")))
        outside[name] = (moved, shapes)
    return outside


def _attention_passes(text, scopes, layers, ceiling_gb, float32_elements):
    """Between a projection and the attention kernel an operand goes
    through HBM once, in the compute dtype: the bytes a layer moves
    under the attention scopes outside products and kernels stay under
    ``ceiling_gb`` (10% over what PR 41 read, 2.25 GB a latent layer
    and 1.50 a grouped-query layer; its parent read 7.74 and 6.04, most
    of it float32 passes of the rotation and the norm and re-layouts
    between them), and no such instruction writes a float32 array of
    ``float32_elements`` or more (the smallest the parent wrote so: a
    latent layer's rotary part of q, a grouped-query layer's q)."""
    outside = _outside_products_and_kernels(text, scopes)
    a_layer = sum(moved for moved, _ in outside.values()) / layers / 1e9
    assert a_layer < ceiling_gb, (a_layer, sorted(
        outside.items(), key=lambda item: -item[1][0])[:8])
    wide = {name: shapes for name, (_, shapes) in outside.items()
            if any(dtype == "f32" and elements >= float32_elements
                   for dtype, elements, _ in _arrays(shapes))}
    assert not wide, wide
    return a_layer


def _walked_targets(text):
    """The step's ``while`` loops under ``loss.targets``: the value
    targets' recursion walked one moment at a time (ops/targets.py)."""
    from handyrl_tpu.telemetry import devtrace

    return [name for name, op_name in devtrace.op_names(text).items()
            if devtrace.phase_of(op_name) == "targets"
            and op_name.endswith("/while")]


def _compile_sequence_step(v5e, f):
    """The fused step over whole 4,096-token windows: 16 B a parameter
    of train state beside the step's temporaries must fit the chip, the
    policy head's logits must never exist whole, and attention runs as
    the fused kernel in every layer: no float32 block of scores is
    defined anywhere in the step."""
    import jax

    replay = f["replay"]
    assert f["buffers"]["obs"] is None          # the token rides `steps`
    assert f["buffers"]["steps"].shape == (1024 * 4096 + 4096, 8)
    assert (replay.run_round, replay.max_run) == (4096, 4)
    compiled = _lower_replay_step(v5e, f).compile()
    mem = compiled.memory_analysis()
    n_params = sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree.leaves(f["params"]))
    assert 700e6 < n_params < 710e6
    # parameters and Adam's moments in float32, the ring beside them
    assert mem.argument_size_in_bytes >= 12 * n_params
    # 14.12 GB (arguments 8.6, temporaries 5.5) with the held experts
    # as grouped products over buffers of the worst case's 65,536 rows
    # and a SwiGLU's three products kept across a layer's
    # rematerialisation (13.65 GB with all three made again)
    assert _footprint(mem) < 14.3e9, _footprint(mem)
    text = compiled.as_text()
    assert not _walked_targets(text)
    positions, vocab = 2 * 4096, 25024
    whole = [m.group(1) for m in map(_DEFINED.match, text.splitlines())
             if m and int(m.group(2)) == positions
             and f",{vocab}]" in m.group(0)]
    assert not whole, whole
    # five layers (four window, one full), each a forward kernel going
    # forward (its output kept: the layer's rematerialisation runs none
    # again) and ONE backward kernel
    kernels = _fused_kernels(text)
    assert kernels == {
        (scope, phase, "splash_mqa_" + kind): layers
        for scope, layers in (("net.attention.window", 4),
                              ("net.attention.full", 1))
        for phase, kind in (("forward", "fwd_residuals"),
                            ("backward", "dkv_no_residuals"))}, kernels
    # four expert layers, each three grouped products going forward,
    # the same three in the layer's rematerialisation, and coming back
    # three more and the three kernels' gradients; the dense held
    # stack's hidden is defined nowhere
    assert _grouped_kernels(text) == {
        ("net.moe.experts", "forward", "gmm"): 12,
        ("net.moe.experts", "backward", "gmm"): 24,
        ("net.moe.experts", "backward", "tgmm"): 12}, _grouped_kernels(text)
    assert not _held_stacks(text, positions, 16, 1024)
    # the dense layer's and the four shared experts' SwiGLU keep their
    # three products (``KEPT_NAMES``): coming back each is x's and the
    # hidden's gradients and nothing made again (3 and 12 of each
    # shape where the layer's rematerialisation made them again)
    products = _products(text)
    assert products["net.mlp", "forward"] == {
        "bf16[2,4096,6144]": 2, "bf16[2,4096,2048]": 1}
    assert products["net.mlp", "backward"] == {
        "bf16[2,4096,6144]": 1, "bf16[2,4096,2048]": 2,
        "bf16[2048,6144,1]": 2, "bf16[6144,2048,1]": 1}
    shared = products["net.moe.shared", "backward"]
    assert (shared["bf16[8192,1024]"], shared["bf16[8192,2048]"]) == (4, 8)
    # q and k each take norm and rotation in ONE pass of a kernel on the
    # way to the attention: going forward, in the layer's
    # rematerialisation, and transposed coming back
    assert _fused_kernels(text, "turn_pass") == {
        (scope, phase, "turn_pass"): 2 * layers * passes
        for scope, layers in (("net.attention.window", 4),
                              ("net.attention.full", 1))
        for phase, passes in (("forward", 1), ("backward", 2))}
    assert text.count('custom_call_target="tpu_custom_call"') == 10 + 48 + 30
    # batch 2, 4 key-value heads of 8 query heads: a block of scores
    # was f32[2,4,8,512,Tk], queries by keys (a log-sum-exp is one a
    # query, written 128 lanes wide)
    scores = [found for found in re.findall(
        r"= (f32\[2,4,8,(\d+),(\d+)\])", text)
        if int(found[1]) >= 512 and int(found[2]) >= 512]
    assert not scores, scores[:4]
    # q and k go from projection to kernel in one pass each (norm and
    # rotation inside it); what is left outside: dq's partial sums, the
    # log-sum-exp's row sums, o's way out of the kernel's layout
    _attention_passes(
        text, ("net.attention.window", "net.attention.full"), 5,
        ATTENTION_GB["grouped_query"], 2 * 4096 * 32 * 128)


def _compile_latent_step(v5e, f):
    """The fused step over ONE whole 8,192-token window of the
    latent-attention net: the train state (16 B a parameter) beside
    the step's temporaries fits the chip, neither the policy's nor the
    module's logits exist whole, and all six attentions (the module's
    among them) run as the fused kernel at query-key heads of 192
    against value heads of 128, under the latent scope."""
    import jax

    from handyrl_tpu.telemetry import devtrace

    replay = f["replay"]
    assert f["buffers"]["obs"] is None          # the token rides `steps`
    assert f["buffers"]["steps"].shape == (512 * 8192 + 8192, 8)
    assert (replay.run_round, replay.max_run) == (8192, 2)
    compiled = _lower_replay_step(v5e, f).compile()
    mem = compiled.memory_analysis()
    n_params = sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree.leaves(f["params"]))
    assert n_params == 680_441_856
    assert mem.argument_size_in_bytes >= 12 * n_params
    # 13.61 GB (arguments 8.3, temporaries 5.3): a SwiGLU's two products
    # by x kept across a layer's rematerialisation (13.08 GB with both
    # made again)
    assert _footprint(mem) < 13.8e9, _footprint(mem)
    text = compiled.as_text()
    assert not _walked_targets(text)
    positions, vocab = 8192, 16160
    whole = [m.group(1) for m in map(_DEFINED.match, text.splitlines())
             if m and int(m.group(2)) == positions
             and f",{vocab}]" in m.group(0)]
    assert not whole, whole
    assert _fused_kernels(text) == {
        ("net.attention.latent", "forward", "splash_mqa_fwd_residuals"): 6,
        ("net.attention.latent", "backward",
         "splash_mqa_dkv_no_residuals"): 6}
    # five expert stacks (the module's among them) as grouped products;
    # a layer's rematerialisation runs two of the three (no norm after
    # the branch here: nothing coming back reads the branch's result)
    assert _grouped_kernels(text) == {
        ("net.moe.experts", "forward", "gmm"): 15,
        ("net.moe.experts", "backward", "gmm"): 25,
        ("net.moe.experts", "backward", "tgmm"): 15}, _grouped_kernels(text)
    assert not _held_stacks(text, positions, 16, 768)
    # the dense layer's and the five shared experts' SwiGLU keep their
    # two products by x (``KEPT_NAMES``; the third's result nothing
    # coming back reads): one product 7,168 / 768 wide a SwiGLU coming
    # back, the hidden's gradient (3 where the layer's
    # rematerialisation made the two again)
    products = _products(text)
    assert products["net.mlp", "forward"] == {
        "bf16[8192,7168]": 2, "bf16[8192,2048]": 1}
    assert products["net.mlp", "backward"] == {
        "bf16[8192,7168]": 1, "bf16[8192,2048]": 2,
        "bf16[2048,7168]": 2, "bf16[7168,2048]": 1}
    shared = products["net.moe.shared", "backward"]
    assert (shared["bf16[8192,768]"], shared["bf16[8192,2048]"]) == (5, 10)
    # q alone takes a pass of its own (k is assembled from two arrays)
    assert _fused_kernels(text, "turn_pass") == {
        ("net.attention.latent", "forward", "turn_pass"): 6,
        ("net.attention.latent", "backward", "turn_pass"): 12}
    assert text.count('custom_call_target="tpu_custom_call"') == 12 + 55 + 18
    assert any("/mtp/layer/attn/net.attention.latent/" in op_name
               for op_name in devtrace.op_names(text).values())
    # batch 1, 32 heads each its own key-value head: a block of scores
    # would be f32[1,32,1,512,Tk]
    scores = [found for found in re.findall(
        r"= (f32\[1,32,1,(\d+),(\d+)\])", text)
        if int(found[1]) >= 512 and int(found[2]) >= 512]
    assert not scores, scores[:4]
    # the module's own operations have their scope
    assert any(devtrace.net_scope_of(op_name) == "net.mtp"
               for op_name in devtrace.op_names(text).values())
    # six latent attentions: q in one pass (its rotation inside), v
    # written once by its own product; left outside: dq's partial sums,
    # k's assembly, o's way out, the kernels' columns put half-split
    _attention_passes(text, ("net.attention.latent",), 6,
                      ATTENTION_GB["latent"], 8192 * 32 * 64)


def _compile_hybrid_step(v5e, f):
    """The fused step over ONE whole 4,096-token window of the
    delta-rule hybrid: the train state of 766 M parameters (16 B each)
    beside the step's temporaries fits the chip -- the wall the
    configuration was cut against -- the logits never exist whole, the
    one full attention runs as the fused kernel at 15 heads each its
    own key-value head, every delta layer's recurrence is a loop
    over chunks under its own scope, forward, rematerialised and
    coming back, and a layer's rematerialisation multiplies by no
    weights of its MLP nor of a delta mixer's q, k, v, gate and
    ``Wo`` projections a second time (``KEPT_NAMES``)."""
    import jax

    from handyrl_tpu.telemetry import devtrace

    assert f["buffers"]["obs"] is None          # the token rides `steps`
    assert f["buffers"]["steps"].shape == (1024 * 4096 + 4096, 8)
    compiled = _lower_replay_step(v5e, f).compile()
    mem = compiled.memory_analysis()
    n_params = sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree.leaves(f["params"]))
    assert n_params == 766_245_786
    assert mem.argument_size_in_bytes >= 12 * n_params
    # 12.82 GB (arguments 9.3, temporaries 3.5) with the named products
    # kept across the layers' rematerialisation (12.61 GB with every
    # one made again; 13.16 GB with ``Wo``'s result made again alone)
    assert _footprint(mem) < 13.1e9, _footprint(mem)
    text = compiled.as_text()
    assert not _walked_targets(text)
    positions, vocab = 4096, 12544
    whole = [m.group(1) for m in map(_DEFINED.match, text.splitlines())
             if m and int(m.group(2)) == positions
             and f",{vocab}]" in m.group(0)]
    assert not whole, whole
    assert _fused_kernels(text) == {
        ("net.attention.full", "forward", "splash_mqa_fwd_residuals"): 1,
        ("net.attention.full", "backward",
         "splash_mqa_dkv_no_residuals"): 1}
    # the q/k norm spans the projection: no pass of the module's own
    assert not _fused_kernels(text, "turn_pass")
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    op_names = devtrace.op_names(text).values()
    scopes = {devtrace.net_scope_of(op_name) for op_name in op_names}
    assert {"net.delta.project", "net.delta.scan", "net.delta.out",
            "net.attention.full", "net.mlp", "net.head"} <= scopes
    assert not {"net.moe.experts", "net.attention.window"} & scopes
    # the scan over chunks: a loop a delta layer in each of the forward
    # pass, its rematerialisation and the way back, under the scan's
    # scope and no other
    loops = {}
    for op_name in op_names:
        if op_name.endswith("/while") and "net.delta" in op_name:
            assert devtrace.net_scope_of(op_name) == "net.delta.scan"
            phase = devtrace.phase_of(op_name)
            if "/rematted_computation/" in op_name:
                phase = "rematerialised"
            loops[phase] = loops.get(phase, 0) + 1
    assert min(loops[phase] for phase in (
        "forward", "rematerialised", "backward")) >= 3, loops
    # four MLPs: three products each going forward, and coming back the
    # gradients of x (by w1 and by w3), of the hidden and of the three
    # kernels: 24 products where the layer's rematerialisation made
    # them 36
    products = _products(text)
    assert products["net.mlp", "forward"] == {
        "bf16[4096,11008]": 8, "bf16[4096,3840]": 4}
    assert products["net.mlp", "backward"] == {
        "bf16[4096,11008]": 4, "bf16[4096,3840]": 8,
        "bf16[3840,11008]": 8, "bf16[11008,3840]": 4}
    # three delta mixers: q, k (1,440 wide), v and the gate (2,880)
    # are projected going forward alone, and so is ``Wo``'s result
    assert products["net.delta.project", "forward"].items() >= {
        "bf16[4096,1440]": 6, "bf16[4096,2880]": 6}.items()
    assert not {"bf16[4096,1440]", "bf16[4096,2880]"} & set(
        products["net.delta.project", "backward"])
    assert products["net.delta.out", "forward"] == {
        "bf16[4096,3840]": 3}
    assert products["net.delta.out", "backward"] == {
        "bf16[4096,2880]": 3, "bf16[2880,3840]": 3}


@pytest.mark.parametrize("geometry", ["flagship", "geister"])
def test_a_board_step_is_the_program_it_was(
        geometry, v5e, request, monkeypatch):
    """``geese32`` and ``geister_drc`` train on 8 moments: their fused
    step walks its value targets as it always did, and its text is the
    text lowered with the constant above every length (the program as
    it stood before the targets had a second schedule)."""
    from handyrl_tpu.ops import targets

    f = request.getfixturevalue(geometry)
    with targets.noting() as notes:
        text = _lower_replay_step(v5e, f).as_text()
    assert notes and all(
        note == {"form": "sequential", "length": 7} for note in notes)
    monkeypatch.setattr(targets, "LOG_DEPTH_ABOVE", 10 ** 9)
    assert _lower_replay_step(v5e, f).as_text() == text


@pytest.mark.parametrize("program,geometry", [
    (_compile_replay_step, "flagship"), (_compile_replay_step, "geister"),
    (_compile_ring_append, "flagship"),
    (_compile_service_forward, "flagship"), (_compile_dp4_step, "flagship"),
    (_compile_sequence_step, "sequence"),
    (_compile_latent_step, "latent_sequence"),
    (_compile_hybrid_step, "hybrid_sequence"),
], ids=["replay_step", "replay_step_geister", "ring_append",
        "service_forward", "dp4_step", "replay_step_sequence",
        "replay_step_latent", "replay_step_hybrid"])
def test_main_path_compiles_for_a_described_v5e(
        program, geometry, v5e, request):
    program(v5e, request.getfixturevalue(geometry))


# -- a dead trainer is a failed run ------------------------------------

TINY_TRAIN = {
    "turn_based_training": True, "observation": False, "gamma": 0.8,
    "forward_steps": 4, "burn_in_steps": 0, "compress_steps": 4,
    "entropy_regularization": 0.1, "entropy_regularization_decay": 0.1,
    "update_episodes": 15, "batch_size": 4, "minimum_episodes": 10,
    "maximum_episodes": 200, "epochs": 2, "num_batchers": 1,
    "eval_rate": 0.1, "worker": {"num_parallel": 1}, "lambda": 0.7,
    "policy_target": "TD", "value_target": "TD", "seed": 5,
    "telemetry": False, "metrics_path": "metrics.jsonl",
}


def test_train_exits_nonzero_when_the_trainer_thread_died(
        tmp_path, monkeypatch, capsys):
    """``main.py --train`` on the default (IMPALA) path with a fused
    step that fails as a refused compile or an OOM would: the learner
    keeps its epoch cadence on the last model, as designed — and then
    the run RAISES instead of returning, so the process exits non-zero
    (before, every record read ``steps: 0`` and the exit code was 0)."""
    import yaml

    import main
    from handyrl_tpu import staging

    def refused(*args, **kwargs):
        def step(*a):
            raise RuntimeError("injected: the compiler refused the step")
        return step

    monkeypatch.setattr(staging, "make_replay_update_step", refused)
    monkeypatch.chdir(tmp_path)
    monkeypatch.setattr(sys, "argv", ["main.py", "--train"])
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path / "cache"))
    (tmp_path / "config.yaml").write_text(yaml.safe_dump({
        "env_args": {"env": "TicTacToe"}, "train_args": TINY_TRAIN,
        "worker_args": {"server_address": "", "num_parallel": 1}}))

    with pytest.raises(RuntimeError, match="dead trainer") as err:
        main.main()
    assert "compiler refused" in str(err.value.__cause__)
    records = [json.loads(line) for line in
               (tmp_path / "metrics.jsonl").read_text().splitlines()]
    assert [r["steps"] for r in records] == [0, 0]   # the old symptom
    assert "serving the last model unchanged" in capsys.readouterr().out


# -- the compile cache stays put ---------------------------------------

_REPORT = """
import json, os, subprocess, sys
from handyrl_tpu.utils.compile_cache import configure_compile_cache
chosen = configure_compile_cache()
import jax
child = subprocess.run(   # a fresh interpreter reads it at import jax
    [sys.executable, "-c", "import os; "
     "print(os.environ.get('JAX_COMPILATION_CACHE_DIR'))"],
    capture_output=True, text=True, check=True).stdout.strip()
print(json.dumps({"chosen": chosen, "child": child,
                  "jax": jax.config.jax_compilation_cache_dir}))
"""


def _cache_report(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c", _REPORT], cwd=REPO, env=env,
        capture_output=True, text=True, check=True, timeout=120)
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("env_dir", [None, "/somewhere/else/jax_cache"],
                         ids=["unset", "set"])
def test_compile_cache_is_one_fixed_place(env_dir):
    """JAX_COMPILATION_CACHE_DIR set: that directory and no other, in
    this process and in a fresh interpreter it starts.  Unset: the same
    in-checkout path from two separate processes — never a temp name —
    and their children land on it too."""
    first, second = _cache_report(env_dir), _cache_report(env_dir)
    expect = env_dir or str(REPO / ".jax_cache")
    for report in (first, second):
        assert report == {"chosen": expect, "child": expect,
                          "jax": expect}
