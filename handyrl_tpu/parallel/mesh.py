"""Mesh construction and sharding specs.

One place decides how arrays lay out over devices; everything else just
asks for a sharding.  Design follows the standard JAX recipe: build a
``Mesh``, annotate shardings with ``NamedSharding``/``PartitionSpec``,
and let XLA insert the collectives.
"""

import threading
from dataclasses import dataclass, field
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Two threads that each launch a program with collectives over the same
# devices (the trainer's step and the inference service's GSPMD forward
# share the training mesh) must not interleave their per-device
# enqueues: device 0 would run step-then-forward, device 1
# forward-then-step, and each program's collective would wait for a
# participant queued behind the other's.  JAX leaves that ordering to
# its caller.
_LAUNCH_LOCK = threading.Lock()


class OrderedLaunch:
    """A jitted callable whose launches over ``mesh`` are ordered
    process-wide; without a mesh it is the callable itself.

    The lock is held only for the enqueue of an executable that is
    already compiled: a signature not seen before is lowered and
    compiled ahead of time OUTSIDE it, so a cold compile of the
    trainer's step (or a ring-growth recompile) never holds up the
    service's forwards, nor the reverse.  ``key(args)`` is a cheap
    hint that tells apart the signatures a caller alternates between
    (the service's batch buckets); the executable's own argument
    check, which runs before anything is enqueued or donated, decides.
    """

    def __init__(self, fn, mesh, key=None):
        self._fn = fn
        self._ordered = mesh is not None
        self._key = key
        self._compiled = {}

    def _compile(self, key, args):
        compiled = self._compiled[key] = self._fn.lower(*args).compile()
        return compiled

    def __call__(self, *args):
        if not self._ordered:
            return self._fn(*args)
        if not hasattr(self._fn, "lower"):
            with _LAUNCH_LOCK:      # no jit: nothing to compile ahead
                return self._fn(*args)
        key = self._key(args) if self._key is not None else None
        compiled = self._compiled.get(key) or self._compile(key, args)
        try:
            with _LAUNCH_LOCK:
                return compiled(*args)
        except (TypeError, ValueError):
            # compiled for another signature (a ring growth)
            compiled = self._compile(key, args)
        with _LAUNCH_LOCK:
            return compiled(*args)

    def __getattr__(self, name):
        return getattr(self._fn, name)

# canonical axis order: data, sequence(time), tensor(model)
AXES = ("dp", "sp", "tp")


@dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape, e.g. ``MeshSpec(dp=4, tp=2)``.

    Axis sizes of 1 are kept in the mesh (so sharding specs never need
    to special-case a missing axis); total size must divide the device
    count.

    ``fsdp`` is a RULE toggle, not an axis: with it set, parameters and
    optimizer state additionally shard over the existing ``dp`` axis
    (ZeRO-style fully-sharded data parallelism) — XLA inserts the
    weight all-gathers and gradient reduce-scatters.
    """

    dp: int = 1
    sp: int = 1
    tp: int = 1
    fsdp: bool = False

    @classmethod
    def from_config(cls, mesh_cfg: Optional[Dict[str, int]]) -> "MeshSpec":
        mesh_cfg = dict(mesh_cfg or {})
        fsdp = bool(mesh_cfg.pop("fsdp", False))
        unknown = set(mesh_cfg) - set(AXES)
        if unknown:
            raise ValueError(f"unknown mesh axes: {sorted(unknown)}")
        return cls(fsdp=fsdp,
                   **{a: int(mesh_cfg.get(a, 1)) for a in AXES})

    @property
    def size(self) -> int:
        return self.dp * self.sp * self.tp

    def shape(self) -> Tuple[int, ...]:
        return (self.dp, self.sp, self.tp)


def make_mesh(spec: Optional[MeshSpec] = None, devices=None) -> Mesh:
    """Build a ``Mesh`` over ``devices`` (default: all visible).

    With no spec, every device goes on ``dp`` — pure data parallelism,
    the reference-parity strategy (DataParallel -> psum-over-ICI).
    """
    devices = list(devices if devices is not None else jax.devices())
    if spec is None:
        spec = MeshSpec(dp=len(devices))
    if spec.size > len(devices):
        raise ValueError(
            f"mesh {spec.shape()} needs {spec.size} devices, have "
            f"{len(devices)} — shrink the `mesh:` config axes "
            f"(dp/sp/tp) to fit the host, or launch with more devices"
        )
    if len(devices) % spec.size != 0:
        # a mesh that doesn't tile the host silently idles the
        # remainder.  Reached by an explicit `mesh:` shape OR by the
        # learner's batch-divisor default (e.g. batch 6 on 8 devices
        # -> dp=6), so the advice names both knobs
        print(f"WARNING: mesh {spec.shape()} uses {spec.size} of "
              f"{len(devices)} devices ({len(devices) - spec.size} "
              f"idle); set an explicit `mesh:` whose axes multiply to "
              f"a divisor of the device count (or make batch_size "
              f"divide evenly) to cover the host")
    dev_array = np.asarray(devices[:spec.size]).reshape(spec.shape())
    return Mesh(dev_array, AXES)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, time_axis: Optional[int] = None) -> NamedSharding:
    """Batch tensors shard their leading dim over ``dp``; optionally the
    time axis over ``sp`` (sequence parallelism for long windows)."""
    if time_axis is None:
        return NamedSharding(mesh, P("dp"))
    spec = [None] * (time_axis + 1)
    spec[0], spec[time_axis] = "dp", "sp"
    return NamedSharding(mesh, P(*spec))


# -- parameter sharding rules -------------------------------------------

def _tp_spec_for(path: Tuple[str, ...], shape: Tuple[int, ...],
                 tp_size: int, min_tp_dim: int) -> P:
    """Shard the output-feature (last) dim of large kernels over ``tp``.

    Conv kernels are (kh, kw, cin, cout) and dense kernels (cin, cout)
    in Flax — the last axis is always output features.  Small tensors
    (biases, norms, tiny heads) stay replicated: the all-gather cost
    would exceed the memory saved.
    """
    if tp_size <= 1 or not shape:
        return P()
    last = shape[-1]
    if last % tp_size != 0 or last < min_tp_dim:
        return P()
    if len(shape) < 2:
        return P()
    return P(*([None] * (len(shape) - 1) + ["tp"]))


def _fsdp_spec_for(shape: Tuple[int, ...], dp_size: int,
                   taken: P, min_fsdp_size: int) -> P:
    """Shard one dim of a large tensor over ``dp`` (ZeRO-style).

    Picks the LAST dim divisible by ``dp`` that isn't already taken by
    ``tp``; small tensors stay replicated — sharding a bias saves
    nothing and costs an all-gather.
    """
    if dp_size <= 1 or not shape:
        return taken
    if int(np.prod(shape)) < min_fsdp_size:
        return taken
    spec = list(taken) + [None] * (len(shape) - len(taken))
    for axis in range(len(shape) - 1, -1, -1):
        if spec[axis] is None and shape[axis] % dp_size == 0 \
                and shape[axis] >= dp_size:
            spec[axis] = "dp"
            return P(*spec)
    return taken


class InferenceShardings(NamedTuple):
    """The GSPMD contract of one batched inference dispatch.

    ``params`` per the :func:`param_sharding` tp/fsdp rules (so a net
    too big for one chip serves from the same layout it trains on),
    the observation batch split over ``dp`` rows, and the outputs
    scattered back on the same ``dp`` rows.  Built once per model
    structure; the service's jitted ``inference_batch`` passes these
    straight to ``jit(in_shardings=..., out_shardings=...)``.
    """

    params: Any
    obs: NamedSharding
    out: NamedSharding


def inference_shardings(mesh: Mesh, params, min_tp_dim: int = 128,
                        fsdp: bool = False,
                        min_fsdp_size: int = 4096) -> InferenceShardings:
    """Shardings for the batched inference forward over ``mesh``.

    One GSPMD program serves every actor and network client: params
    shard exactly like the learner's (:func:`param_sharding`, incl.
    the fsdp rule), each observation leaf splits its leading batch dim
    over ``dp``, and every output leaf comes back scattered on
    ``dp`` — a single-device mesh collapses all three to the
    unsharded layout, so the sharded dispatch is bit-identical there
    by construction.  The batch divisibility contract lives at the
    service (buckets are powers of two with a floor >= dp).
    """
    return InferenceShardings(
        params=param_sharding(mesh, params, min_tp_dim=min_tp_dim,
                              fsdp=fsdp, min_fsdp_size=min_fsdp_size),
        obs=NamedSharding(mesh, P("dp")),
        out=NamedSharding(mesh, P("dp")),
    )


def param_sharding(mesh: Mesh, params, min_tp_dim: int = 128,
                   fsdp: bool = False, min_fsdp_size: int = 4096):
    """NamedShardings for a params pytree.

    Default policy: replicate everything unless the mesh has a real
    ``tp`` axis, in which case wide kernels shard their output
    features.  With ``fsdp``, large tensors additionally shard one dim
    over ``dp`` — parameters and (structurally, via
    ``opt_state_sharding``) Adam moments are then fully distributed,
    cutting per-device state memory ~dp-fold.
    """
    tp_size = mesh.shape["tp"]
    dp_size = mesh.shape["dp"]

    def spec(path, leaf):
        names = tuple(getattr(p, "key", str(p)) for p in path)
        shape = np.shape(leaf)
        part = _tp_spec_for(names, shape, tp_size, min_tp_dim)
        if fsdp:
            part = _fsdp_spec_for(shape, dp_size, part, min_fsdp_size)
        return NamedSharding(mesh, part)

    return jax.tree_util.tree_map_with_path(spec, params)
