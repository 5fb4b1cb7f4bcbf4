"""handyrl_tpu — a TPU-native distributed RL framework.

A ground-up JAX/XLA re-design of the capabilities of HandyRL
(reference: /root/reference): an IMPALA-style learner/actor system for
competitive multi-player games, with policy-gradient training and
off-policy corrections (Monte-Carlo, TD(lambda), V-Trace, UPGO).

Design stance (TPU-first, not a port):
  * the learner is a single jitted ``update_step`` — RL targets are
    backward recursions of composable maps, walked by a reverse
    ``lax.scan`` where the time axis is short and composed log-depth
    (``lax.associative_scan``) where it is long, since a walked moment
    costs its own latency; the RNN time loop is a ``lax.scan``, and all
    multi-player/turn masking is static-shape mask algebra;
  * device parallelism is a ``jax.sharding.Mesh`` with data-parallel
    batch sharding and XLA-inserted ICI collectives (the reference uses
    single-process ``nn.DataParallel``: /root/reference/handyrl/train.py:341);
  * actors remain CPU processes (games are Python) speaking a
    framed-message control plane, shipping compressed trajectories into
    a host-side replay buffer that feeds a device prefetch queue.
"""

__version__ = "0.1.0"
