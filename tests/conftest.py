"""Test harness: force JAX onto CPU with 8 virtual devices.

This is the TPU-native analog of "multi-node without a cluster": every
sharding/collective test runs on a virtual 8-device mesh so the full
multi-chip path compiles and executes in CI with no TPU attached.
Both variables must be set before jax is first imported.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402  (after the env setup on purpose)


@pytest.fixture(autouse=True)
def _disarm_telemetry():
    """Telemetry state is process-global (configured by Learner init
    from its args): start every test disarmed so a learner-driven test
    cannot leak armed tracing — and its trace stamps — into unrelated
    tests that assert exact wire formats."""
    from handyrl_tpu import telemetry

    telemetry.configure(enabled=False)
    yield


@pytest.fixture()
def chips_path(monkeypatch):
    """What ``lax.platform_dependent`` would pick for a TPU, taken here
    in a sequence net's module: the chip's kernels, their bodies run as
    plain JAX (``interpret``)."""
    from handyrl_tpu.models import sequence_net

    monkeypatch.setattr(
        sequence_net.lax, "platform_dependent",
        lambda *operands, default, tpu: tpu(*operands, interpret=True))
