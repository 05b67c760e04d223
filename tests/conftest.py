"""Test harness: force an 8-device virtual CPU platform so every sharding /
collective path is exercised without TPU hardware (the reference's weak spot —
SURVEY.md §4 notes multi-worker paths were only testable on real clusters; we
test them on a virtual mesh from day one)."""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

# Also through the config, in case a plugin imported jax before this file
# set the variable: tests always run on the virtual CPU mesh.
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual cpu devices, got {len(devs)}"
    return devs


@pytest.fixture()
def mesh8(devices):
    """2x4 data x model mesh over the 8 virtual devices."""
    from tepdist_tpu.core.mesh import MeshTopology

    topo = MeshTopology([("data", 2), ("model", 4)])
    return topo.to_jax_mesh(devices)
