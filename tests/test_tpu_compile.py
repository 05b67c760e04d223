"""Compile the main path's pallas kernels for a DESCRIBED v5e (no chip).

The TPU compiler is installed without a device: it compiles for a topology
that is described, not attached, and refuses what the chip would refuse —
a slice off the tiling, too much VMEM, a Mosaic kernel left to the SPMD
partitioner. Interpret-mode tests cannot see any of that. A compile that
passes is NOT a chip run: nothing executes here.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from tepdist_tpu.ops.pallas.flash_attention import flash_attention


@pytest.fixture(scope="module")
def v5e_devices():
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip (the next one warns): keep these
    # out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


# (B, H, T, D) of the main path: GPT-2 117M at batch 8, GPT-2 1.5B (25
# heads) at micro batch 4, and a 32-head x 128 long-sequence shape.
@pytest.mark.parametrize("shape", [(8, 12, 1024, 64), (4, 25, 1024, 64),
                                   (2, 32, 2048, 128)],
                         ids=lambda s: "x".join(map(str, s)))
def test_flash_kernels_compile_for_v5e(v5e_devices, shape):
    """Forward, dQ and dK/dV kernels (via jax.grad), bf16, not interpreted."""
    one_chip = SingleDeviceSharding(v5e_devices[0])
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, interpret=False)
                       .astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3


def test_planned_step_with_kernel_in_scan_compiles_for_mesh(v5e_devices):
    """The SPMD path over four chips: XLA refuses to partition a Mosaic
    kernel ("wrap the call in a shard_map"), so SpmdTransform binds every
    pallas_call under one — including those inside the gradient-
    accumulation scan, which only a re-trace of the scan body reaches."""
    from tepdist_tpu.core.mesh import MeshTopology
    from tepdist_tpu.parallel.auto_parallel import auto_parallel
    from tepdist_tpu.parallel.sync_free import build_ga_step

    B, H, T, D = 4, 2, 128, 64
    tx = optax.sgd(0.1)

    def loss_fn(w, x):
        q = (x @ w).reshape(-1, T, H, D).transpose(0, 2, 1, 3)
        return jnp.mean(flash_attention(q, q, q, interpret=False)
                        .astype(jnp.float32) ** 2)

    def apply_fn(w, s, g):
        updates, s = tx.update(g, s, w)
        return optax.apply_updates(w, updates), s

    w = jnp.ones((H * D, H * D), jnp.bfloat16)
    x = jnp.ones((B, T, H * D), jnp.bfloat16)
    step = build_ga_step(lambda w, x: jax.value_and_grad(loss_fn)(w, x),
                         apply_fn, num_micro_batches=2)
    plan = auto_parallel(step, MeshTopology([("data", 4)]), w, tx.init(w), x)
    fn = plan.executable(devices=v5e_devices)
    args = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
            for v in plan.graph.invars]
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()
