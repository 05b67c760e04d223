"""A TPU that is described, not attached: the TPU compiler is installed
without a device and compiles for such a topology, refusing what the chip
would refuse (``tests/test_tpu_compile.py``, ``tools/same_ops.py``). Nothing
runs, and a compile that passes is not a chip run."""

import contextlib
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp


@contextlib.contextmanager
def described_v5e(topology: str = "v5e:2x2"):
    """The devices of a v5e that is described, not attached (the tests'
    too). A compile for it is written to the persistent cache but cannot be
    read back without the chip (the next one warns): the cache is off."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topology)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield list(topo.devices)
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()
