"""Test harness: force an 8-device virtual CPU platform so every sharding /
collective path is exercised without TPU hardware (the reference's weak spot —
SURVEY.md §4 notes multi-worker paths were only testable on real clusters; we
test them on a virtual mesh from day one)."""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags += " --xla_force_host_platform_device_count=8"
# The suite compiles some thousands of small programs for the CPU and runs
# each once or twice: LLVM's optimisation of them was a quarter of a model
# file's time and buys nothing at these sizes. Level 1 and not 0: at 0 the
# instruction selector changes, fused multiply-adds form in one program and
# not in its twin, and two bit-for-bit comparisons fail (ROADMAP D17).
for _flag in ("--xla_backend_optimization_level=1",
              "--xla_llvm_disable_expensive_passes=true"):
    if _flag.split("=")[0] not in _flags:
        _flags += " " + _flag
os.environ["XLA_FLAGS"] = _flags.strip()
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


# Under ``--dist loadfile`` a file is one worker's chain. pytest-xdist deals
# the files out by their number of cases, most first, and keeps the collected
# order among files of equal count: that is all this order decides. It puts
# the files of two or three long cases (the walks, the described-chip
# siblings) ahead of the short files that have as few, which were the run's
# tail (ROADMAP D17). Turning xdist's re-sorting off so that this were the
# whole order was tried at PR 56 and bought nothing: 1,376 s against 1,364
# the same hour. By the summed case seconds of a run under six workers (60 s
# a file and over; PR 56's runs: 5,539 s of cases on a quiet hour, 923 s a
# worker at best, and 7,267 s on a loaded one).
_LONGEST_FIRST = (
    "test_sarvam_mla.py", "test_tpu_compile.py", "test_xing.py",
    "test_afmoe.py",
    "test_minicpm_sala.py", "test_mellum.py", "test_jamba.py",
    "test_stack_in_place.py", "test_nemotron_h.py", "test_kimi_linear.py",
    "test_kimi_linear_walk.py", "test_kda_attention.py",
    "test_multiworker.py", "test_qwen3_next.py", "test_zaya.py",
    "test_olmoe.py", "test_ssd_attention.py", "test_nemotron_h_walk.py",
    "test_tpu_compile_nemotron_h.py", "test_tpu_compile_xing.py",
    "test_qwen3_next_walk.py",
    "test_models.py",
    "test_attn_kept.py", "test_gdn_attention.py", "test_serving_chaos.py",
    "test_sequence_parallel.py", "test_zaya_walk.py", "test_ga_fused.py",
    "test_serving_fleet.py", "test_serving_paged.py",
    "test_tpu_compile_qwen3_next.py", "test_rpc_explore.py",
    "test_selective_scan.py", "test_rows_sum.py", "test_tpu_compile_kimi.py",
    "test_evaluator_measured.py", "test_causal_conv.py",
    "test_subgraph_dp.py",
)


def pytest_collection_modifyitems(items):
    rank = {name: i for i, name in enumerate(_LONGEST_FIRST)}
    items.sort(key=lambda item: rank.get(item.path.name, len(rank)))

