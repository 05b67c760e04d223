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
# file's time and buys nothing at these sizes. These flags are the process's
# and say level 1; ``jax_disable_most_optimizations`` below takes the run to
# level 0, and ``optimized_programs`` puts level 1 back for a file.
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
# LLVM level 0 through the compile options (a third of the backend's compile
# seconds at level 1, which are half of a model file's). The optimized HLO,
# ``memory_analysis()`` and ``cost_analysis()`` of a CPU program read the
# same at either level: what changes is the instruction selector, so fused
# multiply-adds form in one program and not in its twin.
jax.config.update("jax_disable_most_optimizations", True)

import pytest  # noqa: E402


@pytest.fixture(scope="module")
def optimized_programs():
    """Level 1 for the file that names it (``pytestmark =
    pytest.mark.usefixtures("optimized_programs")``): a file that holds two
    programs to each other bit for bit where level 0 breaks the tie, or whose
    readings of a compiled program are pinned to the digit (the described
    chip's files: the option reaches the TPU compiler too). The value is not
    part of what ``jit``'s cache keys on, so both flips clear the caches."""
    jax.clear_caches()
    jax.config.update("jax_disable_most_optimizations", False)
    yield
    jax.config.update("jax_disable_most_optimizations", True)
    jax.clear_caches()


def _mappings() -> int:
    try:
        with open("/proc/self/maps", "rb") as f:
            return sum(1 for _ in f)
    except OSError:
        return 0


def _mapping_limit() -> int:
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            return int(f.read())
    except (OSError, ValueError):
        return 65530


@pytest.fixture(scope="module", autouse=True)
def _programs_freed_before_the_kernel_refuses_one():
    """A compiled CPU program holds three memory mappings a kernel of it
    (text, constants, data), some twenty a small program, and a worker keeps
    every program of every file it has run: after twenty minutes it stands
    at ``vm.max_map_count`` (65,530 a process), the next compile's ``mmap``
    fails and the worker dies of SIGSEGV in ``backend_compile_and_load``,
    after which xdist waits for it for ever (ROADMAP D17 (2): the planned
    steps of the walk files, which a worker runs last). So after a file,
    past a third of the limit, the worker lets its programs go."""
    yield
    if _mappings() > _mapping_limit() // 3:
        jax.clear_caches()


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


# Under ``--dist loadfile`` a file is one worker's chain, and a worker takes
# the next file of the queue when it has two cases left. pytest-xdist 3.8
# sorts that queue by the files' number of cases, most first, which puts the
# files of one to six long cases (the described-chip siblings, the walks) at
# the end of the run: the last worker of PR 63's first whole run ended 113 s
# after the mean of the six with ``test_tpu_compile_nemotron_h.py`` (193 s)
# and ``test_tpu_compile_kimi.py`` (103 s) as its last two files. With the
# re-sorting off the queue is the collected order, which the hook below
# makes longest first, and the six workers end within a short file of each
# other. By the summed case seconds of a whole run under six workers (60 s a
# file and over; PR 63's second whole run of its own tree: 6,991 s of cases,
# 1,198 s of wall, the six workers ending within 24 s of each other).
_LONGEST_FIRST = (
    "test_tpu_compile.py", "test_stack_in_place.py", "test_sarvam_mla.py",
    "test_mellum.py", "test_afmoe.py", "test_kimi_linear.py",
    "test_jamba.py", "test_minicpm_sala.py", "test_nemotron_h.py",
    "test_kimi_linear_walk.py", "test_xing.py",
    "test_tpu_compile_nemotron_h.py", "test_multiworker.py",
    "test_qwen3_next.py", "test_models.py", "test_granite_hybrid.py",
    "test_tpu_compile_kimi.py", "test_attn_kept.py", "test_zaya.py", "test_gdn_attention.py",
    "test_qwen3_next_walk.py", "test_nemotron_h_walk.py",
    "test_kda_attention.py", "test_sequence_parallel.py", "test_olmoe.py",
    "test_zaya_walk.py", "test_ssd_attention.py",
    "test_tpu_compile_xing.py", "test_rpc_explore.py",
    "test_selective_scan.py", "test_ga_fused.py", "test_subgraph_dp.py",
    "test_tpu_compile_qwen3_next.py", "test_serving_fleet.py",
    "test_serving_chaos.py", "test_evaluator_measured.py",
    "test_rows_sum.py",
)


def pytest_configure(config):
    if getattr(config.option, "loadscopereorder", False):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(items):
    rank = {name: i for i, name in enumerate(_LONGEST_FIRST)}
    items.sort(key=lambda item: rank.get(item.path.name, len(rank)))
