"""The attached chips: refusal without them, peaks, memory, compile cache."""

from __future__ import annotations

import json
import os
import sys


def fail(why: str) -> "None":
    """No result line, non-zero exit."""
    print(f"benchmark: FAILED: {why}", file=sys.stderr, flush=True)
    raise SystemExit(2)


def own_chips(n: int) -> list:
    """Make this process the owner of ``n`` TPU chips or fail: there is no
    CPU fallback and no default device."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"jax found no backend: {e}")
    if devices[0].platform != "tpu":
        fail(f"needs a TPU; jax gave platform {devices[0].platform!r} "
             f"({devices[0].device_kind})")
    if len(devices) < n:
        fail(f"the cell needs {n} chips, {len(devices)} attached")
    return list(devices[:n])


def peaks_for(device_kind: str, bench_dir: str) -> dict:
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        fail(f"device kind {device_kind!r} is not in peaks.json "
             f"({sorted(table)}); an unknown chip has no default peak")
    return table[device_kind]


def configure_cache(root: str) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` if
    the environment sets it, else the fixed ``<checkout>/.jax_cache`` (the
    program's own rule, ``tepdist_tpu/core/compile_cache.py``). Every
    program is written, however short its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def program_peak_bytes(compiled) -> int:
    """Most bytes one device holds while ``compiled`` runs, by the
    compiler's own account (its buffer assignment's peak: arguments,
    results and scratch live together). The runtime's ``peak_bytes_in_use``
    counts the buffers it handed out and leaves a program's scratch out
    (PR 21 read 0.84 GiB where the compiler said 0.70 + 3.04 GiB), so the
    driver loops report the larger of the two."""
    return int(compiled.memory_analysis().peak_memory_in_bytes)


def device_record(devices, extra_peak_bytes: int = 0) -> dict:
    """``memory_peak_bytes`` is the larger of the runtime's peak on the
    fullest chip and ``extra_peak_bytes`` (``program_peak_bytes`` of the
    largest program the cell ran)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peak, int(extra_peak_bytes))}
