"""chip_smoke.py must fail without a chip, and the compile-cache helper must
leave the directory to the environment when the environment names one."""

import os
import subprocess
import sys

import jax
import pytest

from tepdist_tpu.core import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_the_cpu():
    """No accelerator: non-zero exit and no result line — the server child
    is told ``--platform tpu`` and dies, there is no CPU fallback."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "FAILED" in out.stderr


@pytest.mark.parametrize("from_env", ["/some/where/else", None],
                         ids=["variable-set", "variable-unset"])
def test_compile_cache_dir_comes_from_outside(monkeypatch, from_env):
    """Variable set: nothing is set in code (jax reads it itself). Unset:
    the fixed ``<checkout>/.jax_cache`` — no pid, time or temp name in it."""
    # The helper caches nothing for the CPU backend; this test stands in
    # for a process that owns a chip.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if from_env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", from_env)
    before = jax.config.jax_compilation_cache_dir
    try:
        used = compile_cache.configure_compile_cache()
        in_code = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if from_env is None:
        assert used == in_code == os.path.join(ROOT, ".jax_cache")
    else:
        assert used == from_env
        assert in_code == before
