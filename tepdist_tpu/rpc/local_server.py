"""Spawn the server binary next to a client that owns no accelerator.

A chip belongs to one process at a time, so a launcher that starts a local
server must keep its own process off the chip (:func:`pin_client_to_cpu`,
before the client's first device use) and hand the chip to the server by
name (``--platform``), so that a missing chip is an error and not a CPU
server. Used by the example launchers and ``chip_smoke.py``.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
from typing import Dict, Optional, Tuple

import jax

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def pin_client_to_cpu() -> None:
    """Call before the client process first touches a device."""
    jax.config.update("jax_platforms", "cpu")


def server_platform() -> str:
    """The platform a spawned server is told to own: the chip, unless the
    environment pins jax elsewhere (``JAX_PLATFORMS=cpu`` rehearsals)."""
    return os.environ.get("JAX_PLATFORMS") or "tpu"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def spawn_local_server(platform: str,
                       extra_env: Optional[Dict[str, str]] = None,
                       **popen_kwargs) -> Tuple[subprocess.Popen, int]:
    """Start ``python -m tepdist_tpu.rpc.server --platform <platform>`` on
    a free localhost port; returns (process, port). The caller stops it."""
    port = _free_port()
    env = dict(os.environ)
    env.update(extra_env or {})
    proc = subprocess.Popen(
        [sys.executable, "-m", "tepdist_tpu.rpc.server",
         "--port", str(port), "--platform", platform],
        env=env, cwd=_ROOT, **popen_kwargs)
    return proc, port
