"""Launch tepdist servers from a cluster config (reference: launch_worker.sh
— jq over config_*worker_template.json, sets CLUSTER_SPEC and starts
grpc_service_gpu per worker). This Python version launches the local
worker(s) of the config matching --task_index, or all localhost workers.

Supported layout on real hardware: ONE server per host, owning all of that
host's chips (config_4worker_template.json — four hosts, one worker each).
A chip belongs to one process at a time and every worker started here gets
the same environment, so several workers on one host would all claim every
chip: that is refused unless the environment pins jax to the CPU
(``JAX_PLATFORMS=cpu``, the virtual-mesh rehearsal). Each server is told its
platform by name, so a missing chip is an error and not a CPU server."""

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.abspath(_os.path.join(
    _os.path.dirname(_os.path.abspath(__file__)), "..")))

import argparse
import json
import os
import subprocess
import sys


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True)
    parser.add_argument("--task_index", type=int, default=-1,
                        help="-1 = every localhost worker")
    args = parser.parse_args()
    with open(args.config) as f:
        spec = json.load(f)
    from tepdist_tpu.rpc.local_server import server_platform

    if args.task_index >= 0:
        local = [w for w in spec["workers"]
                 if w.get("task_index") == args.task_index]
    else:
        local = [w for w in spec["workers"]
                 if w["ip"] in ("127.0.0.1", "localhost")]
    platform = server_platform()
    if len(local) > 1 and platform != "cpu":
        raise SystemExit(
            f"{len(local)} workers on this host would each claim every "
            f"{platform} chip; run one server per host owning all its "
            "chips (config_4worker_template.json), or rehearse with "
            "JAX_PLATFORMS=cpu")
    procs = []
    for w in local:
        env = dict(os.environ)
        env["CLUSTER_SPEC"] = json.dumps(spec)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "tepdist_tpu.rpc.server",
             "--port", str(w["port"]), "--platform", platform,
             "--task_index", str(w.get("task_index", 0))],
            env=env))
        print(f"launched worker task_index={w.get('task_index')} "
              f"port={w['port']} pid={procs[-1].pid}")
    for p in procs:
        p.wait()


if __name__ == "__main__":
    main()
