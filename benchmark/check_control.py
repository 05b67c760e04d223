"""Read the two numbers every ``correct`` limit is set from, on the chip.

    python benchmark/check_control.py --workload <name> --seeds 1,2,... --control 1,2,3

For each seed: the error of the program's outputs against the float32
reference (sound runs). For each control seed: the error of the control, the
reference computed one precision step below the configuration's and put in
the program's place. A limit goes above the largest sound reading and below
the smallest control reading; where these are less than three times apart no
limit holds. No timed window; one process for all seeds.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control", default="")
    args = parser.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = [int(s) for s in args.control.split(",") if s]

    from benchmark.lib import cells, device
    from benchmark.lib.host import HostLog
    cell = cells.load_cell(args.workload, ROOT)
    devices = device.own_chips(cell.chips)
    device.configure_cache(ROOT)
    builder, driver = cells.builder_for(cell), cells.driver_for(cell)
    rows = []
    for row in driver.readings(cell, builder, devices,
                               sorted(set(seeds) | set(control)), control,
                               HostLog()):
        print(json.dumps(row), flush=True)
        rows.append(row)
    keys = [k for k in rows[0] if k not in ("seed", "side")]
    summary = {"workload": cell.name,
               "device": {"platform": devices[0].platform,
                          "kind": devices[0].device_kind,
                          "count": len(devices)}}
    for k in keys:
        sound = [r[k] for r in rows if r["side"] == "program"]
        ctl = [r[k] for r in rows if r["side"] == "control"]
        summary[k] = {"sound_max": max(sound), "sound_n": len(sound),
                      "control_min": min(ctl) if ctl else None,
                      "control_n": len(ctl),
                      "ratio": min(ctl) / max(sound)
                      if ctl and max(sound) > 0 else None}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
