"""Find the highest arrival rate a serving cell sustains, once, on the chip.

    python benchmark/sweep.py --workload <name> --rates 4,8,12,16 --seconds 20 --seed 1

One process and one engine; for each rate the cell's own mix runs open loop
for ``--seconds`` and is then drained. A rate is sustained when the backlog
does not grow: every request finishes within the drain and the time to
first token of the last fifth of the window is no worse than twice that of
the first fifth after the ramp. The cell's traffic file then fixes its rate
at four fifths of the knee; ``run.py`` never searches.
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
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--seed", type=int, default=1)
    args = parser.parse_args(argv)

    from benchmark.lib import cells, device, openloop
    from benchmark.lib.host import CompileWatch, HostLog
    cell = cells.load_cell(args.workload, ROOT)
    devices = device.own_chips(cell.chips)
    device.configure_cache(ROOT)
    builder, driver = cells.builder_for(cell), cells.driver_for(cell)
    host, compiles = HostLog(), CompileWatch()
    params, system = driver.build(cell, builder, args.seed, host)
    del params
    rates = [float(r) for r in args.rates.split(",")]
    mix = dict(cell.traffic["mix"], rate_per_s=max(rates))
    print("warm-up: " + str(driver.warm_up(
        system, openloop.make_schedule(mix, args.seconds),
        cell.traffic)), flush=True)
    drain_s = float(cell.traffic["drain_seconds"])
    for i, rate in enumerate(rates):
        mix = dict(cell.traffic["mix"], rate_per_s=rate)
        schedule = openloop.make_schedule(mix, args.seconds)
        for r in schedule:
            r.rid = f"s{i}-{r.rid}"
        system.decode_calls = system.decode_rows = 0
        mark = compiles.n
        openloop.run_open_loop(system, schedule, args.seconds, drain_s)
        s = openloop.summarise(schedule, args.seconds, drain_s)
        for r in schedule:               # leave nothing for the next rate
            if r.done_s is None:
                system.engine.cancel(r.rid)
        fifth = args.seconds / 5
        early = [(r.first_s - r.due_s) * 1e3 for r in schedule
                 if r.first_s is not None and fifth <= r.due_s < 2 * fifth]
        late = [(r.first_s - r.due_s) * 1e3 for r in schedule
                if r.first_s is not None and r.due_s >= 4 * fifth]
        s.update(rate_per_s=rate, compiles=compiles.since(mark),
                 rows_mean=system.decode_rows / max(1, system.decode_calls),
                 ttft_p50_early_ms=openloop.percentile(early, 0.5)
                 if early else None,
                 ttft_p50_late_ms=openloop.percentile(late, 0.5)
                 if late else None,
                 offered_tokens_per_s=sum(r.max_new_tokens for r in schedule)
                 / args.seconds,
                 device=devices[0].device_kind)
        print(json.dumps(s), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
