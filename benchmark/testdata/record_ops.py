"""Record an excerpt of a traced step's operations for the readers' tests.

After ``python3 benchmark/run.py --workload CELL --seed N --seconds S
--trace 1`` (which leaves its trace under ``.bench_trace/CELL``), on the same
machine:

    python3 benchmark/testdata/record_ops.py --cell CELL --match A,B
        --top 8 --out FILE --source "what run this was"

writes ``{"source", "window_s", "ops": [[HLO text, seconds, calls], ...]}``:
every operation whose text holds one of the ``--match`` strings and the
``--top`` longest of the rest (``--all 1``: every operation). The tests read
it back through a ``SavedTrace`` (``tests/test_jamba_cell.py``).
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--match", default="")
    ap.add_argument("--top", type=int, default=8)
    ap.add_argument("--all", type=int, default=0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--source", default="")
    args = ap.parse_args(argv)

    from benchmark.lib import tracing
    summary = tracing.reduce_trace(
        os.path.join(ROOT, ".bench_trace", args.cell))
    marks = [m for m in args.match.split(",") if m]
    ops = sorted(summary.ops(lambda t: True), key=lambda op: -op[1])
    if not args.all:
        named = [op for op in ops if any(m in op[0] for m in marks)]
        rest = [op for op in ops if op not in named][:args.top]
        ops = named + rest
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"source": args.source, "window_s": summary.window_s,
                   "ops": [list(op) for op in ops]}, f, indent=1)
    print(f"{len(ops)} operations of a {summary.window_s:.4f} s window "
          f"-> {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
