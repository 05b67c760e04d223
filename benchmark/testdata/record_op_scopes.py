"""Record an excerpt of a traced step's operations WITH their scopes, for
the tests of the readers that read a scope and not a kernel's name.

After ``python3 benchmark/run.py --workload CELL --seed N --seconds S
--trace 1`` (which leaves its trace under ``.bench_trace/CELL``), on the same
machine:

    python3 benchmark/testdata/record_op_scopes.py --cell CELL
        --words mhc_maps,mhc_read,mhc_write,mtp --match tepdist_mla,tepdist_gmm
        --top 12 --out FILE.ops.json --scopes FILE.scopes.json --source "..."

writes ``record_ops.py``'s file (``{"source", "window_s", "ops": [[HLO text,
seconds, calls], ...]}``) of every operation whose ``tf_op`` holds one of
``--words`` as a whole word (and took ``--least`` seconds or more), every
operation whose text holds one of the
``--match`` strings and the ``--top`` longest of the rest, and beside it
``{short operation name: tf_op}`` of those operations (``_scopes.
operation_scopes``' map, cut to them). An operation that is no kernel keeps
the first ``--text`` characters of its HLO text (its name and result shape).
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
    ap.add_argument("--words", default="")
    ap.add_argument("--match", default="")
    ap.add_argument("--top", type=int, default=12)
    ap.add_argument("--text", type=int, default=160)
    ap.add_argument("--least", type=float, default=0.0,
                    help="seconds under which a scoped operation is left "
                    "out")
    ap.add_argument("--out", required=True)
    ap.add_argument("--scopes", required=True)
    ap.add_argument("--source", default="")
    args = ap.parse_args(argv)

    from benchmark import trace_reduce
    from benchmark.layer_metrics import _scopes
    from benchmark.lib import tracing
    path = os.path.join(ROOT, ".bench_trace", args.cell)
    summary = tracing.reduce_trace(path)
    scopes = _scopes.operation_scopes(trace_reduce.find_xplane(path))
    words = [w for w in args.words.split(",") if w]
    word = _scopes._whole_word(words) if words else None
    marks = [m for m in args.match.split(",") if m]
    ops = sorted(summary.ops(lambda t: True), key=lambda op: -op[1])

    def scope(op):
        return scopes.get(trace_reduce.short_name(op[0]), "")

    scoped = [op for op in ops if word and word.search(scope(op))
              and op[1] >= args.least]
    named = [op for op in ops if op not in scoped
             and any(m in op[0] for m in marks)]
    rest = [op for op in ops if op not in scoped and op not in named]
    kept = scoped + named + rest[:args.top]
    for out in (args.out, args.scopes):
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"source": args.source, "window_s": summary.window_s,
                   "ops": [[op[0] if op in named else op[0][:args.text],
                            op[1], op[2]] for op in kept]}, f, indent=1)
    with open(args.scopes, "w") as f:
        json.dump({trace_reduce.short_name(op[0]): scope(op) for op in kept},
                  f, indent=1)
    print(f"{len(scoped)} scoped, {len(named)} named and "
          f"{len(kept) - len(scoped) - len(named)} more operations of a "
          f"{summary.window_s:.4f} s window -> {args.out}, {args.scopes}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
