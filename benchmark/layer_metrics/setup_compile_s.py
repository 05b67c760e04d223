"""Seconds of set-up that JAX spent turning the plan's step program into an
executable: the program's ``lower:compile`` spans (its compile counter's
observations while the recorder is on, ``telemetry/compiles.py``) whose
``program`` names the step program, all three phases: its trace under
``jit``, its lowerings to MLIR and its backend compiles, where a read from
the persistent cache counts as a (short) compile. The step is lowered more
than once in a set-up (the explored plan's post-check, the first step, the
benchmark's own look at the compiled step); all of it is the lowering layer's
work and all of it is in ``setup_s``.

The counter itself is process-wide: it also holds the benchmark's own
programs (weights, token batches) and the planner's traces, which
``plan_trace_s`` reads. Those are printed, by program, and are no part of
the metric. Another cut of ``setup_s`` than the ``plan_*`` spans: a compile
lies inside whichever span caused it."""

NAME, UNIT, LAYER, MOVES = "setup_compile_s", "s", "lowering", "setup_s"
KINDS = ("train",)
SOURCE = "program_span"

STEP_PROGRAM = "tepdist_train_step"     # ``SpmdTransform.executable`` names it


def split(spans, program: str = STEP_PROGRAM) -> tuple:
    """(seconds by phase of the ``lower:compile`` spans whose program's
    name holds ``program``, seconds by program name of the others)."""
    ours, others = {}, {}
    for s in spans:
        if s["name"] != "lower:compile":
            continue
        name = str(s["args"].get("program", ""))
        into, key = (ours, s["args"].get("phase")) if program in name \
            else (others, name)
        into[key] = into.get(key, 0.0) + s["dur"] * 1e-6
    return ours, others


def read(trace, host, cell):
    ours, others = split(host.get("program_spans") or ())
    if not ours:
        return None
    stats = host.get("program_compiles") or {}
    print("program compile counter at the window's opening, all programs "
          "of the process: " + str({k: round(v, 4)
                                    for k, v in stats.items()}), flush=True)
    print(f"lower:compile spans of {STEP_PROGRAM} by phase (s): "
          + str({k: round(v, 4) for k, v in ours.items()})
          + f"; of {len(others)} other programs {sum(others.values()):.4f},"
          " the longest: " + str(
              {k: round(v, 3) for k, v in sorted(
                  others.items(), key=lambda kv: -kv[1])[:8]}), flush=True)
    return sum(ours.values())
