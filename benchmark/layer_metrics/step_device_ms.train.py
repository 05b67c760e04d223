"""Device milliseconds of one optimizer step: mean run of the program the
plan names ``jit_tepdist_train_step`` on the ``XLA Modules`` line, in the
traced window."""

NAME, UNIT, LAYER = "step_device_ms.train", "ms", "device"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"

PROGRAM = "jit_tepdist_train_step"


def read(trace, host, cell):
    runs = trace.module_runs(lambda name: name.startswith(PROGRAM))
    return 1e3 * sum(runs) / len(runs) if runs else None
