"""Host milliseconds a step spends before the device has its work: mean per
traced step of the program's ``step:h2d`` (flattening and ``device_put`` of
the batch) and ``step:dispatch`` (the call of the step program up to its
return) spans."""

from benchmark.layer_metrics import _program_spans

NAME, UNIT, LAYER = "step_host_ms.train", "ms", "runtime"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "program_span"


def read(trace, host, cell):
    found = _program_spans.self_seconds(_program_spans.within(
        _program_spans.traced(cell), trace.window))
    if "step" not in found:
        return None
    steps = found["step"][2]
    print("program spans of the traced window (self s, whole s, count): "
          + str({k: (round(a, 6), round(b, 6), n)
                 for k, (a, b, n) in found.items()}), flush=True)
    return 1e3 * sum(found[k][1] for k in ("step:h2d", "step:dispatch")
                     if k in found) / steps
