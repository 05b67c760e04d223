"""Share of the device's idle seconds in the traced window that lie under a
span of the program (``tepdist:<name>`` on the profiler's clock), each
moment laid under the innermost span: what the program can say about why
the device waited. The seconds by span are printed on an earlier line."""

from benchmark.lib import intervals as iv
from benchmark.layer_metrics import _program_spans

NAME, UNIT, LAYER = "idle_attributed_share.train", "%", "runtime"
MOVES = "train_tokens_per_s_chip"
KINDS = ("train",)
SOURCE = "device_trace"


def _under(gaps, spans) -> float:
    """Seconds of ``gaps`` that ``spans`` cover."""
    return iv.total(gaps) - iv.total(iv.subtract(gaps, spans))


def read(trace, host, cell):
    lines = _program_spans.within(_program_spans.traced(cell), trace.window)
    spans = _program_spans.self_intervals(lines)
    if not spans:
        return None
    idle = under = 0.0
    by_span = {}
    covered = [i for _, ivs in spans for i in ivs]
    for d in trace.devices:
        gaps = iv.gaps(d.busy, *trace.window)
        idle += iv.total(gaps)
        under += _under(gaps, covered)
        for name, ivs in spans:
            by_span[name] = by_span.get(name, 0.0) \
                + _under(gaps, ivs) / len(trace.devices)
    print("device idle seconds by innermost program span: " + str(
        {k: round(v, 6) for k, v in sorted(by_span.items(),
                                           key=lambda kv: -kv[1])})
          + f", of {idle / len(trace.devices):.6f} idle", flush=True)
    return 100.0 * under / idle if idle > 0 else None
